"""Autoregressive decode throughput on the real chip — the inference
half of the perf record (training rows come from bench_sweep).

GPT-2s bf16, prompt 128, KV-cache incremental decode
(GPTModel.decode_step inside generate's single jitted fori_loop):

    python scripts/bench_decode.py            # b=1 and b=8

Prints one RESULT row per batch: decode tok/s (new tokens only) and
per-token latency. The first call traces + compiles; the timed second
call reuses the per-model generate program cache, so the RESULT row is
pure execution.
"""
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)

import jax
import jax.numpy as jnp

from paddle_tpu.utils import compile_cache  # noqa: E402
compile_cache.enable()

import paddle_tpu as pt

t0 = time.time()


def log(m):
    print(f"[{time.time()-t0:7.1f}s] {m}", flush=True)


def run(batch, prompt_len=128, new_tokens=512, family="gpt"):
    from paddle_tpu.nlp.gpt import generate

    pt.seed(0)
    if family == "llama":
        # GQA decode: 32 q heads over 8 kv heads — the cache-bandwidth
        # shape modern serving cares about
        from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
        cfg = LlamaConfig(vocab_size=32000, hidden_size=768,
                          num_layers=12, num_heads=12, num_kv_heads=4,
                          max_seq_len=prompt_len + new_tokens)
        model = LlamaForCausalLM(cfg)
    else:
        from paddle_tpu.nlp import GPTConfig, GPTForPretraining
        cfg = GPTConfig(vocab_size=32768, hidden_size=768, num_layers=12,
                        num_heads=12, max_seq_len=prompt_len + new_tokens,
                        dropout=0.0, attn_dropout=0.0)
        model = GPTForPretraining(cfg)
    model.to(dtype=jnp.bfloat16)
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, prompt_len)).astype("int32")

    t1 = time.time()
    out = generate(model, ids, max_new_tokens=new_tokens, use_cache=True)
    np.asarray(out.numpy() if hasattr(out, "numpy") else out)
    log(f"decode {family} b={batch} warm (compile): {time.time()-t1:.1f}s")

    t1 = time.time()
    out = generate(model, ids, max_new_tokens=new_tokens, use_cache=True)
    np.asarray(out.numpy() if hasattr(out, "numpy") else out)
    dt = time.time() - t1
    rate = batch * new_tokens / dt
    log(f"RESULT decode {family} b={batch} prompt={prompt_len} "
        f"new={new_tokens}: "
        f"{rate:,.0f} tok/s  {dt/new_tokens*1e3:.2f} ms/token")


def main():
    fams = sys.argv[1:] or ["gpt", "llama"]
    for family in fams:
        for b in (1, 8):
            run(b, family=family)


if __name__ == "__main__":
    main()
