"""A/B the GPT-2s bench step over the two knobs that moved since the last
on-chip measurement (round 2's 66.9 ms / 0.414 MFU):

  - fused_head_loss: vocab-chunked fused LM-head+CE (round 3, default ON,
    never measured on-chip) vs the dense head + cross_entropy path
  - attn_layout: bhsd (per-head kernels, transposes feed them) vs bshd
    (packed-lane kernels, no transposes)

    python scripts/ab_gpt.py                 # all 4 combos
    python scripts/ab_gpt.py fused=0 layout=bhsd   # one combo

Prints one ms/step + MFU row per combo; steady-state after 3 warmups,
persistent compile cache on.
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
from paddle_tpu.jit import TrainStep
from paddle_tpu.utils.flight_recorder import mfu_text

t0 = time.time()


def log(m):
    print(f"[{time.time()-t0:7.1f}s] {m}", flush=True)


def run_combo(fused, layout, batch=8, seq=1024, iters=20):
    from paddle_tpu.nlp import GPTConfig, GPTForPretraining
    from paddle_tpu.nlp.gpt import gpt_pretrain_loss
    cfg = GPTConfig(vocab_size=32768, hidden_size=768, num_layers=12,
                    num_heads=12, max_seq_len=1024, dropout=0.0,
                    attn_dropout=0.0, fused_head_loss=fused,
                    attn_layout=layout)
    pt.seed(0)
    model = GPTForPretraining(cfg)
    model.to(dtype=jnp.bfloat16)
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters())
    step = TrainStep(model, gpt_pretrain_loss, opt, donate=True)
    ids = np.random.RandomState(0).randint(
        0, cfg.vocab_size, (batch, seq)).astype("int32")
    tag = f"fused={int(fused)} layout={layout}"
    for i in range(3):
        t1 = time.time()
        loss = step(ids, ids)
        v = float(loss.numpy())
        log(f"{tag} warm {i}: {time.time()-t1:.3f}s loss={v:.4f}")
    t1 = time.time()
    for _ in range(iters):
        loss = step(ids, ids)
    float(loss.numpy())
    dt = (time.time() - t1) / iters
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    tf = 6 * n_params * batch * seq / dt / 1e12
    log(f"RESULT {tag}: {dt*1e3:.2f} ms/step  {batch*seq/dt:,.0f} tok/s  "
        f"{tf:.1f} TF/s  MFU={mfu_text(tf * 1e12)}")
    del step, model, opt
    return dt


def main():
    bad = [a for a in sys.argv[1:] if "=" not in a]
    if bad:
        raise SystemExit(f"unknown args {bad}; use fused=0|1 layout=bhsd|bshd"
                         " (gpt2m-no-recompute moved to"
                         " scripts/bench_sweep.py gpt2m_norc)")
    want = dict(a.split("=") for a in sys.argv[1:] if "=" in a)
    fuseds = ([bool(int(want["fused"]))] if "fused" in want
              else [True, False])
    layouts = [want["layout"]] if "layout" in want else ["bhsd", "bshd"]
    for layout in layouts:
        for fused in fuseds:
            run_combo(fused, layout)


if __name__ == "__main__":
    main()
