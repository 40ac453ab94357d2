"""Capture a device trace of a bench-family train step on the real chip.

    python scripts/capture_trace.py resnet 128
    python scripts/capture_trace.py gpt 8

Runs the family's bench step (same model builders as bench_sweep) for 3
warmup + 5 traced steps under the jax.profiler XPlane trace and leaves
the trace directory under docs/perf/traces/<family>/ for Perfetto /
TensorBoard. The round-2 gpt trace drove the 128->512 block retune; a
resnet trace is the prerequisite for attacking its 0.145 MFU (layout vs
BN vs small-conv underutilisation is unknowable without one).
"""
import os
import shutil
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

t0 = time.time()


def log(m):
    print(f"[{time.time()-t0:7.1f}s] {m}", flush=True)


def build(family, batch):
    if family == "resnet":
        from paddle_tpu.vision.models import resnet50
        import paddle_tpu.nn.functional as F
        pt.seed(0)
        model = resnet50()
        model.to(dtype=jnp.bfloat16)
        opt = pt.optimizer.Momentum(learning_rate=0.1, momentum=0.9,
                                    parameters=model.parameters())
        step = TrainStep(model, lambda lo, la: F.cross_entropy(lo, la),
                         opt, donate=True)
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(batch, 3, 224, 224), jnp.bfloat16)
        y = jnp.asarray(rng.randint(0, 1000, (batch,)), jnp.int32)
        return step, x, y
    if family == "gpt":
        from paddle_tpu.nlp import GPTConfig, GPTForPretraining
        from paddle_tpu.nlp.gpt import gpt_pretrain_loss
        pt.seed(0)
        cfg = GPTConfig(vocab_size=32768, hidden_size=768, num_layers=12,
                        num_heads=12, max_seq_len=1024, dropout=0.0,
                        attn_dropout=0.0)
        model = GPTForPretraining(cfg)
        model.to(dtype=jnp.bfloat16)
        opt = pt.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())
        step = TrainStep(model, gpt_pretrain_loss, opt, donate=True)
        ids = np.random.RandomState(0).randint(
            0, cfg.vocab_size, (batch, 1024)).astype("int32")
        return step, ids, ids
    raise SystemExit(f"unknown family {family}")


def main():
    family = sys.argv[1] if len(sys.argv) > 1 else "resnet"
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 128
    trace_dir = os.path.join(_REPO, "docs", "perf", "traces", family)
    shutil.rmtree(trace_dir, ignore_errors=True)
    os.makedirs(trace_dir, exist_ok=True)

    step, x, y = build(family, batch)
    for i in range(3):
        t1 = time.time()
        loss = step(x, y)
        float(loss.numpy())
        log(f"{family} warm {i}: {time.time()-t1:.2f}s")

    from paddle_tpu.utils.profiler import start_profiler, stop_profiler
    start_profiler(trace_dir=trace_dir)
    for _ in range(5):
        loss = step(x, y)
    float(loss.numpy())
    stop_profiler()
    n = sum(len(fs) for _, _, fs in os.walk(trace_dir))
    log(f"RESULT trace {family} b={batch}: {n} files in {trace_dir}")


if __name__ == "__main__":
    main()
