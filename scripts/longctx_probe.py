"""Long-context single-chip probe: GPT-2s at seq 4096/8192 with the Pallas
flash kernels (fwd + bwd) and optional recompute. The S x S score matrix
at 8192 would be 256MB/head-layer in HBM — flash streams it, so these
configs fit one v5e where the XLA dense path OOMs.

Usage: python scripts/longctx_probe.py [seq ...]
"""
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
from paddle_tpu.utils import compile_cache  # noqa: E402
compile_cache.enable()

from paddle_tpu.utils.flight_recorder import mfu_text  # noqa: E402
import paddle_tpu as pt                # noqa: E402
from paddle_tpu.nlp import GPTConfig, GPTForPretraining  # noqa: E402
from paddle_tpu.nlp.gpt import gpt_pretrain_loss         # noqa: E402
from paddle_tpu.jit import TrainStep   # noqa: E402

t0 = time.time()


def log(m):
    print(f"[{time.time()-t0:7.1f}s] {m}", flush=True)


# rows: full causal at 4k/8k, plus sliding-window 1024 at 8k (the banded
# kernel skips KV blocks outside the last-W band: O(S*W) attention)
ROWS = ([(int(a), None) for a in sys.argv[1:]]
        or [(4096, None), (8192, None), (8192, 1024)])
for seq, window in ROWS:
    batch = max(1, 8192 // seq)
    pt.seed(0)
    cfg = GPTConfig(vocab_size=32768, hidden_size=768, num_layers=12,
                    num_heads=12, max_seq_len=seq, dropout=0.0,
                    attn_dropout=0.0, use_recompute=(seq >= 8192),
                    attn_window=window)
    model = GPTForPretraining(cfg)
    model.to(dtype=jnp.bfloat16)
    opt = pt.optimizer.AdamW(learning_rate=1e-4,
                             parameters=model.parameters())
    step = TrainStep(model, gpt_pretrain_loss, opt, donate=True)
    ids = np.random.RandomState(0).randint(
        0, 32768, (batch, seq)).astype("int32")
    for i in range(3):
        t1 = time.time()
        loss = step(ids, ids)
        v = float(loss.numpy())
        log(f"seq={seq}{f'-w{window}' if window else ''} b={batch} warm {i}: {time.time()-t1:.1f}s "
            f"loss={v:.4f}")
    iters = 10
    t1 = time.time()
    for _ in range(iters):
        loss = step(ids, ids)
    float(loss.numpy())
    dt = (time.time() - t1) / iters
    toks = batch * seq / dt
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    tf = toks * 6 * n_params / 1e12
    log(f"seq={seq}{f'-w{window}' if window else ''}: {dt*1e3:.1f} ms/step  {toks:,.0f} tok/s  "
        f"{tf:.1f} TF/s  MFU={mfu_text(tf * 1e12)} "
        f"(attn-flops excluded from MFU)")
    del step, model, opt
