"""Op micro-benchmark harness (ref paddle/fluid/operators/benchmark/
op_tester.cc): times a representative op set on the current backend and
prints a table. Used to sanity-check kernel regressions chip-side.

Usage: python scripts/op_bench.py [--cpu] [op ...]
"""
import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _REPO)
if "--cpu" in sys.argv:
    sys.argv.remove("--cpu")
    jax.config.update("jax_platforms", "cpu")
from paddle_tpu.utils import compile_cache  # noqa: E402
compile_cache.enable()


def _flash(q):
    from paddle_tpu.ops.pallas.flash_attention import _flash_array
    return _flash_array(q, q, q, causal=True)


def _flash_grad(q):
    from paddle_tpu.ops.pallas.flash_attention import _flash_array
    return jax.grad(
        lambda x: jnp.sum(_flash_array(x, x, x, causal=True)
                          .astype(jnp.float32)))(q)


CASES = {
    # name: (fn, arg builder, flops estimate or None)
    "matmul_4k_bf16": (
        lambda a, b: a @ b,
        lambda r: (jnp.asarray(r.randn(4096, 4096), jnp.bfloat16),
                   jnp.asarray(r.randn(4096, 4096), jnp.bfloat16)),
        2 * 4096 ** 3),
    "matmul_1k_f32": (
        lambda a, b: a @ b,
        lambda r: (jnp.asarray(r.randn(1024, 1024), jnp.float32),) * 2,
        2 * 1024 ** 3),
    "layer_norm_8x1024x1024": (
        lambda x: jax.nn.standardize(x, axis=-1),
        lambda r: (jnp.asarray(r.randn(8, 1024, 1024), jnp.bfloat16),),
        None),
    "softmax_8x1024x32768": (
        lambda x: jax.nn.softmax(x, axis=-1),
        lambda r: (jnp.asarray(r.randn(8, 1024, 32768), jnp.bfloat16),),
        None),
    "flash_attn_fwd_b8h12s1024d64": (
        _flash,
        lambda r: (jnp.asarray(r.randn(8, 12, 1024, 64), jnp.bfloat16),),
        4 * 8 * 12 * 1024 * 1024 * 64 // 2),
    "flash_attn_fwdbwd_b8h12s1024d64": (
        _flash_grad,
        lambda r: (jnp.asarray(r.randn(8, 12, 1024, 64), jnp.bfloat16),),
        int(4 * 8 * 12 * 1024 * 1024 * 64 // 2 * 3.5)),
    "embedding_32k_to_8x1024": (
        lambda w, i: w[i],
        lambda r: (jnp.asarray(r.randn(32768, 768), jnp.bfloat16),
                   jnp.asarray(r.randint(0, 32768, (8, 1024)), jnp.int32)),
        None),
    "conv2d_64x64x224": (
        lambda x, k: jax.lax.conv_general_dilated(
            x, k, (1, 1), "SAME",
            dimension_numbers=("NCHW", "OIHW", "NCHW")),
        lambda r: (jnp.asarray(r.randn(8, 64, 224, 224), jnp.bfloat16),
                   jnp.asarray(r.randn(64, 64, 3, 3), jnp.bfloat16)),
        2 * 8 * 64 * 64 * 224 * 224 * 9),
}


def bench_hot_row_cache():
    """Heter-PS hot-row cache micro-bench: steady-state step latency with
    the device cache (zero RPCs) vs the pull/push path, same workload."""
    import paddle_tpu as pt
    from paddle_tpu.distributed.fleet.ps import PsServer, PsClient
    from paddle_tpu.distributed.fleet.heter import HeterPSTrainer

    emb_dim, nfeat, batch, vocab = 64, 26, 512, 4096
    s = PsServer()
    s.add_sparse_table(1, dim=emb_dim, lr=0.1)
    s.add_sparse_table(2, dim=emb_dim, lr=0.1)
    port = s.start(0)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, vocab, (batch, nfeat))
    y = jnp.asarray(rng.randn(batch).astype("f4"))

    def loss_fn(p, urows, inv, y):
        x = urows[inv].reshape(y.shape[0], nfeat * emb_dim)
        return jnp.mean(jnp.square(jnp.sum(x, -1) - y))

    out = {}
    for tag, table, cap in (("pull/push", 1, 0), ("hot-cache", 2, 8192)):
        opt = pt.optimizer.AdamW(learning_rate=0.01, parameters=[])
        tr = HeterPSTrainer(loss_fn, {"w": np.ones(2, "f4")}, opt,
                            PsClient(port=port), sparse_table=table,
                            emb_dim=emb_dim, cache_capacity=cap)
        for _ in range(3):
            tr.step(ids, y)                        # warm + fill cache
        t0 = time.perf_counter()
        n = 10
        for _ in range(n):
            tr.step(ids, y)
        out[tag] = (time.perf_counter() - t0) / n * 1e3
    s.stop()
    print(f"{'heter step (pull/push)':36s} {out['pull/push']:9.3f}")
    print(f"{'heter step (hot-row cache)':36s} {out['hot-cache']:9.3f}")
    print(f"cache speedup: {out['pull/push'] / out['hot-cache']:.2f}x "
          f"(host RPCs skipped on the hot set)")


def main():
    if "heter_cache" in sys.argv[1:]:
        bench_hot_row_cache()
        sys.argv.remove("heter_cache")
        if not sys.argv[1:]:
            return
    names = sys.argv[1:] or list(CASES)
    rng = np.random.RandomState(0)
    print(f"backend: {jax.default_backend()}")
    print(f"{'op':36s} {'ms':>9s} {'TFLOP/s':>9s}")
    for name in names:
        fn, build, flops = CASES[name]
        args = build(rng)
        jfn = jax.jit(fn)
        jax.block_until_ready(jfn(*args))          # compile + warm
        t0 = time.perf_counter()
        n = 10
        for _ in range(n):
            out = jfn(*args)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / n
        tf = f"{flops / dt / 1e12:9.1f}" if flops else "        -"
        print(f"{name:36s} {dt * 1e3:9.3f} {tf}")


if __name__ == "__main__":
    main()
