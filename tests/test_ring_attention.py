"""Ring attention (sequence parallelism over 'sp') vs dense reference.

New capability vs the reference (SURVEY.md §5: no sequence parallelism in
Yelrose/Paddle); correctness is checked against the dense softmax(QK^T)V
reference on the 8-device virtual mesh, including gradients and end-to-end
GPT training with dp x mp x sp."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.distributed.mesh import make_mesh
from paddle_tpu.distributed.ring_attention import ring_attention
from paddle_tpu.ops.pallas.flash_attention import _sdpa_reference


@pytest.fixture(autouse=True)
def reset_mesh():
    yield
    import paddle_tpu.distributed.mesh as mesh_mod
    mesh_mod._current_mesh = None


def _rand_qkv(rs, b=2, h=4, s=64, d=16):
    return [jnp.asarray(rs.randn(b, h, s, d), jnp.float32) for _ in range(3)]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("mesh_shape", [{"sp": 8}, {"dp": 2, "sp": 4}])
def test_ring_matches_dense(causal, mesh_shape):
    make_mesh(mesh_shape)
    q, k, v = _rand_qkv(np.random.RandomState(0))
    out = ring_attention(q, k, v, causal=causal)
    ref = _sdpa_reference(q, k, v, None, causal, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_ring_gradients_match_dense():
    make_mesh({"dp": 2, "sp": 4})
    q, k, v = _rand_qkv(np.random.RandomState(1))

    g_ring = jax.grad(
        lambda *a: jnp.sum(ring_attention(*a, causal=True) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        lambda *a: jnp.sum(_sdpa_reference(*a, None, True, None) ** 2),
        argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_ring_inside_jit():
    mesh = make_mesh({"sp": 8})
    q, k, v = _rand_qkv(np.random.RandomState(2))
    f = jax.jit(lambda q, k, v: ring_attention(q, k, v, causal=True,
                                               mesh=mesh))
    np.testing.assert_allclose(
        np.asarray(f(q, k, v)),
        np.asarray(_sdpa_reference(q, k, v, None, True, None)),
        rtol=1e-5, atol=1e-5)


def test_fallback_without_sp_axis():
    make_mesh({"dp": 8})
    q, k, v = _rand_qkv(np.random.RandomState(3))
    out = ring_attention(q, k, v, causal=True)
    ref = _sdpa_reference(q, k, v, None, True, None)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_gpt_sequence_parallel_training_step():
    """GPT with ring attention trains under dp x mp x sp GSPMD jit and the
    loss matches the non-sp model on the same data."""
    from paddle_tpu.nlp import GPTConfig, GPTForPretraining
    from paddle_tpu.nlp.gpt import gpt_pretrain_loss
    from paddle_tpu.distributed.sharded import ShardedTrainStep

    kw = dict(vocab_size=256, hidden_size=64, num_layers=2, num_heads=4,
              max_seq_len=64, dropout=0.0, attn_dropout=0.0)
    ids = np.random.RandomState(0).randint(0, 256, (4, 64)).astype("i4")

    losses = {}
    for sp_flag in (False, True):
        make_mesh({"dp": 2, "mp": 2, "sp": 2} if sp_flag else {"dp": 4})
        pt.seed(7)
        model = GPTForPretraining(GPTConfig(sequence_parallel=sp_flag, **kw))
        opt = pt.optimizer.AdamW(learning_rate=1e-3,
                                 parameters=model.parameters())
        step = ShardedTrainStep(model, gpt_pretrain_loss, opt)
        vals = [float(step(ids, ids).numpy()) for _ in range(3)]
        losses[sp_flag] = vals
        assert vals[-1] < vals[0]  # it learns
    np.testing.assert_allclose(losses[True], losses[False],
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("seq", [2048, 4096])
def test_long_context_correctness_at_length(seq):
    """Long-context story (SURVEY §5): ring AND Ulysses sequence
    parallelism stay numerically correct at 2k/4k context vs the dense
    reference — the CPU-mesh correctness half of scripts/longctx_probe.py
    (throughput half runs on the real chip)."""
    make_mesh({"sp": 8})
    rs = np.random.RandomState(0)
    q, k, v = [jnp.asarray(rs.randn(1, 8, seq, 16), jnp.float32)
               for _ in range(3)]
    ref = _sdpa_reference(q, k, v, None, True, None)
    out = ring_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    from paddle_tpu.distributed.ulysses import ulysses_attention
    out2 = ulysses_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ring_per_device_sequence_shard():
    """The reason ring attention exists: each device holds S/sp of the
    sequence. Assert the partitioned program computes on seq/8 blocks
    (ppermute ring), not the full S — the memory-scaling evidence."""
    make_mesh({"sp": 8})
    rs = np.random.RandomState(0)
    S = 2048
    q, k, v = [jnp.asarray(rs.randn(1, 8, S, 16), jnp.float32)
               for _ in range(3)]

    import paddle_tpu.distributed.mesh as mesh_mod
    mesh = mesh_mod.get_mesh()

    def f(q_, k_, v_):
        return ring_attention(q_, k_, v_, causal=True)

    txt = jax.jit(f).lower(q, k, v).compile().as_text()
    shard = S // 8
    assert f"{shard},16" in txt.replace(" ", ""), \
        "no seq/8-sized operand in partitioned HLO"
    assert "collective-permute" in txt, "ring ppermute missing"


def test_ring_memory_advantage_xla_analysis():
    """Per-device compiled memory (XLA memory_analysis, grad included) of
    ring attention over sp=8 must beat the sequence-replicated dense
    step — the reason sequence parallelism exists."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P, NamedSharding
    from paddle_tpu.distributed import mesh as mesh_mod
    from paddle_tpu.distributed.ring_attention import ring_attention
    from paddle_tpu.ops.pallas.flash_attention import _flash_array

    m = Mesh(np.array(jax.devices()[:8]).reshape(8), ("sp",))
    mesh_mod.set_mesh(m)
    try:
        q = jnp.zeros((1, 4, 2048, 32), jnp.float32)
        shard = NamedSharding(m, P(None, None, "sp", None))
        repl = NamedSharding(m, P())

        def peak(fn, sh):
            g = jax.jit(jax.grad(fn, argnums=(0, 1, 2)),
                        in_shardings=(sh, sh, sh))
            ma = g.lower(q, q, q).compile().memory_analysis()
            return (ma.temp_size_in_bytes + ma.argument_size_in_bytes
                    + ma.output_size_in_bytes)

        p_ring = peak(lambda a, b, c: ring_attention(
            a, b, c, causal=True).sum(), shard)
        p_dense = peak(lambda a, b, c: _flash_array(
            a, b, c, causal=True).sum(), repl)
        # hand-rolled ring backward: strictly local residuals (the
        # autodiff-through-scan baseline sat at ~0.35x dense here)
        assert p_ring < p_dense * 0.25, (p_ring, p_dense)
    finally:
        mesh_mod.set_mesh(None)


def test_ring_tiled_block_path_parity():
    """Shard length > _KV_CHUNK exercises the kv-tiling inside each ring
    block (incl. a non-multiple remainder tail): fwd + dq/dk/dv must
    match dense exactly — the path the linear-memory claim rests on."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from paddle_tpu.distributed import mesh as mesh_mod
    import importlib
    ra = importlib.import_module("paddle_tpu.distributed.ring_attention")
    from paddle_tpu.ops.pallas.flash_attention import _flash_array

    m = Mesh(np.array(jax.devices()[:4]).reshape(4), ("sp",))
    mesh_mod.set_mesh(m)
    old_chunk = ra._KV_CHUNK
    ra._KV_CHUNK = 64          # small tile so the test stays fast
    try:
        r = np.random.RandomState(0)
        # S_loc = 160 = 2 full 64-tiles + a 32 remainder tail
        S = 160 * 4
        q = jnp.asarray(r.randn(1, 2, S, 16).astype("f4") * 0.3)
        k = jnp.asarray(r.randn(1, 2, S, 16).astype("f4") * 0.3)
        v = jnp.asarray(r.randn(1, 2, S, 16).astype("f4"))
        ref = _flash_array(q, k, v, causal=True)
        got = ra.ring_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=3e-3, atol=3e-3)
        for arg in range(3):
            g1 = jax.grad(lambda *a: ra.ring_attention(
                *a, causal=True).sum(), argnums=arg)(q, k, v)
            g2 = jax.grad(lambda *a: _flash_array(
                *a, causal=True).sum(), argnums=arg)(q, k, v)
            np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                       rtol=1e-2, atol=1e-2)
    finally:
        ra._KV_CHUNK = old_chunk
        mesh_mod.set_mesh(None)
        ra._jitted_ring.cache_clear()   # drop graphs traced w/ tiny chunk
