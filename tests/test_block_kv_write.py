"""The block pool's stored form and its one write.

`nn.paged_attention.write_block_kv` moves whole pages where PR 28's
parent scattered single rows (`scatter_block_kv_at`, `_chunk`,
`_chunk_batched`); what a program may observe of it has to be what the
row scatters did. The oracle below is those scatters in plain numpy, a
position at a time, on the stored form `[NB, Hkv, BS, 2D]`. Two things
differ by design and are held here too: the scratch block holds zeros
after every write (the scatters left the padded tail's rows in it), and a
position past the table's end is not written (the scatters wrapped it
onto the table's last page; the engine never asks for one).

Then the two attention cores on the stored form at the head shapes the
benchmark serves, and the paged engine's greedy tokens against the dense
engine's (GPT, Llama) and the model's own forward (Nemotron-H).
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.nn import paged_attention as pa
from paddle_tpu.nn.paged_attention import (gather_block_kv, init_block_kv,
                                           write_block_kv)

HKV, D = 2, 4


def _rows_model(pool, k, v, tables, start, valid_len):
    """The parent's row scatters, one position at a time."""
    pool = pool.copy()
    lanes, _, c, d = k.shape
    bs, nblk = pool.shape[2], tables.shape[1]
    for s in range(lanes):
        for i in range(min(int(valid_len[s]), c)):
            p = int(start[s]) + i
            if p >= nblk * bs:
                continue
            blk = tables[s, p // bs]
            pool[blk, :, p % bs, :d] = k[s, :, i]
            pool[blk, :, p % bs, d:] = v[s, :, i]
    pool[0] = 0
    return pool


def _setup(seed, lanes, c, bs, nblk, dtype):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    nb = lanes * nblk + 1
    pool = rng.standard_normal((nb, HKV, bs, 2 * D)).astype(np.float32)
    pool[0] = np.nan                    # whatever a fault left in scratch
    tables = 1 + rng.permutation(lanes * nblk).reshape(lanes, nblk)
    k = rng.standard_normal((lanes, HKV, c, D)).astype(np.float32)
    v = rng.standard_normal((lanes, HKV, c, D)).astype(np.float32)
    cast = lambda a: np.array(jnp.asarray(a, dtype).astype(jnp.float32))
    return cast(pool), cast(k), cast(v), tables.astype(np.int32)


def _write(pool, k, v, tables, start, valid_len, dtype):
    import jax
    import jax.numpy as jnp
    out = jax.jit(write_block_kv)(
        jnp.asarray(pool, dtype), jnp.asarray(k, dtype),
        jnp.asarray(v, dtype), jnp.asarray(tables),
        jnp.asarray(start, jnp.int32),
        None if valid_len is None else jnp.asarray(valid_len, jnp.int32))
    assert out.dtype == jnp.dtype(dtype) and out.shape == pool.shape
    return np.asarray(out.astype(jnp.float32))


DTYPES = ["float32", "bfloat16"]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("bs", [4, 16])
def test_decode_wave_rows_at_ragged_positions(bs, dtype):
    """One row a lane, in one call, at 0, bs - 1, bs, mid-table and the
    table's last position; a retired lane (its table row is scratch)
    writes nothing a live lane can see."""
    nblk = 5
    pool, k, v, tables = _setup(0, 6, 1, bs, nblk, dtype)
    start = np.asarray([0, bs - 1, bs, 2 * bs + 1, nblk * bs - 1, 7])
    tables[5] = 0
    got = _write(pool, k, v, tables, start, None, dtype)
    want = _rows_model(pool, k, v, tables, start, np.ones(6, int))
    np.testing.assert_array_equal(got, want)
    for s in range(5):      # and the row is where the table says it is
        row = got[tables[s, start[s] // bs], :, start[s] % bs]
        np.testing.assert_array_equal(row[:, :D], k[s, :, 0])
        np.testing.assert_array_equal(row[:, D:], v[s, :, 0])


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("valid", [16, 11, 1, 0])
@pytest.mark.parametrize("start", [0, 16, 32])
def test_prefill_chunk_with_a_padded_tail(start, valid, dtype):
    """A 16-token chunk over pages of 4 at a chunk-aligned start (0, or
    further on after earlier chunks or a prefix hit): rows past
    `valid_len` keep their bits, pages before the chunk are untouched,
    and the scalar start and valid_len the engine passes are taken."""
    import jax
    import jax.numpy as jnp
    pool, k, v, tables = _setup(1, 1, 16, 4, 12, dtype)
    got = _write(pool, k, v, tables, [start], [valid], dtype)
    want = _rows_model(pool, k, v, tables, [start], [valid])
    np.testing.assert_array_equal(got, want)
    before = tables[0, :start // 4]
    np.testing.assert_array_equal(got[before], pool[before])
    scalar = jax.jit(write_block_kv)(
        jnp.asarray(pool, dtype), jnp.asarray(k, dtype),
        jnp.asarray(v, dtype), jnp.asarray(tables), jnp.int32(start),
        jnp.int32(valid))
    np.testing.assert_array_equal(
        np.asarray(scalar.astype(jnp.float32)), got)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("c,bs", [(5, 4), (5, 16), (3, 4), (9, 8)])
def test_batched_verify_spans_at_per_lane_starts(c, bs, dtype):
    """The speculative verify wave: every lane writes a span of its own
    length from its own position, spans that cross page boundaries
    anywhere, one clamped to nothing, one that runs off the table."""
    nblk = 6
    pool, k, v, tables = _setup(2, 6, c, bs, nblk, dtype)
    start = np.asarray([0, bs - 1, bs - 2, 2 * bs + 1, nblk * bs - 2, 3])
    valid = np.asarray([c, c, max(c - 2, 1), c, c, 0])
    got = _write(pool, k, v, tables, start, valid, dtype)
    want = _rows_model(pool, k, v, tables, start, valid)
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("c", [1, 16])
def test_pages_of_other_owners_keep_their_bits(c):
    """Prefix-shared and copy-on-write pages belong to tables this call
    does not write through: every page outside the written lanes'
    frontier pages is bit for bit what it was, whatever it holds."""
    pool, k, v, tables = _setup(3, 4, c, 4, 8, "float32")
    shared = tables[2:].ravel()
    pool[shared[::3]] = np.inf
    start = np.asarray([4, 9])
    got = _write(pool, k[:2], v[:2], tables[:2], start, [c, c], "float32")
    np.testing.assert_array_equal(got[shared], pool[shared])
    touched = {int(tables[s, (start[s] + i) // 4])
               for s in range(2) for i in range(c)}
    rest = sorted(set(range(1, len(pool))) - touched)
    np.testing.assert_array_equal(got[rest], pool[rest])


def test_scratch_is_zero_after_any_write():
    """So that a padded tail's queries, which attend past the lane's
    last written position through unmapped table entries, read nothing
    non-finite that could reach the good rows as 0 * nan."""
    for c in (1, 16):
        pool, k, v, tables = _setup(4, 2, c, 4, 8, "float32")
        assert np.isnan(pool[0]).all()
        got = _write(pool, k, v, tables, [5, 8], [c, c], "float32")
        assert (got[0] == 0).all()


def test_gather_reads_back_what_write_stored():
    import jax.numpy as jnp
    rng = np.random.default_rng(5)
    pool = init_block_kv(9, HKV, 4, D, jnp.float32)
    assert pool.shape == (9, HKV, 4, 2 * D)
    tables = jnp.asarray([[3, 1, 7], [2, 8, 5]], jnp.int32)
    k = jnp.asarray(rng.standard_normal((2, HKV, 12, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, HKV, 12, D)), jnp.float32)
    pool = write_block_kv(pool, k, v, tables, jnp.zeros(2, jnp.int32))
    ck, cv = gather_block_kv(pool, tables)
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(k))
    np.testing.assert_array_equal(np.asarray(cv), np.asarray(v))


# ---------------------------------------------------------------------------
# the two cores on the stored form, at the benchmark's head shapes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bs", [16, 8])
@pytest.mark.parametrize("form", ["decode", "chunk"])
@pytest.mark.parametrize("h,hkv,d", [(12, 12, 64), (32, 8, 128),
                                     (32, 2, 128)],
                         ids=["mha12x64", "gqa32-8x128", "gqa32-2x128"])
def test_cores_agree_on_the_stored_form(h, hkv, d, form, bs):
    """Written by `write_block_kv` into a bfloat16 pool of pages of 16
    (whole (16, 128) tiles) and of 8 (half a tile a page, which only the
    CPU can check cheaply), attended by each core: `pallas` against
    `reference`."""
    import jax.numpy as jnp
    rng = np.random.default_rng(6)
    lanes, nblk, c = 3, 4, (1 if form == "decode" else 8)
    tables = jnp.asarray(
        1 + rng.permutation(lanes * nblk).reshape(lanes, nblk), jnp.int32)
    pool = init_block_kv(lanes * nblk + 1, hkv, bs, d, jnp.bfloat16)
    hist = [jnp.asarray(rng.standard_normal((lanes, hkv, nblk * bs, d)),
                        jnp.bfloat16) for _ in range(2)]
    pool = write_block_kv(pool, *hist, tables, jnp.zeros(lanes, jnp.int32))
    start = jnp.asarray([0, 17, nblk * bs - c], jnp.int32)
    q = jnp.asarray(rng.standard_normal((lanes, h, c, d)), jnp.bfloat16)
    ref = pa.attend(q, pool, tables, start, d ** -0.5, kernel="reference")
    out = pa.attend(q, pool, tables, start, d ** -0.5, kernel="pallas")
    assert out.shape == (lanes, h, c, d) and out.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


# ---------------------------------------------------------------------------
# greedy tokens through the paged engine, for the three model files
# ---------------------------------------------------------------------------

VOCAB, MAX_LEN, BLOCK, CHUNK = 96, 64, 8, 16


def _jobs(seed, n=6):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, VOCAB, (int(rng.randint(2, 40)),)).tolist(),
             int(rng.randint(2, 9))) for _ in range(n)]


def _tokens(engine, jobs):
    from paddle_tpu.serving import Scheduler
    sched = Scheduler(engine)
    reqs = [sched.submit(prompt=p, max_tokens=m) for p, m in jobs]
    sched.run()
    return [r.output_tokens for r in reqs]


def _gpt():
    from paddle_tpu.nlp import GPTConfig, GPTForPretraining
    return GPTForPretraining(GPTConfig(
        vocab_size=VOCAB, hidden_size=64, num_layers=2, num_heads=4,
        max_seq_len=MAX_LEN, dropout=0.0, attn_dropout=0.0))


def _llama():
    from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=VOCAB, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, max_seq_len=MAX_LEN))


@pytest.mark.parametrize("kernel", pa.KERNELS)
@pytest.mark.parametrize("family", ["gpt", "llama"])
def test_paged_greedy_tokens_equal_the_dense_engines(family, kernel):
    """Prompts of under one chunk to several (with ragged tails), three
    admission waves over four slots, prefix sharing on."""
    from paddle_tpu.serving import PagedServingEngine, ServingEngine
    pt.seed(11)
    model = _gpt() if family == "gpt" else _llama()
    model.eval()
    jobs = _jobs(12)
    jobs[3] = (jobs[1][0][:BLOCK * 2] + jobs[3][0], jobs[3][1])  # a hit
    jobs = [(p[:MAX_LEN - 10], m) for p, m in jobs]
    paged = PagedServingEngine(model, num_slots=4, max_len=MAX_LEN,
                               block_size=BLOCK, prefill_chunk_len=CHUNK,
                               paged_kernel=kernel)
    dense = ServingEngine(model, num_slots=4, max_len=MAX_LEN,
                          prefill_len=MAX_LEN)
    assert _tokens(paged, jobs) == _tokens(dense, jobs)


@pytest.mark.parametrize("kernel", pa.KERNELS)
def test_nemotron_paged_greedy_tokens_equal_its_forward(kernel):
    """The hybrid model has no dense engine: its served tokens against
    the argmax of its own full forward over prompt + tokens."""
    from paddle_tpu.nlp import NemotronHConfig, NemotronHForCausalLM
    from paddle_tpu.serving import PagedServingEngine, Scheduler
    pt.seed(13)
    model = NemotronHForCausalLM(NemotronHConfig(
        hybrid_override_pattern="M*E*", vocab_size=VOCAB, hidden_size=64,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        mamba_num_heads=8, mamba_head_dim=8, n_groups=2,
        ssm_state_size=16, chunk_size=8, n_routed_experts=8,
        num_experts_per_tok=3, moe_intermediate_size=64,
        moe_shared_expert_intermediate_size=96, initializer_range=0.2))
    model.eval()
    eng = PagedServingEngine(model, num_slots=2, max_len=MAX_LEN,
                             block_size=BLOCK, prefill_chunk_len=CHUNK,
                             paged_kernel=kernel)
    for prompt, new in _jobs(14, n=3):
        out = Scheduler(eng).generate(prompt, max_tokens=new)
        logits = np.asarray(model(np.asarray([prompt + out]))._data)[0]
        n = len(prompt)
        assert out == [int(np.argmax(logits[n - 1 + i]))
                       for i in range(new)]
