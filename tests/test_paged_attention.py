"""paddle_tpu.nn.paged_attention — the two paged-attention cores and
their dispatch front door.

The acceptance contract for the kernel is PARITY, not approximation:
both cores ("reference" — gather_block_kv + a plain masked softmax, the
oracle; "pallas" — the TPU kernel run in interpret mode on CPU so tier-1
executes the genuine kernel body) must produce the SAME TOKENS through
the serving engines, greedy and sampled, single request and
mixed-length multi-wave streams, plain and speculative — while the
compile-once program counts and the isfinite poison sentinel hold.

The masking contract rides along: masked scores are -inf (not -1e9),
fully-masked rows renormalise to exactly 0, and non-finite garbage in
a scratch block — which the engines read at MASKED positions by design
— cannot leak into any lane's output, while a genuine non-finite at an
ATTENDED position still propagates to the logits (the poison
sentinel's signal). That the chip's programs hold no gathered view and
no pool-sized copy is tests/test_tpu_compile.py's, for a described v5e
at the benchmark's shapes.
"""
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.nn import paged_attention as pa
from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import PagedServingEngine, Scheduler

KERNELS = pa.KERNELS
FUSED = ("pallas",)

VOCAB = 128
MAX_LEN = 64
BLOCK = 8
CHUNK = 16
MAX_NEW = 8


# ---------------------------------------------------------------------------
# op-level parity: kernel x form x window on random pools
# ---------------------------------------------------------------------------

def _fuse(pk, pv):
    """K and V pools [NB, Hkv, BS, D] -> the stored form [NB, Hkv, BS, 2D]."""
    import jax.numpy as jnp
    return jnp.concatenate([jnp.asarray(pk), jnp.asarray(pv)], axis=-1)


def _pools(seed, nb=11, hkv=2, bs=4, d=8, poison_scratch=False):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    pk = jnp.asarray(rng.standard_normal((nb, hkv, bs, d)), jnp.float32)
    pv = jnp.asarray(rng.standard_normal((nb, hkv, bs, d)), jnp.float32)
    if poison_scratch:
        pk = pk.at[0].set(jnp.nan)
        pv = pv.at[0].set(jnp.nan)
    return pk, pv


def _case(seed, b=3, h=4, c=4, d=8, nblk=5, nb=11, **kw):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    pk, pv = _pools(seed, nb=nb, d=d, **kw)
    # tables into REAL blocks only — scratch (block 0) is what unmapped
    # table entries point at in the engines, not a decodable block
    tables = jnp.asarray(rng.integers(1, nb, (b, nblk)), jnp.int32)
    q = jnp.asarray(rng.standard_normal((b, h, c, d)), jnp.float32)
    return q, pk, pv, tables


@pytest.mark.parametrize("window", [None, 6])
@pytest.mark.parametrize("kernel", FUSED)
def test_decode_parity_vs_reference(kernel, window):
    import jax.numpy as jnp
    q, pk, pv, tables = _case(0, c=1)
    pos = jnp.asarray([3, 9, 17], jnp.int32)
    ref = pa.attend(q, _fuse(pk, pv), tables, pos, 0.35, window=window,
                    kernel="reference")
    out = pa.attend(q, _fuse(pk, pv), tables, pos, 0.35, window=window,
                    kernel=kernel)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [None, 6])
@pytest.mark.parametrize("kernel", FUSED)
def test_chunk_parity_vs_reference(kernel, window):
    import jax.numpy as jnp
    q, pk, pv, tables = _case(1)
    start = jnp.asarray([0, 5, 12], jnp.int32)
    ref = pa.attend(q, _fuse(pk, pv), tables, start, 0.35, window=window,
                    kernel="reference")
    out = pa.attend(q, _fuse(pk, pv), tables, start, 0.35, window=window,
                    kernel=kernel)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("kernel", KERNELS)
def test_scalar_position_matches_vector(kernel):
    """Traced-scalar pos/start (the single-request prefill path) is the
    broadcast of the per-lane vector form."""
    import jax.numpy as jnp
    q, pk, pv, tables = _case(2)
    vec = pa.attend(q, _fuse(pk, pv), tables,
                    jnp.asarray([7, 7, 7], jnp.int32), 0.3, kernel=kernel)
    sca = pa.attend(q, _fuse(pk, pv), tables, jnp.int32(7), 0.3,
                    kernel=kernel)
    np.testing.assert_array_equal(np.asarray(vec), np.asarray(sca))


# ---------------------------------------------------------------------------
# the masking contract: -inf + guarded renorm, scratch poison isolated
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kernel", KERNELS)
def test_poisoned_scratch_block_cannot_leak(kernel):
    """NaN garbage in the scratch block (read only at MASKED positions
    when the tables map real blocks) must not reach any output — the
    old -1e9 masking left 0 * nan == nan paths open on the V side."""
    import jax.numpy as jnp
    q, pk, pv, tables = _case(3, c=1, poison_scratch=True)
    pos = jnp.asarray([3, 9, 17], jnp.int32)
    for window in (None, 6):
        out = pa.attend(q, _fuse(pk, pv), tables, pos, 0.35, window=window,
                        kernel=kernel)
        assert np.isfinite(np.asarray(out)).all(), (kernel, window)
    qc, pkc, pvc, tc = _case(4, poison_scratch=True)
    out = pa.attend(qc, _fuse(pkc, pvc), tc,
                    jnp.asarray([0, 5, 12], jnp.int32), 0.35, kernel=kernel)
    assert np.isfinite(np.asarray(out)).all()


@pytest.mark.parametrize("kernel", KERNELS)
def test_attended_nonfinite_still_propagates(kernel):
    """The poison sentinel's signal: a non-finite at an ATTENDED
    position (lane 1's table maps scratch at its first block) must
    reach that lane's output — and ONLY that lane's."""
    import jax.numpy as jnp
    q, pk, pv, tables = _case(5, c=1, poison_scratch=True)
    tables = tables.at[1, 0].set(0)            # attended scratch read
    pos = jnp.asarray([3, 9, 17], jnp.int32)
    out = np.asarray(pa.attend(q, _fuse(pk, pv), tables, pos, 0.35,
                               kernel=kernel))
    assert not np.isfinite(out[1]).all()
    assert np.isfinite(out[0]).all() and np.isfinite(out[2]).all()


@pytest.mark.parametrize("kernel", KERNELS)
def test_fully_masked_rows_are_exactly_zero(kernel):
    """Rows attending nothing (pos < 0 — no valid key yet) renormalise
    to exactly 0 through the guarded l == 0 branch, even with a
    poisoned scratch pool — never a softmax over a uniform -1e9 row."""
    import jax.numpy as jnp
    q, pk, pv, tables = _case(6, c=1, poison_scratch=True)
    neg = jnp.asarray([-1, -1, -1], jnp.int32)
    out = np.asarray(pa.attend(q, _fuse(pk, pv), tables, neg, 0.35,
                               kernel=kernel))
    assert (out == 0).all()


# ---------------------------------------------------------------------------
# the pallas core walks only the pages a lane attends: ragged lanes,
# poison everywhere else, the bounds against a brute-force count
# ---------------------------------------------------------------------------

RAGGED_BS, RAGGED_NBLK = 4, 11


def _ragged(seed, c, rep, hkv=2, d=8):
    """Six lanes in one call: start at 0, bs - 1, bs, mid-table, the
    table's last position (a chunk's tail then reaches past it), and a
    lane whose table row is all scratch. Every other lane owns its
    blocks, so a test can poison one lane's page and no other's."""
    import jax.numpy as jnp
    bs, nblk = RAGGED_BS, RAGGED_NBLK
    start = np.asarray([0, bs - 1, bs, 21, nblk * bs - 1, 13], np.int32)
    b = len(start)
    rng = np.random.default_rng(seed)
    nb = b * nblk + 1
    pk = rng.standard_normal((nb, hkv, bs, d)).astype(np.float32)
    pv = rng.standard_normal((nb, hkv, bs, d)).astype(np.float32)
    tables = 1 + rng.permutation(b * nblk).reshape(b, nblk).astype(np.int32)
    tables[-1] = 0
    q = jnp.asarray(rng.standard_normal((b, hkv * rep, c, d)), jnp.float32)
    return q, pk, pv, tables, start


# window 6 starts mid-page, window 3 is shorter than a page of 4;
# 16 query heads a kv-head is the hybrid cell's GQA 32 / 2
@pytest.mark.parametrize("window", [None, 6, 3])
@pytest.mark.parametrize("rep", [1, 4, 16], ids=["mha", "gqa4", "gqa16"])
@pytest.mark.parametrize("form", ["decode", "chunk"])
@pytest.mark.parametrize("kernel", FUSED)
def test_ragged_lanes_parity_vs_reference(kernel, form, rep, window):
    import jax.numpy as jnp
    q, pk, pv, tables, start = _ragged(7, 1 if form == "decode" else 5, rep)
    args = (q, _fuse(pk, pv), jnp.asarray(tables),
            jnp.asarray(start), 0.35)
    ref = pa.attend(*args, window=window, kernel="reference")
    out = pa.attend(*args, window=window, kernel=kernel)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [None, 6])
@pytest.mark.parametrize("form", ["decode", "chunk"])
@pytest.mark.parametrize("kernel", KERNELS)
def test_poison_outside_the_attended_keys_changes_nothing(kernel, form,
                                                          window):
    """NaN in every page outside a lane's `attended_pages`, and in the
    keys of its first and last visited page that no row attends, leaves
    the output finite and bit for bit what it was."""
    import jax.numpy as jnp
    c = 1 if form == "decode" else 5
    q, pk, pv, tables, start = _ragged(8, c, rep=2)
    tables[-1] = tables.max() + 1 + np.arange(RAGGED_NBLK)   # own blocks
    pk = np.concatenate([pk, pk[:RAGGED_NBLK]])
    pv = np.concatenate([pv, pv[:RAGGED_NBLK]])
    clean = pa.attend(q, _fuse(pk, pv), jnp.asarray(tables),
                      jnp.asarray(start), 0.35, window=window, kernel=kernel)
    pk[0] = pv[0] = np.nan
    for lane, st in enumerate(start):
        first = 0 if window is None else max(0, st - window + 1)
        keys = np.arange(RAGGED_NBLK * RAGGED_BS).reshape(RAGGED_NBLK, -1)
        dead = (keys < first) | (keys > st + c - 1)     # [nblk, bs]
        for j in range(RAGGED_NBLK):
            pk[tables[lane, j]][:, dead[j]] = np.nan
            pv[tables[lane, j]][:, dead[j]] = np.nan
    out = pa.attend(q, _fuse(pk, pv), jnp.asarray(tables),
                    jnp.asarray(start), 0.35, window=window, kernel=kernel)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_array_equal(np.asarray(out), np.asarray(clean))


@pytest.mark.parametrize("c,window", [(1, None), (1, 6), (1, 3), (5, None),
                                      (5, 6), (16, 40)])
def test_attended_pages_match_a_brute_force_count(c, window):
    """The exported bounds are exactly the pages that hold a key some
    row of the lane attends, for every start up to past the table."""
    bs, nblk = RAGGED_BS, RAGGED_NBLK
    start = np.arange(-1, nblk * bs + 3 * bs, dtype=np.int32)
    lo, hi = pa.attended_pages(start, c, bs, nblk, window)
    for st, a, z in zip(start, lo, hi):
        held = [j for j in range(nblk) for ks in range(j * bs, (j + 1) * bs)
                if any(ks <= st + i and (window is None
                                         or ks > st + i - window)
                       for i in range(c))]
        want = sorted(set(held))
        assert list(range(a, z)) == want, (st, a, z, want)
    # the kernel's own bounds are the same function on traced scalars
    import jax
    import jax.numpy as jnp
    traced = jax.vmap(lambda s: jnp.stack(
        pa.attended_pages(s, c, bs, nblk, window)))(jnp.asarray(start))
    np.testing.assert_array_equal(np.asarray(traced), np.stack([lo, hi], 1))


# window 3 is shorter than a page: its `lo` falls inside a step of
# several pages. Tiles: kv-heads in the grid or sharing a step; one page
# a step, three (the table's 11 do not divide), 32 (more than the table
# holds: one step, the rest of it past the table's end); the chunk's six
# queries whole, or in query tiles of three and of two, each with its
# own bounds
@pytest.mark.parametrize("window", [None, 6, 3])
@pytest.mark.parametrize("heads,pages,cq", [
    (1, 1, 0), (2, 1, 0), (1, 3, 0), (2, 3, 0), (2, 32, 0), (1, 3, 3),
    (2, 2, 2)])
@pytest.mark.parametrize("form", ["decode", "chunk"])
def test_every_tile_of_the_pallas_core_gives_the_reference(monkeypatch, form,
                                                           heads, pages, cq,
                                                           window):
    """One recurrence, whatever tile the shapes choose: kv-heads in the
    grid or folded into a step, one page a step or several, a last step
    that reaches past the table, a chunk's queries in one tile or in
    several."""
    import jax.numpy as jnp
    c = 1 if form == "decode" else 6
    monkeypatch.setattr(pa, "_tile",
                        lambda *_: (heads, pages, min(cq or c, c)))
    q, pk, pv, tables, start = _ragged(9, c, 2)
    args = (q, _fuse(pk, pv), jnp.asarray(tables),
            jnp.asarray(start), 0.35)
    ref = pa.attend(*args, window=window, kernel="reference")
    out = pa.attend(*args, window=window, kernel="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("window", [None, 24])
@pytest.mark.parametrize("form", ["decode", "chunk"])
def test_bfloat16_operands_give_the_float32_products(form, window):
    """Queries and pool both bfloat16: the kernel hands the MXU those
    values as they are (their products summed in float32) and keeps the
    probabilities float32, so what comes out is the float32 arithmetic
    on the same values (the reference's, up to the order of the sums)."""
    import jax.numpy as jnp
    q, pk, pv, tables, start = _ragged(11, 1 if form == "decode" else 8, 2)
    pool = _fuse(pk, pv).astype(jnp.bfloat16)
    q = q.astype(jnp.bfloat16)
    args = (jnp.asarray(tables), jnp.asarray(start), 0.35)
    out = pa.attend(q, pool, *args, window=window, kernel="pallas")
    ref = pa.attend(q.astype(jnp.float32), pool.astype(jnp.float32), *args,
                    window=window, kernel="reference")
    assert out.dtype == jnp.bfloat16
    # one rounding of the output to bfloat16 (2^-9 of values up to ~3)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               rtol=0, atol=2e-2)


#: the four K/V configurations the benchmark serves: query heads a
#: kv-head, kv-heads, head size, pages a lane's table holds, chunk
SERVED = {"gpt2-small": (1, 12, 64, 64, 128),
          "mistral-7b": (4, 8, 128, 160, 128),
          "nemotron3-nano": (16, 2, 128, 128, 128),
          "granite-4.0-h-micro": (4, 8, 64, 1088, 512)}


@pytest.mark.parametrize("form", ["wave", "chunk", "verify-k4"])
@pytest.mark.parametrize("config", sorted(SERVED))
def test_tile_follows_the_shapes_of_the_call(config, form):
    """The rule by the keys' side, at the served configurations' wave,
    chunk and verify-wave shapes (block 16, a bfloat16 pool)."""
    rep, hkv, d, nblk, chunk = SERVED[config]
    c = {"wave": 1, "chunk": chunk, "verify-k4": 5}[form]
    bs, itemsize = 16, 2
    heads, pages, cq = pa._tile(c, rep, hkv, d, bs, nblk, itemsize)
    assert hkv % heads == 0 and c % cq == 0 and 1 <= pages <= nblk
    # what the call asks the compiler for covers what a step plans
    assert pa._step_vmem(heads, pages, heads * rep * cq, d, bs,
                         itemsize) <= pa._VMEM_PLAN < pa._VMEM_LIMIT
    if form == "chunk":
        # one kv-head's queries against at least a whole vreg of keys,
        # hundreds where VMEM holds them beside the query tile
        assert heads == 1 and pages * bs >= 256
        assert rep * cq <= pa._MAX_ROWS
    else:
        # every kv-head shares a step, and a step carries what the
        # chip's table says: a quarter of the table's pages as a power
        # of two, 8 to 64 of them
        assert heads == hkv and cq == c
        quarter = {64: 16, 160: 64, 128: 32, 1088: 64}[nblk]
        assert pages == quarter or (
            pages == quarter // 2 and pa._step_vmem(
                heads, quarter, heads * rep * cq, d, bs,
                itemsize) > pa._VMEM_PLAN)
        assert form == "verify-k4" or pages == quarter
        assert pages * hkv * bs * 2 * d * itemsize >= 3 << 17


@pytest.mark.parametrize("c", [1, 128])
def test_a_step_never_takes_more_pages_than_the_table_holds(c):
    """A table of 4 pages is one step of 4, in both forms (of 11, one
    of 8 and a rest), and a chunk too long for one query tile is cut
    where the tiles divide it."""
    assert pa._tile(c, 4, 8, 128, 16, 4, 2)[1] == 4
    assert pa._tile(c, 4, 8, 128, 16, 11, 2)[1] == 8
    heads, pages, cq = pa._tile(4096, 4, 8, 64, 16, 1088, 2)
    assert heads == 1 and 4096 % cq == 0 and 4 * cq <= pa._MAX_ROWS
    assert pages * 16 >= 128


# ---------------------------------------------------------------------------
# dispatch front door: two names, one rule
# ---------------------------------------------------------------------------

def test_kernel_resolution_order(monkeypatch):
    import jax
    assert pa.KERNELS == ("reference", "pallas")
    # nothing said: what the backend decides
    assert pa.resolve_kernel() == "reference"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pa.resolve_kernel() == "pallas"
    # a scope beats the backend; inner scope beats outer; explicit
    # beats scope
    with pa.kernel_scope("reference"):
        assert pa.resolve_kernel() == "reference"
        with pa.kernel_scope("pallas"):
            assert pa.resolve_kernel() == "pallas"
            assert pa.resolve_kernel("reference") == "reference"
        assert pa.resolve_kernel() == "reference"
    assert pa.resolve_kernel() == "pallas"


def test_unknown_kernel_rejected(model):
    for name in ("flash", "lax", "auto", ""):
        with pytest.raises(ValueError, match="unknown paged kernel"):
            pa.resolve_kernel(name)
        with pytest.raises(ValueError, match="unknown paged kernel"):
            with pa.kernel_scope(name):
                pass
        with pytest.raises(ValueError, match="unknown paged kernel"):
            _engine(model, name)


# ---------------------------------------------------------------------------
# engine-level parity: the same tokens through every kernel
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def model():
    pt.seed(7)
    cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, max_seq_len=MAX_LEN)
    return LlamaForCausalLM(cfg)


def _engine(model, kernel):
    return PagedServingEngine(model, num_slots=4, max_len=MAX_LEN,
                              block_size=BLOCK, num_blocks=33,
                              prefill_chunk_len=CHUNK,
                              paged_kernel=kernel)


def _jobs(seed, n=8):
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, VOCAB, (int(rng.randint(2, 14)),)).tolist(),
             int(rng.randint(2, 10))) for _ in range(n)]


def _stream(engine, jobs, **kw):
    sched = Scheduler(engine)
    reqs = [sched.submit(prompt=p, max_tokens=m, **kw) for p, m in jobs]
    sched.run()
    return reqs


@pytest.mark.parametrize("kernel", FUSED)
def test_engine_stream_token_identical_across_kernels(model, kernel):
    """Mixed-length multi-wave stream (8 requests on 4 slots, two
    admission waves): the fused engine's tokens equal the
    reference-kernel engine's token for token, with compile-once and
    the configured kernel surfaced in /healthz."""
    jobs = _jobs(1)
    ref = _stream(_engine(model, "reference"), jobs)
    eng = _engine(model, kernel)
    out = _stream(eng, jobs)
    assert [r.output_tokens for r in out] == \
        [r.output_tokens for r in ref]
    assert [r.finish_reason for r in out] == \
        [r.finish_reason for r in ref]
    assert eng.decode_compiles == 1
    assert eng.prefill_compiles == 1
    assert eng.paged_kernel == kernel
    assert eng._health()["paged_kernel"] == kernel


@pytest.mark.parametrize("window", [None, 12])
def test_engine_counts_the_pages_its_waves_visit(window):
    """`paged_pages_visited` is the sum, over every staged wave's lanes,
    of the pages holding a key the lane attends at its `slot_pos`
    (lanes not in the wave ride along at a stale one), counted here key
    by key; `paged_pages_spanned` is waves x slots x blocks per lane."""
    pt.seed(7)
    cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=64, num_layers=1,
                      num_heads=4, num_kv_heads=2, max_seq_len=MAX_LEN,
                      attn_window=window)
    eng = _engine(LlamaForCausalLM(cfg), None)
    waves, stage = [], eng._wave_args

    def spy(*args):
        waves.append(np.array(eng.slot_pos))
        return stage(*args)

    eng._wave_args = spy
    sched = Scheduler(eng)
    for prompt, m in _jobs(2, n=6):
        sched.submit(prompt=prompt, max_tokens=m)
    sched.run()
    snap = sched.metrics.snapshot()
    nblk = MAX_LEN // BLOCK
    assert waves and snap["paged_pages_spanned"] == len(waves) * 4 * nblk
    want = sum(len({ks // BLOCK for ks in range(int(pos) + 1)
                    if window is None or ks > pos - window})
               for wave in waves for pos in wave)
    assert snap["paged_pages_visited"] == want
    assert 0 < want < snap["paged_pages_spanned"]


@pytest.mark.parametrize("window", [None, 12])
def test_engine_counts_the_steps_its_kernel_runs(window):
    """`paged_steps_run` / `paged_steps_visited` are, summed over every
    staged wave (all lanes at their `slot_pos`) and every staged prefill
    chunk (one lane at the chunk's start), the grid the kernel's call
    runs a layer (every query tile's steps from its first attended page
    to its last; one step for a tile that attends nothing) and the steps
    of it that score pages: here counted one by one against each query
    tile's `attended_pages`."""
    pt.seed(7)
    cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=64, num_layers=1,
                      num_heads=4, num_kv_heads=2, max_seq_len=MAX_LEN,
                      attn_window=window)
    eng = _engine(LlamaForCausalLM(cfg), None)
    calls, wave, prompt = [], eng._wave_args, eng._prompt_args

    def spy_wave(*args):
        calls.append((np.array(eng.slot_pos, np.int32), 1))
        return wave(*args)

    def spy_prompt(slot, chunk, c0, *rest):
        calls.append((np.asarray([c0], np.int32), CHUNK))
        return prompt(slot, chunk, c0, *rest)

    eng._wave_args, eng._prompt_args = spy_wave, spy_prompt
    sched = Scheduler(eng)
    rng = np.random.RandomState(3)
    for n in (5, 23, 40, 17, 33, 9):
        sched.submit(prompt=rng.randint(0, VOCAB, (n,)).tolist(),
                     max_tokens=int(rng.randint(2, 10)))
    sched.run()
    snap = sched.metrics.snapshot()
    nblk, hkv, rep, d = MAX_LEN // BLOCK, 2, 2, 16
    assert {c for _, c in calls} == {1, CHUNK}
    assert sum(c == CHUNK for _, c in calls) > 6     # chunks past the first
    run = visited = 0
    for start, c in calls:
        heads, pages, cq = pa._tile(c, rep, hkv, d, BLOCK, nblk, 4)
        for st in start:
            for t in range(c // cq):
                lo, hi = pa.attended_pages(int(st) + t * cq, cq, BLOCK,
                                           nblk, window)
                steps = sum(j * pages < hi and (j + 1) * pages > lo
                            for j in range(-(-nblk // pages)))
                run += hkv // heads * max(int(steps), 1)
                visited += hkv // heads * int(steps)
    assert snap["paged_steps_run"] == run
    assert snap["paged_steps_visited"] == visited
    assert 0 < visited <= run


@pytest.mark.parametrize("kernel", FUSED)
def test_engine_sampled_stream_identical_across_kernels(model, kernel):
    """Sampled decoding (temperature 0.8, per-engine PRNG seeded
    identically): the sampled trajectories are bitwise the reference
    kernel's — the fused scores feed the same categorical draws."""
    jobs = _jobs(2, n=6)
    kw = dict(do_sample=True, temperature=0.8)
    ref = _stream(_engine(model, "reference"), jobs, **kw)
    out = _stream(_engine(model, kernel), jobs, **kw)
    assert [r.output_tokens for r in out] == \
        [r.output_tokens for r in ref]


@pytest.mark.parametrize("kernel", FUSED)
def test_spec_engine_token_identical_across_kernels(model, kernel):
    """The speculative trio (draft wave, verify, chunked prefill) under
    a fused kernel equals the reference-kernel speculative engine AND
    stays at three compiled programs."""
    from paddle_tpu.serving import SpeculativePagedEngine
    pt.seed(23)
    dcfg = LlamaConfig(vocab_size=VOCAB, hidden_size=32, num_layers=1,
                       num_heads=2, num_kv_heads=1, max_seq_len=MAX_LEN)
    draft = LlamaForCausalLM(dcfg)

    def spec(k):
        return SpeculativePagedEngine(model, draft, spec_k=3,
                                      num_slots=4, max_len=MAX_LEN,
                                      block_size=BLOCK, num_blocks=33,
                                      prefill_chunk_len=CHUNK,
                                      paged_kernel=k)
    jobs = _jobs(3, n=6)
    ref = _stream(spec("reference"), jobs)
    eng = spec(kernel)
    out = _stream(eng, jobs)
    assert [r.output_tokens for r in out] == \
        [r.output_tokens for r in ref]
    assert eng.draft_compiles == 1
    assert eng.decode_compiles == 1
    assert eng.prefill_compiles == 1


def test_engine_scratch_poison_regression(model):
    """Poison the LIVE pool's scratch block (block 0) with NaN after
    warmup: every kernel still produces the clean engine's tokens and
    no non-finite fault fires — scratch garbage is read only at masked
    positions and the -inf masking keeps it out of the logits."""
    jobs = _jobs(4, n=4)
    want = [r.output_tokens for r in _stream(_engine(model, "reference"),
                                             jobs)]
    import jax.numpy as jnp
    for kernel in KERNELS:
        eng = _engine(model, kernel)
        Scheduler(eng).generate([1, 2, 3], max_tokens=2)   # warm/compile
        eng._caches = [pool.at[0].set(jnp.nan) for pool in eng._caches]
        sched = Scheduler(eng)
        reqs = [sched.submit(prompt=p, max_tokens=m) for p, m in jobs]
        sched.run()
        assert [r.output_tokens for r in reqs] == want, kernel
        assert sched.metrics.snapshot()["faults"] == {}, kernel


def test_front_door_via_inference_config(model):
    """inference.Config.enable_llm_engine(paged_kernel=...) reaches the
    engine through create_llm_predictor, token-compatible with a
    directly-built reference engine."""
    from paddle_tpu import inference
    cfg = inference.Config()
    cfg.enable_llm_engine(paged=True, num_slots=2, max_len=48,
                          prefill_len=16, block_size=8,
                          paged_kernel="pallas")
    pred = inference.create_llm_predictor(cfg, model=model)
    assert pred.engine.paged_kernel == "pallas"
    prompt = _prompt_tokens(31)
    ref = PagedServingEngine(model, num_slots=2, max_len=48,
                             block_size=8, prefill_chunk_len=16,
                             paged_kernel="reference")
    assert pred.generate(prompt, max_tokens=4) == \
        Scheduler(ref).generate(prompt, max_tokens=4)


def test_front_door_health_names_the_resolved_core(model):
    """A single-engine predictor's health() is the engine's /healthz
    payload (it used to raise AttributeError: only the fleet router had
    a health()); chip_smoke.py reads status and the resolved paged core
    from it."""
    from paddle_tpu import inference
    cfg = inference.Config().enable_llm_engine(
        paged=True, num_slots=2, max_len=48, prefill_len=16, block_size=8)
    pred = inference.create_llm_predictor(cfg, model=model)
    pred.generate(_prompt_tokens(32), max_tokens=2)
    health = pred.health()
    assert health["status"] == "ok"
    assert health["paged_kernel"] == "reference"           # off the TPU
    assert health["decode_compiles"] == 1


def _prompt_tokens(seed, n=5):
    return np.random.RandomState(seed).randint(0, VOCAB, (n,)).tolist()
