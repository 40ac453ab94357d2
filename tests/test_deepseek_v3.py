"""nlp.DeepseekV3ForCausalLM — latent attention (MLA) over a paged pool
of latent rows, absorbed in the decode wave and expanded in a prompt
chunk, a leading dense layer, gated routed experts — and what the paged
engine does for a model whose pages hold latent rows.

Everything runs at a tiny size in float32 on the CPU. The yardstick is
`benchmark/reference/deepseek_v3.py`, the plain float32 forward written
from the equations (expanded attention over all positions, a loop over
all experts), which shares no code with the program.

Tolerance, relative to the largest reference logit (or value): 2e-5
where both sides are float32 and differ only in the order of their sums
(absorbed against expanded, cached against whole, kernel against oracle,
grouped experts against the loop): float32 rounds at 6e-8 and a logit
sums a few thousand products over three layers.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from benchmark.reference import deepseek_v3 as ref
from paddle_tpu import inference
from paddle_tpu.nlp import DeepseekV3Config, DeepseekV3ForCausalLM
from paddle_tpu.nn import paged_attention as pa
from paddle_tpu.ops.pallas.grouped_mlp import grouped_mlp
from paddle_tpu.serving import (PagedServingEngine, Scheduler,
                                SpeculativePagedEngine)
from paddle_tpu.serving.paged.engine import HandoffRefused

VOCAB, MAX_LEN, BLOCK, CHUNK = 96, 64, 8, 16
TOL = 2e-5
RANK, ROPE, NOPE, VD, HEADS = 32, 8, 16, 16, 4
SIZES = dict(vocab_size=VOCAB, hidden_size=64, intermediate_size=96,
             moe_intermediate_size=32, num_hidden_layers=3,
             num_attention_heads=HEADS, kv_lora_rank=RANK,
             qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE, v_head_dim=VD,
             n_routed_experts=8, n_shared_experts=2, num_experts_per_tok=3,
             max_position_embeddings=128, initializer_range=0.2)


def _model(seed=11, **over):
    """A seeded tiny model whose vectors are off their neutral values,
    so that every norm's scale and the router's correction take part."""
    pt.seed(seed)
    model = DeepseekV3ForCausalLM(DeepseekV3Config(**{**SIZES, **over}))
    rng = np.random.default_rng(seed)
    for _, p in model.named_parameters():
        if len(p.shape) == 1:
            p.set_value((np.asarray(p._data)
                         + rng.normal(0, 0.2, p.shape)).astype(np.float32))
    return model.eval()


def _cfg(model):
    """The configuration file's keys, as the reference reads them."""
    c = model.cfg
    return {k: getattr(c, k) for k in (
        "num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "rope_theta",
        "num_experts_per_tok", "routed_scaling_factor", "rms_norm_eps",
        "first_k_dense_replace")}


def _reference_logits(model, ids):
    state = {n: p._data for n, p in model.named_parameters()}
    rw = ref.from_state_dict(state, model.cfg.num_layers)
    return np.asarray(ref.forward(rw, np.asarray(ids), _cfg(model)))


def _close(got, want, tol=TOL):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * np.abs(want).max() + 1e-7


@pytest.fixture(scope="module")
def model():
    return _model()


@pytest.fixture(scope="module")
def engine(model):
    return PagedServingEngine(model, num_slots=4, max_len=MAX_LEN,
                              block_size=BLOCK, prefill_chunk_len=CHUNK)


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, VOCAB, n).tolist()


@pytest.fixture
def path(request, monkeypatch):
    """Which way the latent pool is attended: "rule" leaves
    `latent_path` to decide (absorbed at these sizes, for a wave and a
    chunk of 16 alike), the others force one way for every call."""
    if request.param != "rule":
        monkeypatch.setattr(pa, "latent_path",
                            lambda *a: request.param)
    return request.param


# ------------------------------------------------------------- the model
@pytest.mark.parametrize("layers,dense,seq", [(3, 1, 21), (2, 0, 16),
                                              (2, 2, 9), (4, 1, 40)])
def test_forward_equals_the_reference(layers, dense, seq):
    model = _model(num_hidden_layers=layers, first_k_dense_replace=dense)
    ids = np.random.default_rng(0).integers(0, VOCAB, (2, seq))
    _close(model(ids)._data, _reference_logits(model, ids))
    assert [blk.dense for blk in model.layers] == \
        [i < dense for i in range(layers)]


def test_parameters_are_created_in_the_named_dtype_and_kept():
    model = DeepseekV3ForCausalLM(DeepseekV3Config(
        **{**SIZES, "param_dtype": "bfloat16", "init_weights": False}))
    for name, p in model.named_parameters():
        assert p._data.dtype == jnp.bfloat16, name
        if len(p.shape) >= 2 and ".mlp.gate_proj" not in name \
                and ".mlp.up_proj" not in name \
                and ".mlp.down_proj" not in name:
            assert not np.asarray(p._data, np.float32).any(), name
    names = {n.split(".", 2)[-1] for n, _ in model.named_parameters()
             if n.startswith("layers.1.")}
    assert {"mlp.experts_gate", "mlp.shared_gate", "self_attn.kv_b_proj",
            "self_attn.kv_a_norm_weight"} <= names


@pytest.mark.parametrize("kw,what", [
    ({"rope_scaling": {"type": "linear", "factor": 4.0}},
     "rope_scaling.type='linear'"),
    ({"n_group": 8}, "n_group"),
    ({"hc_mult": 0}, "hc_mult=0"),
    ({"num_experts_per_tok": 9}, "num_experts_per_tok 9 > "),
    ({"qk_rope_head_dim": 7}, "even")])
def test_config_refuses_what_is_not_computed(kw, what):
    with pytest.raises(ValueError, match=what):
        DeepseekV3Config(**{**SIZES, **kw})


# ------------------------------------------------- the gated expert kernel
def _gated_loop(x, up, gate, down, owner):
    x, up, gate, down = (np.asarray(a, np.float64)
                         for a in (x, up, gate, down))
    rows = []
    for i, e in enumerate(owner):
        g = x[i] @ gate[e].T
        rows.append((g / (1 + np.exp(-g)) * (x[i] @ up[e].T)) @ down[e])
    return np.stack(rows)


@pytest.mark.parametrize("sizes", [
    [0, 1, 16, 0, 17, 30, 3],       # empty experts, a block that straddles
    [5, 0, 0, 0, 0, 0, 11],         # the tail of one, the head of the next
    [64, 0, 0, 0, 0, 0, 0]])
def test_gated_grouped_kernel_equals_a_loop_over_experts(sizes):
    """Groups of 0, 1, 16, 17 and 30 rows: blocks of 16 rows that hold
    one expert, two, or the tail of one and the head of the next."""
    rng = np.random.default_rng(5)
    sizes = np.asarray(sizes, np.int32)
    x = jnp.asarray(rng.normal(0, 1, (int(sizes.sum()), 32)), jnp.float32)
    up, gate, down = (jnp.asarray(rng.normal(0, 0.3, (7, 24, 32)),
                                  jnp.float32) for _ in range(3))
    got = grouped_mlp(x, up, down, jnp.asarray(sizes), gate=gate)
    _close(got, _gated_loop(x, up, gate, down,
                            np.repeat(np.arange(7), sizes)))


@pytest.mark.parametrize("width,experts,tokens", [
    (32, 8, 18), (48, 4, 7), (32, 8, 1400)])
def test_gated_experts_equal_the_expert_loop(width, experts, tokens):
    """Sorted picks through the gated kernel (interpreted here) against
    the reference's loop over every expert; 1,400 tokens x 3 picks pass
    `MAX_ROWS` and go through it a segment at a time."""
    model = _model(moe_intermediate_size=width, n_routed_experts=experts)
    moe = model.layers[1].mlp
    assert moe.gated and moe.experts_gate.shape == [experts, width, 64]
    x = jnp.asarray(np.random.default_rng(3).normal(0, 1, (tokens, 64)),
                    jnp.float32)
    lw = {"mlp." + n: p._data for n, p in moe.named_parameters()}
    with jax.default_matmul_precision("highest"):
        want = ref._experts(x[None], lw, 3,
                            model.cfg.routed_scaling_factor)[0]
    _close(moe(x), want)


# ------------------------------------------------------- the latent pool
def _pool(rng, blocks=40, dtype=jnp.float32):
    width = pa.latent_width(RANK, ROPE)
    pool = rng.normal(0, 1, (blocks, BLOCK, width))
    pool[..., RANK + ROPE:] = 0
    return jnp.asarray(pool, dtype)


def _tables(rng, lanes, nblk, blocks=40):
    return jnp.asarray(rng.permutation(np.arange(1, blocks))[:lanes * nblk]
                       .reshape(lanes, nblk), jnp.int32)


def test_the_stored_form_is_one_padded_row_a_position(model):
    assert pa.latent_width(512, 64) == 640 and pa.latent_width(RANK, ROPE) \
        == 128
    caches = model.init_paged_cache(9, BLOCK, MAX_LEN)
    assert [c.shape for c in caches] == [(9, BLOCK, 128)] * 3
    with pytest.raises(ValueError, match="rotary table"):
        model.init_paged_cache(9, BLOCK, 256)


@pytest.mark.parametrize("c,start,valid", [(1, [5, 0, 23], None),
                                           (5, [6, 0, 14], [5, 2, 0]),
                                           (16, [8, 16, 40], [16, 9, 16])])
def test_the_latent_write_moves_its_rows_and_nothing_else(c, start, valid):
    """Rows land at start + i for i < valid_len through the tables,
    zero-extended to the stored width; every other row keeps its bits;
    scratch is zeroed."""
    rng = np.random.default_rng(c)
    pool, tables = _pool(rng), _tables(rng, 3, 8)
    rows = jnp.asarray(rng.normal(0, 1, (3, c, RANK + ROPE)), jnp.float32)
    got = np.asarray(pa.write_block_latent(
        pool, rows, tables, jnp.asarray(start, jnp.int32),
        None if valid is None else jnp.asarray(valid, jnp.int32)))
    want = np.asarray(pool).copy()
    want[0] = 0
    for s in range(3):
        for i in range(c if valid is None else valid[s]):
            p = start[s] + i
            want[int(tables[s, p // BLOCK]), p % BLOCK] = np.pad(
                np.asarray(rows[s, i]), (0, 128 - RANK - ROPE))
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("c", [1, 4])
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, TOL),
                                       (jnp.bfloat16, 2e-2)])
def test_the_latent_kernel_equals_the_oracle(c, dtype, tol):
    """The Pallas kernel (interpreted here) against gather-then-softmax:
    lanes at the first position, inside a page and across many, in the
    decode form and at four queries a lane."""
    rng = np.random.default_rng(2)
    pool, tables = _pool(rng, dtype=dtype), _tables(rng, 3, 6)
    q = rng.normal(0, 1, (3, HEADS, c, 128))
    q[..., RANK + ROPE:] = 0
    q, start = jnp.asarray(q, dtype), jnp.asarray([5, 0, 41], jnp.int32)
    want = pa.attend_latent(q, pool, tables, start, 0.2, kernel="reference")
    got = pa.attend_latent(q, pool, tables, start, 0.2, kernel="pallas")
    assert got.dtype == want.dtype == jnp.float32
    _close(got, want, tol)


def _attend(core, q_nope, q_rope, w, pool, tables, start, scale=0.2):
    """One of the three ways through the pool as it stands: the two
    absorbed cores, or the expanded path."""
    if core == "expanded":
        return pa._expanded_core(q_nope, q_rope, w, pool, tables, start,
                                 scale)
    q_abs = jnp.einsum("bhcn,rhn->bhcr", q_nope, w[..., :NOPE])
    q = jnp.pad(jnp.concatenate([q_abs, q_rope], -1),
                ((0, 0),) * 3 + ((0, 128 - RANK - ROPE),))
    o = pa.attend_latent(q, pool, tables, start, scale, kernel=core)
    return jnp.einsum("bhcr,rhv->bhcv", o[..., :RANK], w[..., NOPE:])


@pytest.mark.parametrize("core", ["reference", "pallas", "expanded"])
def test_masked_lanes_and_poisoned_scratch_under_the_contract(core):
    """A lane whose table is all scratch attends nothing it is allowed
    to see and gives exact zeros, not nan, though scratch holds nan and
    inf; a lane beside it is untouched by them; a nan at a position a
    lane does attend reaches that lane's output."""
    rng = np.random.default_rng(4)
    pool, tables = _pool(rng), _tables(rng, 3, 6)
    pool = pool.at[0].set(jnp.nan).at[0, 3].set(jnp.inf)
    tables = tables.at[1].set(0).at[0, 2:].set(0)
    q_nope = jnp.asarray(rng.normal(0, 1, (3, HEADS, 1, NOPE)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(0, 1, (3, HEADS, 1, ROPE)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.3, (RANK, HEADS, NOPE + VD)),
                    jnp.float32)
    # lane 0 attends 12 positions of its two pages; lane 1 sits at -1:
    # no key at all; lane 2 attends 30 positions
    start = jnp.asarray([11, -1, 29], jnp.int32)
    out = np.asarray(_attend(core, q_nope, q_rope, w, pool, tables, start))
    assert np.isfinite(out).all()
    assert not out[1].any() and out[0].any() and out[2].any()
    clean = np.asarray(_attend(core, q_nope, q_rope, w,
                               pool.at[0].set(0.0), tables, start))
    np.testing.assert_array_equal(out, clean)
    poisoned = pool.at[int(tables[2, 1]), 2, 5].set(jnp.nan)
    out = np.asarray(_attend(core, q_nope, q_rope, w, poisoned, tables,
                             start))
    assert np.isnan(out[2]).any() and np.isfinite(out[0]).all()


@pytest.mark.parametrize("c,start,valid", [(1, [5, 0, 23], None),
                                           (3, [6, 0, 14], [3, 2, 3]),
                                           (16, [8, 16, 32], [16, 9, 16])])
def test_absorbed_and_expanded_agree_on_the_same_rows(c, start, valid,
                                                      monkeypatch):
    """One call of the layer's entry point each way: the same rows
    written, the same outputs for every row that is not padding."""
    rng = np.random.default_rng(c)
    pool, tables = _pool(rng), _tables(rng, 3, 8)
    q_nope = jnp.asarray(rng.normal(0, 1, (3, HEADS, c, NOPE)), jnp.float32)
    q_rope = jnp.asarray(rng.normal(0, 1, (3, HEADS, c, ROPE)), jnp.float32)
    rows = jnp.asarray(rng.normal(0, 1, (3, c, RANK + ROPE)), jnp.float32)
    w = jnp.asarray(rng.normal(0, 0.3, (RANK, HEADS, NOPE + VD)),
                    jnp.float32)
    args = (q_nope, q_rope, rows, w, pool, tables,
            jnp.asarray(start, jnp.int32),
            None if valid is None else jnp.asarray(valid, jnp.int32), 0.2)
    got = {}
    for way in ("absorbed", "expanded"):
        monkeypatch.setattr(pa, "latent_path", lambda *a, _w=way: _w)
        got[way] = pa.paged_attend_latent(*args, kernel="reference")
    np.testing.assert_array_equal(got["absorbed"][1], got["expanded"][1])
    for s in range(3):
        n = c if valid is None else valid[s]
        _close(got["absorbed"][0][s, :, :n], got["expanded"][0][s, :, :n])


@pytest.mark.parametrize("c,way", [(1, "absorbed"), (5, "absorbed"),
                                   (170, "absorbed"), (171, "expanded"),
                                   (512, "expanded")])
def test_the_path_follows_from_the_queries_a_lane_brings(c, way):
    """At the published sizes absorbed costs 2,176 operations a (query,
    row, head), expanded 640 and 262,144 a (row, head): they cross at
    170 queries."""
    assert pa.latent_path(c, 512, 64, 128, 128) == way


def test_expanded_rows_are_the_attended_pages_in_whole_tiles():
    # chunk 512 at 8192 of a 640-page table, pages of 16: 544 pages
    assert pa.expanded_rows(8192, 512, 16, 640) == 544 * 16
    assert pa.expanded_rows(0, 512, 16, 640) == 32 * 16
    assert pa.expanded_rows(0, 16, 8, 8) == 8 * 8      # tile = the table
    assert list(pa.expanded_rows(np.asarray([0, 520]), 1, 16, 640)) == \
        [512, 1024]


# ---------------------------------------------------- through the engine
class _Logits:
    """Records the logits of every model call an uncompiled engine makes."""

    def __init__(self, model):
        self.model, self.chunks, self.waves = model, [], []
        for name, log in (("prefill_chunk", self.chunks),
                          ("decode_step", self.waves)):
            inner = getattr(model, name)

            def spy(*a, _inner=inner, _log=log, **k):
                logits, caches = _inner(*a, **k)
                _log.append(np.asarray(logits))
                return logits, caches
            setattr(model, name, spy)

    def restore(self):
        del self.model.prefill_chunk, self.model.decode_step


@pytest.mark.parametrize("path", ["rule", "expanded", "absorbed"],
                         indirect=True)
@pytest.mark.parametrize("n", [5, 16, 37])
def test_prefill_in_chunks_then_decode_equals_the_reference(model, n, path):
    """Prompts of under one chunk, exactly one, and several with a
    ragged last one, then six decoded tokens, through the engine's own
    programs (uncompiled, so that the logits can be read): every logit
    row against the reference's full forward over prompt + tokens, with
    the pool attended each way."""
    eng = PagedServingEngine(model, num_slots=2, max_len=MAX_LEN,
                             block_size=BLOCK, prefill_chunk_len=CHUNK,
                             jit_compile=False)
    spy = _Logits(model)
    try:
        prompt = _prompt(n, n)
        out = Scheduler(eng).generate(prompt, max_tokens=7)
    finally:
        spy.restore()
    want = _reference_logits(model, [prompt + out])[0]
    assert len(spy.chunks) == -(-n // CHUNK) and len(spy.waves) == 6
    _close(spy.chunks[-1][0, 0], want[n - 1])
    for i, lo in enumerate(spy.waves):
        # slot 0 is the request's lane; the other lane is inactive
        _close(lo[0, 0], want[n + i])
    assert out == [int(np.argmax(want[n - 1 + i])) for i in range(7)]


@pytest.mark.parametrize("path", ["rule", "expanded"], indirect=True)
def test_a_prefix_hit_gives_the_logits_of_sharing_off(model, path):
    """The second request finds three pages of its prompt resident and
    starts its prefill behind them: its first-token logits and its
    decoded rows are those of an engine that shares nothing, and of the
    reference."""
    head = _prompt(5, 3 * BLOCK + 2)
    jobs = [head + _prompt(6, 5), head + _prompt(7, 9)]
    rows = {}
    for sharing in (True, False):
        eng = PagedServingEngine(model, num_slots=2, max_len=MAX_LEN,
                                 block_size=BLOCK, prefill_chunk_len=CHUNK,
                                 jit_compile=False, prefix_sharing=sharing)
        sched = Scheduler(eng)
        sched.generate(jobs[0], max_tokens=3)
        spy = _Logits(model)
        try:
            out = sched.generate(jobs[1], max_tokens=5)
        finally:
            spy.restore()
        rows[sharing] = (out, spy.chunks[-1][0, 0],
                         [w[0, 0] for w in spy.waves], len(spy.chunks))
        if sharing:
            assert eng.block_pool.prefix_hits == 3
            assert eng._health()["latent_cache"] is True
    # 35 tokens: three chunks unshared; the hit skips the first (its
    # pages 0 and 1 are resident, page 2 is rewritten by chunk 1)
    assert (rows[True][3], rows[False][3]) == (2, 3)
    assert rows[True][0] == rows[False][0]
    want = _reference_logits(model, [jobs[1] + rows[True][0]])[0]
    n = len(jobs[1])
    for got in (rows[True], rows[False]):
        _close(got[1], want[n - 1])
        for i, lo in enumerate(got[2]):
            _close(lo, want[n + i])


def _solo(model, prompt, max_tokens):
    fresh = PagedServingEngine(model, num_slots=4, max_len=MAX_LEN,
                               block_size=BLOCK, prefill_chunk_len=CHUNK,
                               prefix_sharing=False)
    return Scheduler(fresh).generate(prompt, max_tokens=max_tokens)


def test_shared_pages_copy_on_write_and_eviction_are_unchanged(model):
    """A page of latent rows is a page: two requests that share a prefix
    decode side by side on shared pages, a forced copy-on-write copies
    the latent page, and a pool too small for everyone preempts and
    recomputes; every answer equals a solo run."""
    eng = PagedServingEngine(model, num_slots=4, max_len=MAX_LEN,
                             block_size=BLOCK, num_blocks=14,
                             prefill_chunk_len=CHUNK)
    head = _prompt(5, 2 * BLOCK)
    jobs = [head + _prompt(20 + i, 6) for i in range(4)]
    sched = Scheduler(eng)
    reqs = [sched.submit(prompt=p, max_tokens=12) for p in jobs]
    sched.run()
    assert all(r.finish_reason == "max_tokens" for r in reqs)
    assert eng.block_pool.prefix_hits >= 2
    for p, r in zip(jobs, reqs):
        assert r.output_tokens == _solo(model, p, 12)
    src, dst = 3, 9
    before = [np.asarray(c) for c in eng._caches]
    eng._caches = eng._copy_block(eng._caches, src, dst)
    for old, new in zip(before, eng._caches):
        np.testing.assert_array_equal(np.asarray(new)[dst], old[src])


def test_speculation_and_handoff_refuse_the_latent_form_by_name(model,
                                                                engine):
    with pytest.raises(ValueError, match="speculative decoding.*latent "
                                         "rows"):
        SpeculativePagedEngine(model, model, num_slots=2, max_len=MAX_LEN,
                               block_size=BLOCK)
    with pytest.raises(HandoffRefused, match="export_slot_kv.*K/V pages"):
        engine.export_slot_kv(0)
    with pytest.raises(HandoffRefused, match="import_handoff.*latent_cache"):
        engine.import_handoff(0, _prompt(0, 4), {})


def test_counters_of_the_latent_cache_and_the_experts(model, engine):
    sched = Scheduler(engine)
    snap0 = sched.metrics.snapshot()
    out = sched.generate(_prompt(9, 20), max_tokens=4)
    snap = sched.metrics.snapshot()
    # 20 prompt tokens in two chunks of 16, then three decoded tokens
    assert snap["prefill_tokens"] - snap0["prefill_tokens"] == 20
    # the table is one tile of 8 pages: a chunk expands 64 rows if it
    # takes the expanded path (the count is of the path, taken or not)
    assert snap["mla_rows_expanded"] - snap0["mla_rows_expanded"] == 2 * 64
    # waves at positions 20, 21, 22 attend 21 + 22 + 23 rows
    assert snap["mla_rows_attended"] - snap0["mla_rows_attended"] == 66
    # two expert layers, top-3
    assert snap["moe_picks"] - snap0["moe_picks"] == \
        (20 + len(out) - 1) * 2 * 3
    assert model.moe_picks_per_token == 6 and snap["state_resets"] == 0


@pytest.mark.parametrize("path", ["rule", "expanded"], indirect=True)
def test_device_work_carries_its_scope_names(model, path):
    """`mla_absorb`, `moe_route`, `moe_experts`, `moe_shared` name the
    wave's instructions, `mla_expand` a chunk's on the expanded path."""
    engine = PagedServingEngine(model, num_slots=4, max_len=MAX_LEN,
                                block_size=BLOCK, prefill_chunk_len=CHUNK,
                                paged_kernel="pallas")
    key = jax.random.PRNGKey(0)
    greedy = engine._sampling_state(False, 1.0, 0, 1.0, None, False)
    wave = jax.jit(engine._decode_wave_fn).lower(
        *engine._wave_args([True] * 4, np.zeros(4, bool), key)
    ).as_text(debug_info=True)
    chunk = jax.jit(engine._prefill_fn).lower(
        engine._params, engine._buffers, engine._caches,
        *engine._prompt_args(0, np.zeros(CHUNK, np.int32), 0, CHUNK, 0,
                             greedy, engine._tables[0])
    ).as_text(debug_info=True)
    for scope in ("moe_route", "moe_experts", "moe_shared"):
        assert scope in wave and scope in chunk, scope
    if path == "expanded":
        assert "mla_expand" in wave and "mla_expand" in chunk
        assert "paged_latent_attention" not in wave
    else:
        assert "mla_absorb" in wave and "paged_latent_attention" in wave
        assert "mla_expand" not in wave


def test_the_front_door_serves_it(model, engine):
    cfg = inference.Config().enable_llm_engine(
        num_slots=4, max_len=MAX_LEN, prefill_len=CHUNK, paged=True,
        block_size=BLOCK)
    pred = inference.create_llm_predictor(cfg, model=model)
    try:
        prompt = _prompt(8, 19)
        assert pred.generate(prompt, max_tokens=6) == \
            Scheduler(engine).generate(prompt, max_tokens=6)
        assert pred.health()["latent_cache"] is True
        assert pred.health()["prefix_sharing"] is True
    finally:
        pred.close(drain=False)
