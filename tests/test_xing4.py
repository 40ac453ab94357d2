"""nlp.DeepseekV3ForCausalLM with the keys of `model_type: xing4_0`:
`hc_mult` residual streams mixed by Sinkhorn-projected maps
(manifold-constrained hyper-connections), a low-rank query
(`q_lora_rank`) and YaRN-scaled rotary positions (`rope_scaling`), on the
latent pool and the gated experts of the plain model
(`tests/test_deepseek_v3.py`).

Everything runs at a tiny size in float32 on the CPU: 4 streams, 3
layers of which 1 dense, rank 32 + rope 8, `q_lora_rank` 24, 8 experts
top-3, YaRN from an original length of 32 by a factor of 8, so that
sequences of 40 to 64 positions cross it. The yardstick is
`benchmark/reference/xing4.py`, the plain float32 forward written from
the equations, which shares no code with the program.

Tolerance, relative to the largest reference logit: 3e-5 where both
sides are float32 and differ in the order of their sums (the split
rotary tables against whole ones, `(x Phi) / rms` against `(x / rms)
Phi`, cached against whole): float32 rounds at 6e-8, a logit sums a few
thousand products over three layers and six maps.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from benchmark.reference import xing4 as ref
from paddle_tpu import inference
from paddle_tpu.nlp import DeepseekV3Config, DeepseekV3ForCausalLM
from paddle_tpu.nlp import deepseek_v3 as dsv3
from paddle_tpu.nlp.llama import rope_tables
from paddle_tpu.serving import PagedServingEngine, Scheduler

VOCAB, MAX_LEN, BLOCK, CHUNK = 96, 64, 8, 16
TOL = 3e-5
YARN = {"type": "yarn", "factor": 8, "original_max_position_embeddings": 32,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
PUBLISHED_YARN = {**YARN, "factor": 64,
                  "original_max_position_embeddings": 4096}
SIZES = dict(vocab_size=VOCAB, hidden_size=64, intermediate_size=96,
             moe_intermediate_size=32, num_hidden_layers=3,
             num_attention_heads=4, kv_lora_rank=32, q_lora_rank=24,
             qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
             n_routed_experts=8, n_shared_experts=1, num_experts_per_tok=3,
             routed_scaling_factor=2.0, first_k_dense_replace=1,
             rms_norm_eps=1e-6, rope_theta=10000.0, rope_scaling=YARN,
             max_position_embeddings=128, initializer_range=0.2,
             hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
             mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)
PLAIN = dict(hc_mult=1, q_lora_rank=None, rope_scaling=None)


def _model(seed=11, **over):
    """A seeded tiny model whose vectors are off their neutral values,
    so that every norm's scale, the router's correction and the maps'
    scalars and offsets take part."""
    pt.seed(seed)
    sizes = {**SIZES, **over}
    model = DeepseekV3ForCausalLM(DeepseekV3Config(**sizes))
    rng = np.random.default_rng(seed)
    for _, p in model.named_parameters():
        if len(p.shape) == 1:
            p.set_value((np.asarray(p._data)
                         + rng.normal(0, 0.2, p.shape)).astype(np.float32))
    model.file_keys = sizes         # what a configuration file would hold
    return model.eval()


def _reference_logits(model, ids):
    state = {n: p._data for n, p in model.named_parameters()}
    rw = ref.from_state_dict(state, model.cfg.num_layers)
    return np.asarray(ref.forward(rw, np.asarray(ids), model.file_keys))


def _close(got, want, tol=TOL):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * np.abs(want).max() + 1e-7


def _prompt(seed, n):
    return np.random.default_rng(seed).integers(0, VOCAB, n).tolist()


@pytest.fixture(scope="module")
def model():
    return _model()


# ------------------------------------------------------------- the model
@pytest.mark.parametrize("over,seq", [
    ({}, 50),                                       # across the length 32
    ({"num_hidden_layers": 2, "first_k_dense_replace": 0}, 21),
    ({"hc_mult": 2, "hc_sinkhorn_iters": 5}, 40),
    ({"hc_mult": 1}, 40),                           # one stream, no maps
    ({"q_lora_rank": None, "rope_scaling": None}, 33)])
def test_forward_equals_the_reference(over, seq):
    model = _model(**over)
    ids = np.random.default_rng(0).integers(0, VOCAB, (2, seq))
    _close(model(ids)._data, _reference_logits(model, ids))
    names = {n.split(".", 2)[-1] for n, _ in model.named_parameters()
             if n.startswith("layers.1.")}
    assert ("hc_mlp.phi" in names) == (model.cfg.hc_mult > 1)
    assert ("self_attn.q_a_proj" in names) == bool(model.cfg.q_lora_rank)
    assert ("self_attn.q_proj" in names) != bool(model.cfg.q_lora_rank)


def test_the_streams_differ_and_their_mean_in_their_place_fails(
        model, monkeypatch):
    """After one layer the four streams of a token are four different
    vectors; a stack that carried their mean in the place of each (one
    stream in four copies) does not give the reference's logits."""
    ids = np.random.default_rng(1).integers(0, VOCAB, (1, 12))
    x = jnp.broadcast_to(model.embeddings._data[ids], (4, 1, 12, 64))
    x, _ = model.layers[0].run(x, None, lambda attn, cache, a: (attn(a),
                                                                cache))
    x = np.asarray(x)
    assert x.shape == (4, 1, 12, 64)
    for i in range(3):
        assert np.abs(x[i] - x[i + 1]).max() > 0.05 * np.abs(x).max()
    want = _reference_logits(model, ids)
    _close(model(ids)._data, want)
    mixed = dsv3.HyperConnection.forward

    def averaged(self, x, f):
        out = mixed(self, x, f)
        return jnp.broadcast_to(out.mean(axis=0), out.shape)

    monkeypatch.setattr(dsv3.HyperConnection, "forward", averaged)
    got = np.asarray(model(ids)._data)
    assert np.abs(got - want).max() > 1e3 * TOL * np.abs(want).max()


def test_the_res_map_is_doubly_stochastic_and_the_clamp_holds(model):
    """20 Sinkhorn-Knopp iterations on logits of deviation 2.4 (what
    the benchmark's weights give): columns, normalised last, sum to 1
    within 1e-5 (`hc_eps` and float32); rows within 1e-3 in the median
    matrix and 5e-2 in the worst of 256 (20 iterations leave that of a
    matrix whose large entries nearly form a permutation: the published
    count, not a converged projection). Logits of +-100 are clamped to
    +-30 before `exp`: finite, and the map of +-30."""
    hc = model.layers[0].hc_attn
    r = jnp.asarray(np.random.default_rng(2).normal(0, 2.4, (4, 4, 256)),
                    jnp.float32)
    m = np.asarray(hc.sinkhorn(r))
    assert (m > 0).all()
    assert np.abs(m.sum(axis=0) - 1).max() < 1e-5       # columns
    rows = np.abs(m.sum(axis=1) - 1).max(axis=0)        # a matrix's worst
    assert np.median(rows) < 1e-3 and rows.max() < 5e-2
    wild = jnp.where(r > 0, 100.0, -100.0)
    got = np.asarray(hc.sinkhorn(wild))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(
        got, np.asarray(hc.sinkhorn(jnp.clip(wild, -30.0, 30.0))))
    assert not np.isfinite(np.asarray(jnp.exp(wild))).all()


def test_yarn_tables_at_factor_one_and_at_the_published_keys():
    """Factor 1 is the plain table; the published keys give `low` 10 and
    `high` 23 (pairs 0..10 keep their frequency, pairs 23..31 have it
    divided by 64, a ramp between), cos and sin untouched and the scores
    scaled by 192^-0.5 x (0.1 ln 64 + 1)^2 = 0.07217 x 2.0047."""
    f = 10000.0 ** -(np.arange(0, 64, 2) / 64)
    inv, on_tables, on_scores = dsv3.yarn_frequencies(
        64, 10000.0, {**PUBLISHED_YARN, "factor": 1})
    np.testing.assert_allclose(inv, f, rtol=1e-15)
    assert (on_tables, on_scores) == (1.0, 1.0)
    plain = rope_tables(600, 64, 10000.0)
    whole = dsv3._sequence_tables(tuple(inv), 600, 1.0)
    split = dsv3._split_rows(dsv3._split_tables(tuple(inv), 4096, 1.0),
                             jnp.arange(600))
    for got in (whole, split):
        for g, w in zip(got, plain):
            assert np.abs(np.asarray(g) - np.asarray(w)).max() < 3e-7

    inv, on_tables, on_scores = dsv3.yarn_frequencies(64, 10000.0,
                                                      PUBLISHED_YARN)
    np.testing.assert_allclose(inv[:11], f[:11], rtol=1e-15)
    np.testing.assert_allclose(inv[23:], f[23:] / 64, rtol=1e-15)
    assert ((inv[11:23] < f[11:23]) & (inv[11:23] > f[11:23] / 64)).all()
    assert on_tables == 1.0
    assert abs(on_scores - 2.0047) < 5e-5
    attn = DeepseekV3ForCausalLM(DeepseekV3Config(**{
        **SIZES, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rope_scaling": PUBLISHED_YARN, "num_hidden_layers": 1,
        "max_position_embeddings": 262144})).layers[0].self_attn
    assert abs(attn.scale - 0.07217 * 2.0047) < 2e-5
    # deep positions through the split tables: cos and sin of 262,143
    # turns of the fastest pair, to float32's last digits
    deep = jnp.asarray([4095, 4096, 20479, 262143, 300000])
    cos, sin = dsv3._split_rows(
        dsv3._split_tables(tuple(inv), 262144, 1.0), deep)
    ang = np.outer(np.minimum(np.asarray(deep), 262143), inv)
    assert np.abs(np.asarray(cos) - np.cos(ang)).max() < 3e-7
    assert np.abs(np.asarray(sin) - np.sin(ang)).max() < 3e-7


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


def _engine(model, **kw):
    return PagedServingEngine(model, num_slots=2, max_len=MAX_LEN,
                              block_size=BLOCK, prefill_chunk_len=CHUNK,
                              **kw)


@pytest.fixture(scope="module")
def engine():
    """One compiled engine for the tests that read counts, pools, scope
    names and tokens, none of which the Sinkhorn iterations change: on
    the file's model with 2 of them, where XLA:CPU compiles the 20
    unrolled ones in about 90 s a program. The 20 are compiled for the
    chip in `tests/test_tpu_compile.py` and held to the reference above."""
    return _engine(_model(hc_sinkhorn_iters=2))


@pytest.mark.parametrize("n", [5, 29, 37])
def test_prefill_in_chunks_then_decode_equals_the_reference(model, n):
    """Prompts that end before the YaRN original length (32), that reach
    it while decoding, and that cross it inside a chunk, then seven
    tokens, through the engine's own programs (uncompiled, so that the
    logits can be read): every logit row against the reference's full
    forward over prompt + tokens."""
    eng = _engine(model, jit_compile=False)
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
        _close(lo[0, 0], want[n + i])
    assert out == [int(np.argmax(want[n - 1 + i])) for i in range(7)]


def test_a_prefix_hit_gives_the_logits_of_the_reference(model):
    """The second request finds three pages of its prompt resident and
    starts its prefill behind them; its first-token logits and its
    decoded rows, past position 32, are the reference's."""
    head = _prompt(5, 3 * BLOCK + 2)
    jobs = [head + _prompt(6, 5), head + _prompt(7, 9)]
    eng = _engine(model, jit_compile=False, prefix_sharing=True)
    sched = Scheduler(eng)
    sched.generate(jobs[0], max_tokens=3)
    spy = _Logits(model)
    try:
        out = sched.generate(jobs[1], max_tokens=5)
    finally:
        spy.restore()
    assert eng.block_pool.prefix_hits == 3 and len(spy.chunks) == 2
    want = _reference_logits(model, [jobs[1] + out])[0]
    n = len(jobs[1])
    _close(spy.chunks[-1][0, 0], want[n - 1])
    for i, lo in enumerate(spy.waves):
        _close(lo[0, 0], want[n + i])


def test_the_counter_of_mixed_rows(model, engine):
    """`mhc_rows_mixed`: tokens staged into chunks and waves times the
    2 x layers sub-layers whose maps each passes; 0 with one stream (the
    count is the host's: the one-stream engine runs uncompiled)."""
    assert model.paged_layout().mhc_rows_mixed == 6
    sched = Scheduler(engine)
    snap0 = sched.metrics.snapshot()
    out = sched.generate(_prompt(9, 20), max_tokens=4)
    snap = sched.metrics.snapshot()
    assert snap0["mhc_rows_mixed"] == 0
    # 20 prompt tokens in two chunks, then three decoded tokens
    assert snap["mhc_rows_mixed"] == (20 + len(out) - 1) * 2 * 3
    assert snap["moe_picks"] == (20 + len(out) - 1) * 2 * 3
    plain = _model(**PLAIN)
    assert plain.paged_layout().mhc_rows_mixed == 0
    sched = Scheduler(_engine(plain, jit_compile=False))
    sched.generate(_prompt(9, 20), max_tokens=4)
    snap = sched.metrics.snapshot()
    assert snap["mhc_rows_mixed"] == 0 and snap["prefill_tokens"] == 20


def test_the_paged_layout(model):
    """One group of latent layers, 2 sparse layers of top-3 over 8
    experts, each a kernel call a chunk of 16, and 2 x 3 sub-layers whose
    stream maps a token passes; prefixes shared, hand-off refused for the
    form."""
    from paddle_tpu.nn.paged_layout import AttentionGroup
    layout = model.paged_layout()
    assert layout.groups == (AttentionGroup(3, "latent", 4,
                                            latent=(32, 8, 16, 16)),)
    assert layout.window is None and not layout.slot_record
    assert (layout.moe_picks, layout.mhc_rows_mixed,
            layout.experts_stored) == (6, 6, 2 * 8)
    assert layout.chunk_calls(CHUNK) == (2, 2)
    assert layout.counts_work and layout.shares_prefixes
    assert "latent rows" in layout.refusal("a hand-off")


def test_the_front_door_serves_it_and_a_hard_close_gives_the_pool_back(
        engine):
    """`create_llm_predictor` -> the paged engine, with no option for the
    streams; `close(drain=False)` deletes the pool's arrays (a caller
    that keeps the process gets the device memory back at once) and
    `health()` still answers."""
    cfg = inference.Config().enable_llm_engine(
        num_slots=2, max_len=MAX_LEN, prefill_len=CHUNK, paged=True,
        block_size=BLOCK)
    pred = inference.create_llm_predictor(cfg, model=engine.model)
    prompt = _prompt(8, 37)
    out = pred.generate(prompt, max_tokens=6)
    assert out == Scheduler(engine).generate(prompt, max_tokens=6)
    pools = jax.tree_util.tree_leaves(pred.engine._caches)
    assert len(pools) == 3 and not any(p.is_deleted() for p in pools)
    pred.close(drain=False)
    assert all(p.is_deleted() for p in pools)
    assert pred.health()["latent_cache"] is True
    pred.close(drain=False)                 # a second close finds nothing


def _lowered(engine):
    key = jax.random.PRNGKey(0)
    greedy = engine._sampling_state(False, 1.0, 0, 1.0, None, False)
    slots = engine.num_slots
    wave = jax.jit(engine._decode_wave_fn).lower(
        *engine._wave_args([True] * slots, np.zeros(slots, bool), key))
    chunk = jax.jit(engine._prefill_fn).lower(
        engine._params, engine._buffers, engine._caches,
        *engine._prompt_args(0, np.zeros(CHUNK, np.int32), 0, CHUNK, 0,
                             greedy, engine._tables[0]))
    return wave, chunk


def test_device_work_carries_its_scope_names(model, engine):
    """`mhc_map`, `mhc_mix` and `mla_q_lora` name the instructions of a
    wave and a chunk; a model with one stream and a full-rank query has
    none of them. The Sinkhorn iterations are unrolled elementwise work:
    no loop and no reduction, 32 divisions an iteration."""
    for program in _lowered(engine):
        text = program.as_text(debug_info=True)
        for scope in ("mhc_map", "mhc_mix", "mla_q_lora", "mla_absorb",
                      "moe_experts"):
            assert scope in text, scope
    hc = model.layers[0].hc_attn
    loop = jax.jit(hc.sinkhorn).lower(jnp.zeros((4, 4, 16))).as_text()
    assert "stablehlo.while" not in loop and "stablehlo.reduce" not in loop
    assert loop.count("stablehlo.divide") == 20 * 2 * 16
    for program in _lowered(_engine(_model(**PLAIN))):
        text = program.as_text(debug_info=True)
        assert "mhc_" not in text and "mla_q_lora" not in text
