"""nlp.LagunaForCausalLM (`model_type: laguna`): full and sliding-window
attention layers in one stack, each kind with its own head count and
rotary table, a per-head output gate, a dense lead layer and softmax-routed
gated experts with a shared one; served through the paged engine with the
window layers on a pool of their own.

Everything runs at a tiny size in float32 on the CPU, with the published
period and the dense lead kept: hidden 64, 5 layers (full + dense, three
sliding, full), 6 and 8 query heads over 1 kv-head of 16, a window of 8,
16 experts of 16 top-4 and a shared one, vocabulary 512, YaRN on half of
a full layer's head from 32 positions by 8. The yardstick is
`benchmark/reference/laguna.py`, the plain float32 forward written from
the equations, which shares no code with the program.

Tolerance, relative to the largest reference logit: 3e-5 where both sides
are float32 and differ in the order of their sums (split rotary tables
against whole ones, cached against whole): float32 rounds at 6e-8, a
logit sums a few thousand products over five layers.
"""
import math

import numpy as np
import pytest

import jax

import paddle_tpu as pt
from benchmark.reference import laguna as ref
from paddle_tpu import inference
from paddle_tpu.nlp import LagunaConfig, LagunaForCausalLM
from paddle_tpu.nlp import laguna
from paddle_tpu.nlp.moe import route_softmax
from paddle_tpu.serving import PagedServingEngine, Scheduler

FULL, SLIDING = laguna.FULL, laguna.SLIDING
VOCAB, WINDOW, BLOCK, CHUNK, MAX_LEN = 512, 8, 4, 16, 160
TOL = 3e-5
ROPE = {FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 8,
               "original_max_position_embeddings": 32, "beta_fast": 64,
               "beta_slow": 1, "attention_factor": 0.1 * math.log(8) + 1,
               "partial_rotary_factor": 0.5},
        SLIDING: {"rope_type": "default", "rope_theta": 10000,
                  "partial_rotary_factor": 1}}
KEYS = dict(vocab_size=VOCAB, hidden_size=64, intermediate_size=96,
            num_hidden_layers=5, num_attention_heads=6,
            num_key_value_heads=1, head_dim=16,
            num_attention_heads_per_layer=[6, 8, 8, 8, 6],
            layer_types=[FULL, SLIDING, SLIDING, SLIDING, FULL],
            mlp_layer_types=["dense"] + ["sparse"] * 4,
            sliding_window=WINDOW, rope_parameters=ROPE, rms_norm_eps=1e-6,
            num_experts=16, num_experts_per_tok=4, moe_intermediate_size=16,
            shared_expert_intermediate_size=16,
            moe_routed_scaling_factor=2.5, gating=True,
            max_position_embeddings=256, initializer_range=0.2)


def _model(seed=3, **over):
    """A seeded tiny model whose vectors are off their neutral values, so
    that every norm's scale takes part."""
    pt.seed(seed)
    keys = {**KEYS, **over}
    model = LagunaForCausalLM(LagunaConfig(**keys))
    rng = np.random.default_rng(seed)
    for _, p in model.named_parameters():
        if len(p.shape) == 1:
            p.set_value((np.asarray(p._data)
                         + rng.normal(0, 0.2, p.shape)).astype(np.float32))
    model.file_keys = keys          # what a configuration file would hold
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
    ({}, 45),                                   # 5 windows, past 32
    ({"gating": False}, 21),
    ({"sliding_window": 64}, 40),               # the window never reached
    ({"num_hidden_layers": 2, "layer_types": [SLIDING, FULL],
      "mlp_layer_types": ["sparse", "dense"],
      "num_attention_heads_per_layer": [8, 6]}, 30)])
def test_forward_equals_the_reference(over, seq):
    model = _model(**over)
    ids = np.random.default_rng(0).integers(0, VOCAB, (2, seq))
    _close(model(ids)._data, _reference_logits(model, ids))
    names = {n.split(".", 2)[-1] for n, _ in model.named_parameters()
             if n.startswith("layers.1.")}
    assert ("self_attn.gate_proj" in names) == model.cfg.gating
    # a softmax router has no correction bias
    assert "mlp.e_score_correction_bias" not in names


def test_the_window_and_the_gate_are_load_bearing(model, monkeypatch):
    """The reference with every layer full, or with no gate, is far from
    the program: both mechanisms change the logits."""
    ids = np.random.default_rng(1).integers(0, VOCAB, (1, 40))
    got = np.asarray(model(ids)._data)
    want = _reference_logits(model, ids)
    _close(got, want)
    for wrong in ({"layer_types": [FULL] * 5}, {"gating": False}):
        model.file_keys = {**KEYS, **wrong}
        try:
            other = _reference_logits(model, ids)
        finally:
            model.file_keys = KEYS
        assert np.abs(got - other).max() > 1e3 * TOL * np.abs(want).max()


@pytest.mark.parametrize("key,value", [
    ("tie_word_embeddings", True), ("attention_bias", True),
    ("layer_types", [FULL, "chunked_attention", FULL, FULL, FULL]),
    ("moe_router_logit_softcapping", 30.0),
    ("rope_parameters", {**ROPE, FULL: {**ROPE[FULL],
                                        "rope_type": "dynamic"}}),
    ("num_attention_heads_per_layer", [6, 8, 8])])
def test_config_refuses_what_is_not_computed(key, value):
    with pytest.raises(ValueError):
        LagunaConfig(**{**KEYS, key: value})


def test_rotary_groups_at_the_published_keys():
    """The full layers' YaRN on 64 of 128 channels from the published
    group: pairs 0..low keep their frequency, pairs from high on are
    divided by 64, cos and sin times `attention_factor` 1.41589; the
    sliding layers' plain table over all 128 channels. The program's
    tables are the reference's own (`benchmark/reference/laguna.rotary`,
    written apart)."""
    published = {FULL: {"rope_theta": 500000, "rope_type": "yarn",
                        "factor": 64, "original_max_position_embeddings":
                        4096, "beta_slow": 1, "beta_fast": 64,
                        "attention_factor": 1.4158883083359672,
                        "partial_rotary_factor": 0.5},
                 SLIDING: {"rope_type": "default", "rope_theta": 10000,
                           "partial_rotary_factor": 1},
                 "original_max_position_embeddings": 4096}
    cfg = LagunaConfig(**{**KEYS, "head_dim": 128,
                          "rope_parameters": published,
                          "partial_rotary_factor": 0.5})
    keys = {"head_dim": 128, "rope_parameters": published,
            "partial_rotary_factor": 0.5}
    for kind in (FULL, SLIDING):
        dim, inv, mult = cfg.rotary[kind]
        r, want, att = ref.rotary(keys, kind)
        assert dim == r and mult == att
        np.testing.assert_allclose(inv, want, rtol=1e-15)
    dim, inv, mult = cfg.rotary[FULL]
    f = 500000.0 ** -(np.arange(0, 64, 2) / 64)
    assert dim == 64 and abs(mult - (0.1 * math.log(64) + 1)) < 1e-12
    kept = inv == f
    assert kept[:4].all() and not kept[-1]
    np.testing.assert_allclose(inv[-8:], f[-8:] / 64, rtol=1e-15)
    dim, inv, mult = cfg.rotary[SLIDING]
    assert (dim, mult) == (128, 1.0)
    np.testing.assert_allclose(inv, 10000.0 ** -(np.arange(0, 128, 2) / 128),
                               rtol=1e-15)


def test_the_softmax_router():
    """Softmax over all logits, the top k renormalised, times the scale:
    the chosen weights sum to the scale whatever the logits."""
    logits = np.random.default_rng(4).normal(0, 3, (6, 16)).astype(
        np.float32)
    idx, w = route_softmax(logits, 4, 2.5)
    p = np.exp(logits - logits.max(1, keepdims=True))
    p /= p.sum(1, keepdims=True)
    top = np.argsort(-p, axis=1)[:, :4]
    np.testing.assert_array_equal(np.sort(np.asarray(idx), 1),
                                  np.sort(top, 1))
    chosen = np.take_along_axis(p, np.asarray(idx), 1)
    np.testing.assert_allclose(np.asarray(w),
                               chosen / chosen.sum(1, keepdims=True) * 2.5,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(1), 2.5, rtol=1e-6)


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
def engine(model):
    """One compiled engine for the tests that need no fresh one."""
    return _engine(model)


@pytest.mark.parametrize("n,kernel", [(3 * WINDOW, "reference"),
                                      (61, "reference"),
                                      (15 * WINDOW, "reference"),
                                      (7 * WINDOW + 3, "pallas")])
def test_prefill_in_chunks_then_decode_equals_the_reference(model, n,
                                                            kernel):
    """Prompts of 3 to 15 windows in chunks of two windows, then seven
    tokens, through the engine's own programs (uncompiled, so that the
    logits can be read; the Pallas core in interpret mode once): every
    logit row against the reference's full forward over prompt + tokens,
    the window layers read through their own pool and tables."""
    eng = _engine(model, jit_compile=False, paged_kernel=kernel)
    assert eng.window == WINDOW
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
    # retired: both pools hold nothing
    assert eng.block_pool.used == 0 and eng.window_pool.used == 0


def test_sixteen_lanes_that_pass_window_pages_on_serve_the_reference(model):
    """24 requests over 16 slots, prompts of 3 to 15 windows and answers
    of 4 to 11 tokens, in one queue through the compiled programs (the
    host reads each wave one program late): the window pages one lane
    gives back are handed to others, and at every served token of every
    request the reference's best logit, given the tokens served before
    it, is within the tolerance of its logit of that token."""
    eng = PagedServingEngine(model, num_slots=16, max_len=MAX_LEN,
                             block_size=BLOCK, prefill_chunk_len=CHUNK)
    sched = Scheduler(eng)
    rng = np.random.default_rng(41)
    jobs = [(_prompt(200 + i, int(rng.integers(3 * WINDOW, 15 * WINDOW))),
             int(rng.integers(4, 12))) for i in range(24)]
    reqs = [sched.submit(prompt=p, max_tokens=m) for p, m in jobs]
    owners = {}                                 # window page -> its slots
    while sched.step():
        for s, blocks in enumerate(eng._windowed[0].blocks):
            for b in blocks:
                owners.setdefault(b, set()).add(s)
    assert all(r.finish_reason == "max_tokens" for r in reqs)
    assert sum(len(s) > 1 for s in owners.values()) > eng.window_pages
    for (prompt, m), r in zip(jobs, reqs):
        out = r.output_tokens
        assert len(out) == m
        want = _reference_logits(model, [prompt + out[:-1]])[0][
            len(prompt) - 1:]
        gaps = want.max(1) - want[np.arange(m), out]
        assert gaps.max() <= TOL * np.abs(want).max()
    assert eng.block_pool.used == 0 and eng.window_pool.used == 0


def test_the_counters_of_a_window_pool(model, engine):
    """`moe_picks` (4 expert layers x top-4 a token), the pages given back
    behind the window, and pages and lanes held summed a round; the
    visited pages are counted a kind of layer at a time, each times its
    layers."""
    layout = model.paged_layout()
    assert layout.moe_picks == 16
    assert [(g.layers, g.window) for g in layout.groups] == [
        (2, None), (3, WINDOW)]
    eng = engine
    sched = Scheduler(eng)
    prompt = _prompt(9, 50)
    out = sched.generate(prompt, max_tokens=5)
    snap = sched.metrics.snapshot()
    assert snap["moe_picks"] == (50 + len(out) - 1) * 16
    # four chunks of 16, 16, 16 and 2 tokens: each prompt token attends
    # its position + 1 keys in a full layer
    assert snap["prefill_chunks"] == 4 and snap["prefill_keys"] == 50 * 51 // 2
    assert snap["window_pages_released"] > 0
    assert snap["window_pages_held"] >= snap["window_lanes_held"] > 0
    assert snap["window_pages_held"] <= \
        snap["window_lanes_held"] * eng.window_pages
    assert snap["phase_seconds"]["window.release"] > 0
    # a wave's lanes: 2 full layers over all they hold, 3 window layers
    # over the pages their window reaches, of 2 x 5 tables of 40 entries
    assert snap["paged_pages_spanned"] == 4 * 2 * 5 * (MAX_LEN // BLOCK)
    assert snap["paged_steps_visited"] > 0


class _Routes:
    """Records the experts every sparse layer's router chooses in the
    decode waves of an uncompiled engine (the waves' calls route
    `num_slots` tokens, the chunks' `CHUNK`)."""

    def __init__(self, model, lanes):
        self.moes = [blk.mlp for blk in model.layers if not blk.dense]
        self.picks = []
        for moe in self.moes:
            inner = moe.route

            def spy(x, _inner=inner):
                idx, w = _inner(x)
                if x.shape[0] == lanes:
                    self.picks.append(np.asarray(idx))
                return idx, w
            moe.route = spy

    def restore(self):
        for moe in self.moes:
            del moe.route


def test_the_experts_a_wave_fetches_are_counted_on_the_device(model):
    """`moe_experts_fetched`: the experts the waves' kernel calls walked,
    summed over the sparse layers in the program and read with the wave's
    tokens, is the distinct experts each sparse layer's router chose for
    the wave's lanes; `moe_experts_stored` is waves x 16 experts x 4
    sparse layers. Uncompiled, where the router's choices can be read,
    and compiled, which counts the same."""
    jobs = [(_prompt(60 + i, 20 + 7 * i), 6) for i in range(5)]

    def serve(eng):
        sched = Scheduler(eng)
        for prompt, m in jobs:
            sched.submit(prompt=prompt, max_tokens=m)
        while sched.step():
            pass
        return sched.metrics.snapshot()

    eng = PagedServingEngine(model, num_slots=3, max_len=MAX_LEN,
                             block_size=BLOCK, prefill_chunk_len=CHUNK,
                             jit_compile=False)
    routes = _Routes(model, eng.num_slots)
    try:
        snap = serve(eng)
    finally:
        routes.restore()
    waves = len(routes.picks) // 4
    assert waves == snap["waves_dispatched"] > 5
    distinct = sum(len(np.unique(idx)) for idx in routes.picks)
    assert snap["moe_experts_fetched"] == distinct
    assert snap["moe_experts_stored"] == waves * 16 * 4
    # three lanes of top-4 over 16 experts leave some of them unchosen
    assert distinct < snap["moe_experts_stored"]
    compiled = serve(PagedServingEngine(model, num_slots=3, max_len=MAX_LEN,
                                        block_size=BLOCK,
                                        prefill_chunk_len=CHUNK))
    for key in ("waves_dispatched", "moe_experts_fetched",
                "moe_experts_stored"):
        assert compiled[key] == snap[key], key


def test_the_kernel_calls_a_chunk_makes_are_counted(model, vmem_holds,
                                                    expert_calls):
    """`moe_chunk_calls` is the expert kernel's calls the chunks' sparse
    layers make, by the rule `experts` cuts its picks by
    (`kernel_calls`): one a layer where the plan holds a chunk's 64
    picks, two under a budget whose plan holds 32 of them;
    `moe_chunk_layers` is chunks x 4 sparse layers."""
    moe = next(blk.mlp for blk in model.layers if not blk.dense)
    x = jax.numpy.asarray(np.random.default_rng(1).normal(0, 1, (CHUNK, 64)),
                          jax.numpy.float32)
    assert moe.kernel_calls(CHUNK) == expert_calls(moe, x) == 1
    vmem_holds(32, 64, 16, 3)
    assert moe.kernel_calls(CHUNK) == expert_calls(moe, x) == 2
    eng = PagedServingEngine(model, num_slots=3, max_len=MAX_LEN,
                             block_size=BLOCK, prefill_chunk_len=CHUNK)
    sched = Scheduler(eng)
    for i in range(3):
        sched.submit(prompt=_prompt(70 + i, 20 + 9 * i), max_tokens=3)
    while sched.step():
        pass
    snap = sched.metrics.snapshot()
    assert snap["prefill_chunks"] == 2 + 2 + 3
    assert snap["moe_chunk_layers"] == snap["prefill_chunks"] * 4
    assert snap["moe_chunk_calls"] == snap["prefill_chunks"] * 4 * 2


def test_the_front_door_serves_it_and_a_hard_close_gives_both_pools_back(
        model, engine):
    """`create_llm_predictor` -> the paged engine with a window pool and no
    option for it; `/healthz` reports both pools; `close(drain=False)`
    deletes every pool's arrays."""
    cfg = inference.Config().enable_llm_engine(
        num_slots=2, max_len=MAX_LEN, prefill_len=CHUNK, paged=True,
        block_size=BLOCK)
    pred = inference.create_llm_predictor(cfg, model=model)
    prompt = _prompt(8, 37)
    out = pred.generate(prompt, max_tokens=6)
    assert out == Scheduler(engine).generate(prompt, max_tokens=6)
    h = pred.health()
    assert h["window"] == WINDOW and h["prefix_sharing"] is False
    assert h["window_blocks_total"] == 2 * pred.engine.window_pages
    pools = jax.tree_util.tree_leaves(pred.engine._caches)
    assert [p.shape[0] for p in pools] == [
        pred.engine.block_pool.num_blocks] + [
        pred.engine.window_pool.num_blocks] * 3 + [
        pred.engine.block_pool.num_blocks]
    pred.close(drain=False)
    assert all(p.is_deleted() for p in pools)


def test_the_paged_layout(model):
    """Two groups of K/V layers, by head count and window: the window
    layers' on a pool of their own; 4 sparse layers of top-4 over 16
    experts, each a kernel call a chunk of 16. A stack of window layers
    alone keeps one pool, and two window sizes are refused one."""
    from paddle_tpu.nn.paged_layout import AttentionGroup
    layout = model.paged_layout()
    assert layout.groups == (
        AttentionGroup(2, "kv", 6, 1, 16),
        AttentionGroup(3, "kv", 8, 1, 16, window=WINDOW, pool="window"))
    assert layout.window == WINDOW and not layout.slot_record
    assert (layout.moe_picks, layout.mhc_rows_mixed,
            layout.experts_stored) == (16, 0, 4 * 16)
    assert layout.chunk_calls(CHUNK) == (4, 4)
    assert layout.counts_work and not layout.shares_prefixes
    assert "second pool" in layout.refusal("a hand-off")
    assert layout.weighted() == [(layout.groups[0], 2),
                                 (layout.groups[1], 3)]
    alone = _model(num_hidden_layers=2, layer_types=[SLIDING] * 2,
                   mlp_layer_types=["dense"] * 2,
                   num_attention_heads_per_layer=[8, 8]).paged_layout()
    assert alone.groups == (AttentionGroup(2, "kv", 8, 1, 16, window=WINDOW),)
    assert alone.window is None and alone.shares_prefixes
    assert alone.refusal("a hand-off") is None


def _lowered(engine):
    key = jax.random.PRNGKey(0)
    greedy = engine._sampling_state(False, 1.0, 0, 1.0, None, False)
    slots = engine.num_slots
    wave = jax.jit(engine._decode_wave_fn).lower(
        *engine._wave_args(np.ones(slots, bool), np.zeros(slots, bool), key))
    chunk = jax.jit(engine._prefill_fn).lower(
        engine._params, engine._buffers, engine._caches,
        *engine._prompt_args(0, np.zeros(CHUNK, np.int32), 0, CHUNK, 0,
                             greedy, engine._slot_table(0)))
    return wave, chunk


def test_device_work_carries_its_scope_names(model, engine):
    """`attn_gate`, `moe_route`, `moe_shared` and `moe_experts` name the
    instructions of a wave and a chunk; without `gating` there is no
    gate."""
    for program in _lowered(engine):
        text = program.as_text(debug_info=True)
        for scope in ("attn_gate", "moe_route", "moe_shared",
                      "moe_experts"):
            assert scope in text, scope
    for program in _lowered(_engine(_model(gating=False))):
        assert "attn_gate" not in program.as_text(debug_info=True)
