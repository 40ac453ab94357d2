"""nlp.NemotronHForCausalLM — Mamba-2 state beside the paged cache,
dropless routed experts, attention without rotary — and what the paged
engine does for a model with slot state.

Everything runs at a tiny size in float32 on the CPU. The yardstick is
`benchmark/reference/nemotron_h.py`, the plain float32 forward written
from the equations (a sequential recurrence, a loop over all experts, a
full softmax), which shares no code with the program.

Tolerances, all relative to the largest reference logit (or value):
  * 2e-5 where both sides are float32 and differ only in the order of
    their sums (chunked scan against the recurrence, grouped experts
    against the loop): float32 rounds at 6e-8 and a logit sums a few
    thousand products over five layers;
  * the same for prefill-then-decode through the engine's cache: the
    paged path is the same arithmetic in another order.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from benchmark.reference import nemotron_h as ref
from paddle_tpu import inference
from paddle_tpu.nlp import (GPTConfig, GPTForPretraining, LlamaConfig,
                            LlamaForCausalLM, NemotronHConfig,
                            NemotronHForCausalLM)
from paddle_tpu.nlp import nemotron_h as nh
from paddle_tpu.serving import (PagedServingEngine, Scheduler,
                                SpeculativePagedEngine)
from paddle_tpu.serving.paged.engine import HandoffRefused

VOCAB, MAX_LEN, BLOCK, CHUNK = 96, 64, 8, 16
TOL = 2e-5
SIZES = dict(vocab_size=VOCAB, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, mamba_num_heads=8,
             mamba_head_dim=8, n_groups=2, ssm_state_size=16, chunk_size=8,
             n_routed_experts=8, num_experts_per_tok=3,
             moe_intermediate_size=64,
             moe_shared_expert_intermediate_size=96, initializer_range=0.2)


def _model(pattern="MEM*E", seed=11, **over):
    """A seeded tiny model whose vectors are off their neutral values,
    so that A, D, the dt bias, the conv bias and the router's correction
    all take part."""
    pt.seed(seed)
    model = NemotronHForCausalLM(NemotronHConfig(
        hybrid_override_pattern=pattern, **{**SIZES, **over}))
    rng = np.random.default_rng(seed)
    for name, p in model.named_parameters():
        if len(p.shape) == 1 and not name.endswith("norm_weight"):
            p.set_value((np.asarray(p._data)
                         + rng.normal(0, 0.3, p.shape)).astype(np.float32))
    return model.eval()


def _cfg(model):
    """The configuration file's keys, as the reference reads them."""
    c = model.cfg
    return {k: getattr(c, k) for k in (
        "hybrid_override_pattern", "num_attention_heads",
        "num_key_value_heads", "head_dim", "mamba_num_heads",
        "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel",
        "num_experts_per_tok", "routed_scaling_factor",
        "layer_norm_epsilon")}


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


# ------------------------------------------------------------- the model
@pytest.mark.parametrize("pattern,seq", [("MEM*E", 21), ("*ME", 16),
                                         ("M", 5), ("EE*", 9)])
def test_forward_equals_the_reference(pattern, seq):
    model = _model(pattern)
    ids = np.random.default_rng(0).integers(0, VOCAB, (2, seq))
    _close(model(ids)._data, _reference_logits(model, ids))


def _mixer(model):
    return model.layers[0].mixer


def _recurrence(mixer, x):
    """The one-step recurrence, position by position, from a zero state."""
    st = mixer.init_state(x.shape[0], x.dtype)
    ssm, conv, outs = st["ssm"], st["conv"], []
    active = jnp.ones((x.shape[0],), bool)
    for t in range(x.shape[1]):
        y, ssm, conv = mixer.step(x[:, t:t + 1], ssm, conv, active)
        outs.append(y)
    return jnp.concatenate(outs, axis=1), ssm, conv


@pytest.mark.parametrize("length", [8, 13, 16, 21])
def test_chunked_scan_equals_the_sequential_recurrence(model, length):
    """Chunk 8: lengths that are and are not multiples of it."""
    mixer = _mixer(model)
    x = jnp.asarray(np.random.default_rng(length).normal(
        0, 1, (2, length, 64)), jnp.float32)
    st = mixer.init_state(2, x.dtype)
    y, ssm, conv = mixer.scan(x, st["ssm"], st["conv"])
    y_ref, ssm_ref, conv_ref = _recurrence(mixer, x)
    _close(y, y_ref)
    _close(ssm, ssm_ref)
    _close(conv, conv_ref)


def test_one_decode_step_is_one_more_position_of_the_scan(model):
    mixer = _mixer(model)
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (1, 12, 64)),
                    jnp.float32)
    st = mixer.init_state(1, x.dtype)
    y_all, ssm_all, conv_all = mixer.scan(x, st["ssm"], st["conv"])
    _, ssm, conv = mixer.scan(x[:, :11], st["ssm"], st["conv"])
    y, ssm, conv = mixer.step(x[:, 11:], ssm, conv, jnp.ones((1,), bool))
    _close(y, y_all[:, 11:])
    _close(ssm, ssm_all)
    _close(conv, conv_all)


@pytest.mark.parametrize("valid", [1, 5, 8, 11])
def test_padded_positions_leave_the_carried_state_untouched(model, valid):
    """A 16-token chunk of which `valid` are real, from a carried state:
    state and conv taps come out as from the real tokens alone."""
    mixer = _mixer(model)
    rng = np.random.default_rng(valid)
    x = jnp.asarray(rng.normal(0, 1, (1, 16, 64)), jnp.float32)
    st = mixer.init_state(1, x.dtype)
    _, ssm0, conv0 = mixer.scan(x[:, ::-1][:, :7], st["ssm"], st["conv"])
    y, ssm, conv = mixer.scan(x, ssm0, conv0, valid_len=jnp.int32(valid))
    y_ref, ssm_ref, conv_ref = mixer.scan(x[:, :valid], ssm0, conv0)
    _close(y[:, :valid], y_ref)
    _close(ssm, ssm_ref)
    _close(conv, conv_ref)


def test_inactive_lanes_keep_their_state_in_a_decode_step(model):
    mixer = _mixer(model)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(0, 1, (3, 1, 64)), jnp.float32)
    ssm = jnp.asarray(rng.normal(0, 1, (3, 8, 8, 16)), jnp.float32)
    conv = jnp.asarray(rng.normal(0, 1, (3, 3, 128)), jnp.float32)
    _, ssm1, conv1 = mixer.step(x, ssm, conv, jnp.asarray([True, False,
                                                           True]))
    assert np.array_equal(ssm1[1], ssm[1]) and \
        np.array_equal(conv1[1], conv[1])
    assert not np.array_equal(ssm1[0], ssm[0])
    assert np.array_equal(conv1[2, :2], conv[2, 1:])


# ------------------------------------------------------------ the experts
def _moe(model):
    return next(b.mixer for b in model.layers if b.kind == "E")


@pytest.mark.parametrize("width,experts,tokens", [
    (64, 8, 18), (48, 4, 7), (64, 8, 700), (128, 16, 345)])
def test_grouped_experts_equal_the_expert_loop(width, experts, tokens):
    """Sorted picks through the grouped kernel (interpreted here) against
    the reference's loop over every expert; 700 and 345 tokens x 3 picks
    pass `MAX_ROWS` and go through it a segment at a time, the last one
    padded."""
    model = _model("E", moe_intermediate_size=width,
                   n_routed_experts=experts)
    moe = _moe(model)
    assert moe.experts_up.shape == moe.experts_down.shape == \
        [experts, width, 64]
    x = jnp.asarray(np.random.default_rng(3).normal(0, 1, (tokens, 64)),
                    jnp.float32)
    lw = {n: p._data for n, p in moe.named_parameters()}
    with jax.default_matmul_precision("highest"):
        want, _ = ref._experts(x[None], lw, 3,
                               model.cfg.routed_scaling_factor)
        want = want[0]
    _close(moe(x), want)


def test_grouped_kernel_keeps_each_experts_rows_apart():
    """Groups of 0, 1, 16, 17 and 30 rows: blocks of 16 rows that hold
    one expert, two, or the tail of one and the head of the next."""
    from paddle_tpu.ops.pallas.grouped_mlp import grouped_mlp
    rng = np.random.default_rng(5)
    sizes = np.asarray([0, 1, 16, 0, 17, 30, 3], np.int32)
    x = jnp.asarray(rng.normal(0, 1, (int(sizes.sum()), 32)), jnp.float32)
    up = jnp.asarray(rng.normal(0, 0.3, (7, 24, 32)), jnp.float32)
    down = jnp.asarray(rng.normal(0, 0.3, (7, 24, 32)), jnp.float32)
    got = grouped_mlp(x, up, down, jnp.asarray(sizes))
    owner = np.repeat(np.arange(7), sizes)
    want = np.stack([
        np.square(np.maximum(np.asarray(x[i]) @ np.asarray(up[e]).T, 0))
        @ np.asarray(down[e]) for i, e in enumerate(owner)])
    _close(got, want)


def test_choice_by_biased_scores_weights_from_unbiased_ones():
    logits = jnp.asarray([[2.0, 1.0, 0.5, -1.0], [0.0, 0.1, 0.2, 0.3]])
    s = np.asarray(jax.nn.sigmoid(logits))
    idx, w = nh.route(jnp.zeros(4), logits, 2, 2.5)
    assert sorted(np.asarray(idx)[0]) == [0, 1]
    # a correction that lifts expert 3 over expert 1 flips the choice...
    idx, w = nh.route(jnp.asarray([0.0, 0.0, 0.0, 0.6]), logits, 2, 2.5)
    idx, w = np.asarray(idx), np.asarray(w)
    assert sorted(idx[0]) == [0, 3]
    # ...and the weights are the chosen sigmoids, without it, over their
    # sum, times the scaling factor
    for t in range(2):
        np.testing.assert_allclose(
            w[t], 2.5 * s[t, idx[t]] / s[t, idx[t]].sum(), rtol=1e-6)


def test_no_token_is_dropped_when_all_choose_one_expert():
    """Top-1, a correction that sends every token to expert 5: a layer
    with a capacity would drop most of them; here each gets expert 5's
    whole answer."""
    model = _model("E", num_experts_per_tok=1)
    moe = _moe(model)
    bias = np.zeros(8, np.float32)
    bias[5] = 10.0
    moe.e_score_correction_bias.set_value(bias)
    x = jnp.asarray(np.random.default_rng(4).normal(0, 1, (40, 64)),
                    jnp.float32)
    idx, w = moe.route(x)
    assert np.all(np.asarray(idx) == 5)
    want = (jnp.square(jax.nn.relu(x @ moe.experts_up._data[5].T))
            @ moe.experts_down._data[5]
            * model.cfg.routed_scaling_factor)
    got = moe.experts(x, idx, w)
    _close(got, want)
    assert np.all(np.abs(np.asarray(got)).max(axis=1) > 0)


# -------------------------------------------------- the constructor's cost
def test_parameters_are_created_in_the_named_dtype_and_kept():
    """`param_dtype` with `init_weights=False`: zeros in that dtype (no
    float32 draw on the host), and `set_value` keeps the very array it
    is given, so a caller's weights are the only copy."""
    model = NemotronHForCausalLM(NemotronHConfig(
        hybrid_override_pattern="ME*", param_dtype="bfloat16",
        init_weights=False, **SIZES))
    for name, p in model.named_parameters():
        assert p._data.dtype == jnp.bfloat16, name
        if len(p.shape) > 1:
            assert not np.asarray(p._data, np.float32).any(), name
    w = jnp.ones((64, VOCAB), jnp.bfloat16)
    model.lm_head.set_value(w)
    assert model.lm_head._data is w


@pytest.mark.parametrize("kw,what", [
    (dict(hybrid_override_pattern="MXE"), "string of M, E"),
    (dict(hybrid_override_pattern="ME", num_hidden_layers=3), "length"),
    (dict(num_attention_heads=3), "not divisible"),
    (dict(num_experts_per_tok=9), "n_routed_experts"),
])
def test_config_refuses_sizes_that_cannot_be(kw, what):
    with pytest.raises(ValueError, match=what):
        NemotronHConfig(**{**SIZES, "hybrid_override_pattern": "ME*", **kw})


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


@pytest.mark.parametrize("n", [5, 16, 23, 37])
def test_prefill_in_chunks_then_decode_equals_the_reference(model, n):
    """Prompts of under one chunk, exactly one, and several with a
    ragged last one, then six decoded tokens, through the engine's own
    programs (uncompiled, so that the logits can be read): every logit
    row against the reference's full forward over prompt + tokens."""
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


def _solo(model, prompt, max_tokens):
    fresh = PagedServingEngine(model, num_slots=4, max_len=MAX_LEN,
                               block_size=BLOCK, prefill_chunk_len=CHUNK)
    return Scheduler(fresh).generate(prompt, max_tokens=max_tokens)


def test_a_reused_slot_answers_like_a_fresh_engine(model, engine):
    """The slot's last request leaves its record behind; the next one in
    that slot starts from zero."""
    sched = Scheduler(engine)
    first = sched.generate(_prompt(1, 30), max_tokens=6)
    again = sched.generate(_prompt(2, 9), max_tokens=6)
    assert first == _solo(model, _prompt(1, 30), 6)
    assert again == _solo(model, _prompt(2, 9), 6)
    assert engine.decode_compiles == engine.prefill_compiles == 1


def test_state_reset_zeroes_one_slot_only(model):
    eng = PagedServingEngine(model, num_slots=3, max_len=MAX_LEN,
                             block_size=BLOCK, prefill_chunk_len=CHUNK)
    eng._caches["state"] = jax.tree_util.tree_map(
        lambda a: jnp.ones_like(a), eng._caches["state"])
    eng.begin_prefill(1, _prompt(0, 4))
    for leaf in jax.tree_util.tree_leaves(eng._caches["state"]):
        leaf = np.asarray(leaf, np.float32)
        assert not leaf[1].any() and leaf[0].all() and leaf[2].all()


def test_two_requests_with_one_prefix_share_nothing(model, engine):
    """A prefix hit would hand the second request K/V pages and no
    state: sharing is off, and both answers are the solo answers."""
    sched = Scheduler(engine)
    head = _prompt(5, 24)
    jobs = [head + _prompt(6, 5), head + _prompt(7, 3)]
    reqs = [sched.submit(prompt=p, max_tokens=5) for p in jobs]
    sched.run()
    snap = sched.metrics.snapshot()
    assert snap["prefix_hits"] == snap["prefix_misses"] == 0
    assert engine.block_pool.prefix_hits == 0
    for p, r in zip(jobs, reqs):
        assert r.output_tokens == _solo(model, p, 5)
    assert engine.describe()["prefix_sharing"] is False
    assert engine.describe()["slot_state"] is True
    health = engine._health()
    assert health["prefix_sharing"] is False and health["slot_state"]
    # 2 Mamba layers x 4 slots x (8 x 8 x 16 float32 + 3 x 128 float32)
    assert health["state_bytes"] == 2 * 4 * (8 * 8 * 16 + 3 * 128) * 4


def test_recompute_preemption_reproduces_the_tokens(model):
    """A pool too small for four long requests: a starved lane is
    evicted and re-prefilled from token 0 (no prefix to re-hit), its
    state rebuilt by the scan; every answer equals a solo run."""
    small = PagedServingEngine(model, num_slots=4, max_len=MAX_LEN,
                               block_size=BLOCK, num_blocks=9,
                               prefill_chunk_len=CHUNK)
    jobs = [(_prompt(20 + i, 14), 12) for i in range(4)]
    sched = Scheduler(small)
    reqs = [sched.submit(prompt=p, max_tokens=m) for p, m in jobs]
    sched.run()
    assert all(r.finish_reason == "max_tokens" for r in reqs)
    assert sum(r.preemptions for r in reqs) >= 1
    for (p, m), r in zip(jobs, reqs):
        assert r.output_tokens == _solo(model, p, m)
    snap = sched.metrics.snapshot()
    assert snap["state_resets"] == len(reqs) + sum(r.preemptions
                                                   for r in reqs)


def test_speculation_and_handoff_are_refused_with_their_reason(model,
                                                               engine):
    with pytest.raises(ValueError, match="speculative decoding.*recurrent "
                                         "state a slot"):
        SpeculativePagedEngine(model, model, num_slots=2, max_len=MAX_LEN,
                               block_size=BLOCK)
    with pytest.raises(HandoffRefused, match="export_slot_kv.*K/V pages "
                                             "only"):
        engine.export_slot_kv(0)
    with pytest.raises(HandoffRefused, match="import_handoff.*snapshot"):
        engine.import_handoff(0, _prompt(0, 4), {})


def test_spans_phases_and_counters_appear(model, engine):
    from paddle_tpu.utils import profiler
    sched = Scheduler(engine)
    profiler.start_profiler()
    try:
        out = sched.generate(_prompt(9, 20), max_tokens=4)
    finally:
        events = profiler.stop_profiler()
    names = {e["name"] for e in events}
    assert "serving/state/reset" in names
    snap = sched.metrics.snapshot()
    assert snap["phase_seconds"]["state.reset"] > 0
    assert snap["state_resets"] == 1
    # 20 prompt tokens and 3 decoded ones, two expert layers, top-3
    assert snap["moe_picks"] == (20 + len(out) - 1) * 2 * 3
    assert model.moe_picks_per_token == 6


def test_device_work_carries_its_scope_names(model, engine):
    """`ssm_step`, `moe_route`, `moe_experts`, `moe_shared` name the
    wave's instructions and `ssm_scan` the chunk's, so that a device
    trace can be read by name."""
    key = jax.random.PRNGKey(0)
    wave = jax.jit(engine._decode_wave_fn).lower(
        *engine._wave_args([True] * 4, np.zeros(4, bool), key)
    ).as_text(debug_info=True)
    for scope in ("ssm_step", "moe_route", "moe_experts", "moe_shared"):
        assert scope in wave, scope
    assert "ssm_scan" not in wave
    greedy = engine._sampling_state(False, 1.0, 0, 1.0, None, False)
    chunk = jax.jit(engine._prefill_fn).lower(
        engine._params, engine._buffers, engine._caches,
        *engine._prompt_args(0, np.zeros(CHUNK, np.int32), 0, CHUNK, 0,
                             greedy, engine._tables[0])
    ).as_text(debug_info=True)
    assert "ssm_scan" in chunk and "moe_experts" in chunk


def test_a_wave_of_greedy_lanes_skips_the_sampling_filter(model, engine):
    """The filter (two sorts and two gathers of `[lanes, vocab]`) sits in
    one branch of a conditional on "some lane samples"; greedy and
    sampled lanes in one wave still each get their own token."""
    key = jax.random.PRNGKey(0)
    text = jax.jit(engine._decode_wave_fn).lower(
        *engine._wave_args([True] * 4, np.zeros(4, bool), key)).as_text()
    assert "stablehlo.case" in text or "stablehlo.if" in text
    from paddle_tpu.serving.engine import _select_wave_tokens
    lo = jnp.asarray(np.random.default_rng(0).normal(0, 1, (3, VOCAB)),
                     jnp.float32)
    args = (jnp.zeros(3, jnp.int32), jnp.zeros(3, jnp.int32),
            jnp.ones(3, bool))
    knobs = (jnp.ones(3), jnp.zeros(3, jnp.int32), jnp.ones(3),
             jnp.zeros((3, VOCAB)), jnp.zeros(3, bool), key)
    greedy, _, _ = _select_wave_tokens(lo, *args, jnp.zeros(3, bool),
                                       *knobs)
    assert np.array_equal(greedy, np.argmax(lo, axis=-1))
    mixed, _, _ = _select_wave_tokens(
        lo, *args, jnp.asarray([False, True, False]), *knobs)
    assert mixed[0] == greedy[0] and mixed[2] == greedy[2]
    want = jax.random.categorical(key, lo, axis=-1)
    assert mixed[1] == want[1]


def _program_arity(eng):
    """Arguments each program is called with while one request runs."""
    seen = {}
    for name in ("_prefill", "_decode_wave"):
        inner = getattr(eng, name)

        def count(*a, _inner=inner, _name=name):
            seen[_name] = len(a)
            return _inner(*a)
        setattr(eng, name, count)
    Scheduler(eng).generate(list(range(1, 8)), max_tokens=3)
    return seen["_prefill"], seen["_decode_wave"]


def test_programs_of_models_without_slot_state_take_no_new_argument(model):
    kw = dict(num_slots=2, max_len=MAX_LEN, block_size=BLOCK,
              prefill_chunk_len=CHUNK)
    pt.seed(3)
    llama = LlamaForCausalLM(LlamaConfig(
        vocab_size=VOCAB, hidden_size=64, num_layers=2, num_heads=4,
        num_kv_heads=2, max_seq_len=MAX_LEN))
    gpt = GPTForPretraining(GPTConfig(
        vocab_size=VOCAB, hidden_size=64, num_layers=2, num_heads=4,
        max_seq_len=MAX_LEN))
    for plain in (llama, gpt):
        eng = PagedServingEngine(plain, **kw)
        assert not eng.slot_state and not eng.counts_model_work
        assert "state_bytes" not in eng._health()
        # parameters, buffers, caches, the lanes' tokens and positions,
        # the packed small arguments, the bias, the key: the slot and the
        # active mask ride in the packed one for every model, and reach
        # only a model with slot state (these two's `prefill_chunk` /
        # `decode_step` have no such parameter and would refuse it)
        assert _program_arity(eng) == (8, 8)
    assert _program_arity(PagedServingEngine(model, **kw)) == (8, 8)


def test_the_front_door_serves_it(model, engine):
    cfg = inference.Config().enable_llm_engine(
        num_slots=4, max_len=MAX_LEN, prefill_len=CHUNK, paged=True,
        block_size=BLOCK)
    pred = inference.create_llm_predictor(cfg, model=model)
    try:
        prompt = _prompt(8, 19)
        assert pred.generate(prompt, max_tokens=6) == \
            Scheduler(engine).generate(prompt, max_tokens=6)
        assert pred.health()["slot_state"] is True
    finally:
        pred.close(drain=False)
