"""Speculative decoding in the paged engine (SpeculativePagedEngine):
draft-k/verify-once waves with exact acceptance-rejection, plus the
scenario-diverse sampling tail the same PR widened.

The acceptance bar is the repo's token-exact-parity discipline:
speculative == non-speculative under greedy/fixed seed for single
requests, mixed-length multi-wave streams, chunked-prefill interleave,
preemption-by-recompute, and a fleet migration mid-speculation — while
the speculative configuration compiles EXACTLY three programs (draft
wave, verify wave, prefill chunk). Tier-1 shares the canonical tiny
LLaMA scale with tests/test_serving_paged.py so the persistent cache
shares compiles.

Two draft flavours are used on purpose:
  * `draft` — an independent tiny model. Random-init models collapse to
    attractor tokens, so acceptance is high: the fast path.
  * `bad_draft` — the same draft with one embedding row inflated so it
    always proposes a token the target rejects: acceptance ~0, which is
    what exercises rejection, residual resampling and the spec-block
    ROLLBACK deterministically.
"""
import json

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (PagedServingEngine, Scheduler,
                                SpeculativePagedEngine)
from paddle_tpu.utils import chaos, telemetry

VOCAB = 128
MAX_LEN = 64
BLOCK = 8
CHUNK = 16
SPEC_K = 3
MAX_NEW = 8


@pytest.fixture(scope="module")
def model():
    pt.seed(7)
    cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=64, num_layers=2,
                      num_heads=4, num_kv_heads=2, max_seq_len=MAX_LEN)
    return LlamaForCausalLM(cfg)


def _draft_model(seed=23):
    pt.seed(seed)
    cfg = LlamaConfig(vocab_size=VOCAB, hidden_size=32, num_layers=1,
                      num_heads=2, num_kv_heads=1, max_seq_len=MAX_LEN)
    return LlamaForCausalLM(cfg)


@pytest.fixture(scope="module")
def draft():
    return _draft_model()


@pytest.fixture(scope="module")
def bad_draft():
    """A draft that deterministically DISAGREES with the target: one
    vocab row's embedding is inflated so the draft's argmax pins to it
    while the target's does not — every proposal is rejected, every
    wave still emits the target's own correction token (parity must
    hold at acceptance ~0 too)."""
    m = _draft_model(seed=24)
    w = m.model.embed_tokens.weight.numpy().copy()
    w[VOCAB - 1] += 5.0            # tied embeddings: logits[V-1] balloon
    m.model.embed_tokens.weight.set_value(w)
    return m


def _spec_engine(model, draft, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("block_size", BLOCK)
    kw.setdefault("num_blocks", 33)
    kw.setdefault("prefill_chunk_len", CHUNK)
    return SpeculativePagedEngine(model, draft, spec_k=SPEC_K, **kw)


@pytest.fixture(scope="module")
def spec(model, draft):
    return _spec_engine(model, draft)


@pytest.fixture(scope="module")
def paged(model):
    return PagedServingEngine(model, num_slots=4, max_len=MAX_LEN,
                              block_size=BLOCK, num_blocks=33,
                              prefill_chunk_len=CHUNK)


def _prompt(seed, n=5):
    return np.random.RandomState(seed).randint(0, VOCAB, (n,)).tolist()


def _stream(engine, jobs, **kw):
    sched = Scheduler(engine)
    reqs = [sched.submit(prompt=p, max_tokens=m, **kw) for p, m in jobs]
    sched.run()
    return sched, reqs


# ---------------------------------------------------------------------------
# token-exact parity vs the non-speculative paged engine
# ---------------------------------------------------------------------------

def test_single_request_token_identical_and_three_programs(spec, paged):
    for seed in (0, 3):
        prompt = _prompt(seed)
        assert Scheduler(spec).generate(prompt, max_tokens=MAX_NEW) == \
            Scheduler(paged).generate(prompt, max_tokens=MAX_NEW)
    # the compile-once contract, now THREE programs — counted two ways:
    # executable caches and the live compile metric
    assert spec.draft_compiles == 1
    assert spec.decode_compiles == 1
    assert spec.prefill_compiles == 1
    for label in ("paged_spec_draft_wave", "paged_spec_verify",
                  "paged_spec_prefill_chunk"):
        assert telemetry.compile_count(label) >= 1, label


def test_mixed_length_multiwave_stream_token_identical(spec, paged):
    """12 requests on 4 slots, mixed prompt lengths/budgets + an EOS
    that lands mid-speculation-batch: every request equals the
    non-speculative engine token for token AND reason for reason."""
    rng = np.random.RandomState(1)
    jobs = [(rng.randint(0, VOCAB, (int(rng.randint(2, 14)),)).tolist(),
             int(rng.randint(2, 10))) for _ in range(12)]
    # learn one stream's second token and use it as EOS for that job:
    # the speculative batch must truncate at it exactly
    probe = Scheduler(paged).generate(jobs[0][0], max_tokens=4)
    eos = probe[1]
    _, pr = _stream(spec, jobs, eos_token_id=eos)
    _, dr = _stream(paged, jobs, eos_token_id=eos)
    assert [r.output_tokens for r in pr] == [r.output_tokens for r in dr]
    assert [r.finish_reason for r in pr] == [r.finish_reason for r in dr]
    assert spec.draft_compiles == 1
    assert spec.decode_compiles == 1
    assert spec.prefill_compiles == 1


def test_rejection_heavy_stream_token_identical(model, bad_draft, paged):
    """Acceptance ~0 (the disagreeing draft): every wave rejects the
    whole span and emits the target's correction — output still bitwise
    the target trajectory, one token per wave, no leaked blocks."""
    eng = _spec_engine(model, bad_draft)
    jobs = [(_prompt(40 + i, n=4 + i), 6) for i in range(4)]
    sched, reqs = _stream(eng, jobs)
    _, ref = _stream(paged, jobs)
    assert [r.output_tokens for r in reqs] == \
        [r.output_tokens for r in ref]
    snap = sched.metrics.snapshot()
    assert snap["spec_tokens_proposed"] > snap["spec_tokens_accepted"], \
        "the disagreeing draft produced no rejections"
    assert snap["spec_acceptance_rate"] < 1.0
    assert eng.block_pool.used == 0


def test_chunked_prefill_interleave_token_identical(spec, paged):
    """A 3-chunk prompt admits while short requests decode
    speculatively: folding between SPEC waves stays token-exact (and
    the dual-model chunk means the draft cache was populated by the
    same folded chunks)."""
    rng = np.random.RandomState(4)
    long_prompt = rng.randint(0, VOCAB, (2 * CHUNK + 5,)).tolist()
    jobs = [(_prompt(30 + i), 10) for i in range(3)] \
        + [(long_prompt, 5)]
    _, sr = _stream(spec, jobs)
    _, dr = _stream(paged, jobs)
    assert [r.output_tokens for r in sr] == [r.output_tokens for r in dr]


@pytest.mark.slow
def test_preemption_by_recompute_token_identical(model, draft):
    """A pool too small for four long requests: starved lanes preempt
    by recompute mid-speculation, everyone completes, and every output
    equals the non-speculative small-pool engine's."""
    small_spec = _spec_engine(model, draft, num_blocks=9)     # 8 usable
    small_paged = PagedServingEngine(model, num_slots=4, max_len=MAX_LEN,
                                     block_size=BLOCK, num_blocks=9,
                                     prefill_chunk_len=CHUNK)
    rng = np.random.RandomState(6)
    jobs = [(rng.randint(0, VOCAB, (14,)).tolist(), 12) for _ in range(4)]
    s_sched, s_reqs = _stream(small_spec, jobs)
    p_sched, p_reqs = _stream(small_paged, jobs)
    assert [r.output_tokens for r in s_reqs] == \
        [r.output_tokens for r in p_reqs]
    assert all(r.finish_reason == "max_tokens" for r in s_reqs)
    assert sum(r.preemptions for r in s_reqs) >= 1
    assert small_spec.block_pool.used == 0
    assert small_spec.draft_compiles == 1
    assert small_spec.decode_compiles == 1


def test_fleet_migration_mid_speculation_token_identical(model, draft,
                                                         paged):
    """THE fleet/robustness interleave: a replica serving SPECULATIVE
    engines is killed mid-stream — every accepted request finishes on
    the survivor with output bitwise-equal to the non-speculative
    no-fault run (greedy + identical weights + exact acceptance =
    engine-count- and fault-independent trajectory)."""
    from paddle_tpu.serving import fleet
    prompts = [_prompt(60 + i, n=4 + i % 3) for i in range(6)]
    ref = [Scheduler(paged).generate(p, max_tokens=6) for p in prompts]
    router = fleet.FleetRouter(lambda: _spec_engine(model, draft),
                               replicas=2)
    reqs = [router.submit(prompt=p, max_tokens=6) for p in prompts]
    monkey = chaos.ChaosMonkey([chaos.Fault(
        chaos.REPLICA_KILL, action="payload", payload=0, times=(2,))])
    with chaos.active(monkey):
        router.run()
    assert monkey.fired
    for i, r in enumerate(reqs):
        assert r.finish_reason == "max_tokens", (i, r.finish_reason,
                                                 r.error)
        assert r.output_tokens == ref[i], i
    assert router.metrics.snapshot()["migrations"] >= 1
    for rep in router.replicas:
        assert rep.engine.decode_compiles <= 1
        assert rep.engine.draft_compiles <= 1
    router.shutdown()


# ---------------------------------------------------------------------------
# speculation economics + rollback
# ---------------------------------------------------------------------------

def test_acceptance_metrics_and_multi_token_waves(spec):
    """The headline: with an agreeing draft, waves net MORE than one
    token per lane — mean accepted/wave > 0 and the spec counters move
    in lockstep with the snapshot."""
    before = telemetry.value("serving_spec_tokens_accepted_total",
                             default=0)
    sched, reqs = _stream(spec, [(_prompt(70 + i), MAX_NEW)
                                 for i in range(2)])
    snap = sched.metrics.snapshot()
    assert snap["spec_tokens_proposed"] > 0
    assert snap["spec_tokens_accepted"] > 0
    assert 0 < snap["spec_acceptance_rate"] <= 1
    assert snap["spec_accepted_per_wave"] > 0
    after = telemetry.value("serving_spec_tokens_accepted_total",
                            default=0)
    assert after - before == snap["spec_tokens_accepted"]
    # multi-token waves: fewer decode waves than decoded tokens
    decode_tokens = sum(len(r.output_tokens) - 1 for r in reqs)
    waves = snap["spec_tokens_proposed"] // SPEC_K  # proposed k per wave
    assert waves < decode_tokens


def test_rejected_speculation_blocks_rolled_back(model, bad_draft):
    """Refcount audit: with every proposal rejected, the wave
    repeatedly allocates ahead and must give the uncommitted blocks
    back — after every round each active lane holds at most the blocks
    covering its committed positions plus the next write."""
    eng = _spec_engine(model, bad_draft, num_slots=2)
    sched = Scheduler(eng)
    reqs = [sched.submit(prompt=_prompt(80 + i, n=6), max_tokens=12)
            for i in range(2)]
    while sched.step():
        for s in range(eng.num_slots):
            if eng.slot_active[s]:
                assert len(eng._slot_blocks[s]) <= \
                    eng.slot_pos[s] // BLOCK + 1, \
                    "uncommitted speculative blocks were not rolled back"
    assert all(r.finish_reason == "max_tokens" for r in reqs)
    assert eng.block_pool.used == 0


def test_poisoned_lane_retired_with_speculation_rolled_back(model, draft,
                                                            paged):
    """Chaos: a DECODE_WAVE_NAN fault during a speculative wave retires
    ONLY the poisoned lane (finish 'error', zero tokens from the bad
    wave), healthy lanes stay token-identical to the fault-free run,
    and no draft/spec block leaks (pool drains to 0)."""
    eng = _spec_engine(model, draft)
    prompts = [_prompt(90 + i) for i in range(3)]
    ref = [Scheduler(paged).generate(p, max_tokens=MAX_NEW)
           for p in prompts]
    monkey = chaos.ChaosMonkey([chaos.Fault(
        chaos.DECODE_WAVE_NAN, action="payload", payload=1, times=(1,))])
    with chaos.active(monkey):
        sched, reqs = _stream(eng, [(p, MAX_NEW) for p in prompts])
    assert monkey.fired
    assert reqs[1].finish_reason == "error"
    for i in (0, 2):
        assert reqs[i].output_tokens == ref[i], i
    assert sched.metrics.snapshot()["faults"].get("nonfinite", 0) >= 1
    assert eng.block_pool.used == 0
    assert eng.decode_compiles == 1        # poison is a program INPUT


@pytest.mark.slow
def test_horizon_bounded_request_token_identical(model, draft):
    """A request running into the cache horizon: the speculative batch
    whose LAST token lands at max_len must stream every token before
    retiring 'length' — retiring on the batch's first token (slot_pos
    is already advanced for the whole batch) would drop tokens the
    plain engine delivers."""
    spec32 = _spec_engine(model, draft, max_len=32)
    paged32 = PagedServingEngine(model, num_slots=4, max_len=32,
                                 block_size=BLOCK, num_blocks=33,
                                 prefill_chunk_len=CHUNK)
    for seed in (110, 111):
        prompt = _prompt(seed, n=5)
        s_sched = Scheduler(spec32)
        s_req = s_sched.submit(prompt=prompt, max_tokens=1000)
        s_sched.run()
        p_sched = Scheduler(paged32)
        p_req = p_sched.submit(prompt=prompt, max_tokens=1000)
        p_sched.run()
        assert s_req.finish_reason == p_req.finish_reason == "length"
        assert s_req.output_tokens == p_req.output_tokens


@pytest.mark.slow
def test_truncated_lane_resamples_from_target_distribution():
    """Exactness at spec_len < k (token-mask/horizon-clamped lanes):
    the emitted token must come from p_t itself, NOT the residual
    max(p_t - p_d, 0) against a draft distribution the lane never
    offered. With p_d concentrated on one token that p_t gives 0.6
    mass, the buggy residual can never emit it; the correct tail emits
    it ~60% of the time."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.serving.paged.engine import _spec_verify_tail

    s, k, v = 64, 2, 8
    c = k + 1
    lo = jnp.full((s, c, v), -30.0)
    lo = lo.at[:, :, 0].set(0.0)           # p_t(0) ~ 0.6
    lo = lo.at[:, :, 1].set(-0.405)        # p_t(1) ~ 0.4
    draft_probs = jnp.zeros((s, k, v)).at[:, :, 0].set(1.0)
    out, n_emit, nxt, new_pos, finite = _spec_verify_tail(
        lo, jnp.zeros((s,), jnp.int32), jnp.zeros((s,), jnp.int32),
        jnp.ones((s,), bool), jnp.ones((s,), bool),       # sampled
        jnp.ones((s,), jnp.float32), jnp.zeros((s,), jnp.int32),
        jnp.ones((s,), jnp.float32), jnp.zeros((s, v), jnp.float32),
        jnp.zeros((s,), jnp.int32),        # spec_len = 0: no proposals
        jnp.zeros((s, k), jnp.int32), draft_probs,
        jnp.zeros((s,), bool), jax.random.PRNGKey(0))
    assert bool((n_emit == 1).all())
    frac0 = float((nxt == 0).mean())
    assert 0.4 < frac0 < 0.8, \
        f"token 0 emitted {frac0:.2f} of lanes — a truncated lane's " \
        "resample is not drawing from the target distribution"


def test_filter_matches_reference_sequential_semantics():
    """_filter_top_k_top_p == nn.decode.top_k_top_p_filtering applied
    with the same knobs (top-k threshold with ties, then nucleus over
    the RENORMALIZED survivors) — per-row traced knobs vs the reference
    static path."""
    import jax.numpy as jnp
    from paddle_tpu.nn.decode import top_k_top_p_filtering
    from paddle_tpu.serving.engine import _filter_top_k_top_p

    rng = np.random.RandomState(0)
    lo = jnp.asarray(rng.randn(3, 16).astype("f4") * 2)
    for k, p in ((0, 1.0), (4, 1.0), (0, 0.5), (4, 0.5), (2, 0.3)):
        want = top_k_top_p_filtering(lo, top_k=k, top_p=p)._data
        got = _filter_top_k_top_p(
            lo, jnp.full((3,), k, jnp.int32), jnp.full((3,), p,
                                                       jnp.float32))
        np.testing.assert_array_equal(
            np.asarray(got) <= -1e9 + 1, np.asarray(want) <= -1e9 + 1,
            err_msg=f"keep-mask mismatch at top_k={k}, top_p={p}")


def test_verify_cost_within_k_plus_1_bounds():
    """The perf gate's invariant on the BANKED numbers: the verify
    program streams the pools/params once, so its bytes-accessed must
    stay well under k+1 times the single-token paged wave's (if verify
    ever re-streamed the cache per scored position, this trips long
    before the hlo_audit tolerance would)."""
    import os
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "hlo_baseline.json")
    doc = json.load(open(path))
    progs = doc["programs"]
    verify = progs["paged_spec_verify"]["metrics"]["bytes_accessed"]
    wave = progs["paged_decode_wave"]["metrics"]["bytes_accessed"]
    from paddle_tpu.tools.xprof.registry import SPEC
    assert verify <= (SPEC["spec_k"] + 1) * wave


# ---------------------------------------------------------------------------
# the scenario-diverse sampling tail (shared: paged AND speculative)
# ---------------------------------------------------------------------------

def test_top_k_1_sampling_equals_greedy(paged, spec):
    """top_k=1 collapses sampling to the argmax: a deterministic probe
    that the per-slot truncation really reaches the compiled tail —
    and that the speculative engine applies it identically."""
    prompt = _prompt(100)
    want = Scheduler(paged).generate(prompt, max_tokens=6)
    got_p = Scheduler(paged).generate(prompt, max_tokens=6,
                                      do_sample=True, temperature=1.7,
                                      top_k=1)
    got_s = Scheduler(spec).generate(prompt, max_tokens=6,
                                     do_sample=True, temperature=1.7,
                                     top_k=1)
    assert got_p == want
    assert got_s == want


def test_top_p_nucleus_tiny_equals_greedy(paged):
    """top_p below the best token's probability keeps only the best —
    the nucleus path's deterministic probe."""
    prompt = _prompt(101)
    want = Scheduler(paged).generate(prompt, max_tokens=6)
    got = Scheduler(paged).generate(prompt, max_tokens=6,
                                    do_sample=True, temperature=2.0,
                                    top_p=1e-6)
    assert got == want


def test_stop_sequences_finish_stop(paged, spec):
    """The request retires with finish_reason 'stop' the moment its
    output ends with a stop sequence — identically on the paged and
    speculative engines (the spec batch truncates mid-wave)."""
    prompt = _prompt(102)
    free = Scheduler(paged).generate(prompt, max_tokens=MAX_NEW)
    stop = free[1:3]                       # tokens 2..3 of the stream
    # the EARLIEST prefix of the free stream ending with the stop
    # sequence is the contract (degenerate tiny-model streams repeat,
    # so the match can land before position 3)
    want = next(free[:i] for i in range(len(stop), len(free) + 1)
                if free[:i][-len(stop):] == stop)
    for engine in (paged, spec):
        sched = Scheduler(engine)
        req = sched.submit(prompt=prompt, max_tokens=MAX_NEW,
                           stop_sequences=[stop])
        sched.run()
        assert req.finish_reason == "stop"
        assert req.output_tokens == want


def test_logit_bias_forbids_token_and_spec_parity(paged, spec):
    """Forbidding the greedy token via logit_bias changes the stream —
    and the speculative engine under the SAME bias matches the paged
    engine token for token (bias is part of the verified target
    distribution)."""
    prompt = _prompt(103)
    free = Scheduler(paged).generate(prompt, max_tokens=6)
    banned = free[0]
    bias = {banned: -1e9}
    got_p = Scheduler(paged).generate(prompt, max_tokens=6,
                                      logit_bias=bias)
    got_s = Scheduler(spec).generate(prompt, max_tokens=6,
                                     logit_bias=bias)
    assert banned not in got_p
    assert got_s == got_p != free


def test_token_mask_constrained_decoding(paged, spec):
    """A dynamic token_mask (re-evaluated per wave) constrains every
    emitted token to the allowed set — constrained/JSON-style decoding
    through the one shared tail. On the speculative engine the masked
    lane degenerates to one-token waves and stays token-identical."""
    allowed = [3, 5, 9]

    def mask(req):
        m = np.zeros((VOCAB,), bool)
        # alternate the legal set by position — a mask that CHANGES
        # with the emitted stream, which is what forbids drafting ahead
        m[allowed[len(req.output_tokens) % len(allowed)]] = True
        return m

    outs = []
    for engine in (paged, spec):
        sched = Scheduler(engine)
        req = sched.submit(prompt=_prompt(104), max_tokens=6,
                           token_mask=mask)
        sched.run()
        assert req.finish_reason == "max_tokens"
        for i, t in enumerate(req.output_tokens):
            assert t == allowed[i % len(allowed)]
        outs.append(req.output_tokens)
    assert outs[0] == outs[1]


def test_stop_sequence_spans_migration_seam(paged):
    """A stop sequence whose first half was streamed by a dead hop must
    still fire on the continuation: the fleet passes the prior stream's
    tail as stop_context, and _hit_stop matches across the seam."""
    from paddle_tpu.serving import FleetRequest, Request
    prompt = _prompt(108)
    free = Scheduler(paged).generate(prompt, max_tokens=MAX_NEW)
    stop = free[1:3]
    want = next(free[:i] for i in range(len(stop), len(free) + 1)
                if free[:i][-len(stop):] == stop)
    # the seam: the first half of the stream already migrated into the
    # prompt; the continuation request carries it as stop_context
    cut = len(want) - 1                    # stop straddles the cut
    sched = Scheduler(paged)
    req = Request(prompt=prompt + free[:cut], max_tokens=MAX_NEW,
                  stop_sequences=[stop], stop_context=free[:cut])
    sched.submit(request=req)
    sched.run()
    assert req.finish_reason == "stop"
    assert free[:cut] + req.output_tokens == want
    # and the router-side plumbing produces exactly that context
    fr = FleetRequest(prompt=prompt, max_tokens=MAX_NEW,
                      stop_sequences=[stop])
    fr._prior = free[:cut]
    kw = fr._submit_kwargs()
    assert kw["stop_context"] == free[:cut][-(len(stop) - 1):]
    assert kw["stop_sequences"] == [stop]


def test_bias_matrix_uploaded_once_for_bias_free_streams(paged):
    """The [S, V] bias upload must not ride every wave: bias-free
    requests reuse ONE device-resident array across waves; setting a
    bias row invalidates it, retiring the slot restores the zero
    matrix."""
    sched = Scheduler(paged)
    reqs = [sched.submit(prompt=_prompt(109 + i), max_tokens=4)
            for i in range(2)]
    sched.step()
    def bias_matrix():
        """The device array a wave staged now would be handed."""
        live = paged.slot_active.copy()
        return paged._lane_args(live, np.zeros_like(live))[-1]

    dev1 = bias_matrix()
    sched.step()
    dev2 = bias_matrix()
    assert dev1 is dev2, "bias-free waves re-uploaded the bias matrix"
    paged.set_slot_bias(reqs[0].slot, {3: -1e9})
    dev3 = bias_matrix()
    assert dev3 is not dev2
    assert float(dev3[reqs[0].slot, 3]) == -1e9
    sched.run()
    assert float(np.asarray(bias_matrix()).sum()) == 0.0


def test_raising_token_mask_fails_only_its_request(paged):
    """A token_mask callable that raises is contained to ITS request
    (finish 'error', token_mask_error fault), neighbours unaffected."""
    good_prompt = _prompt(105)
    want = Scheduler(paged).generate(good_prompt, max_tokens=6)

    def boom(req):
        if len(req.output_tokens) >= 2:
            raise RuntimeError("client mask bug")
        m = np.ones((VOCAB,), bool)
        return m

    sched = Scheduler(paged)
    bad = sched.submit(prompt=_prompt(106), max_tokens=8,
                       token_mask=boom)
    good = sched.submit(prompt=good_prompt, max_tokens=6)
    sched.run()
    assert bad.finish_reason == "error"
    assert good.output_tokens == want
    assert sched.metrics.snapshot()["faults"].get("token_mask_error",
                                                  0) == 1


@pytest.mark.slow
def test_spec_front_door_via_inference_config(model, draft):
    """inference.Config.enable_llm_engine(speculative=...) builds the
    speculative engine through create_llm_predictor."""
    from paddle_tpu import inference
    cfg = inference.Config()
    cfg.enable_llm_engine(paged=True, num_slots=2, max_len=48,
                          prefill_len=16, block_size=8,
                          speculative=True, k=2)
    pred = inference.create_llm_predictor(cfg, model=model,
                                          draft_model=draft)
    assert isinstance(pred.engine, SpeculativePagedEngine)
    assert pred.engine.spec_k == 2
    prompt = _prompt(107)
    ref = PagedServingEngine(model, num_slots=2, max_len=48,
                             block_size=8, prefill_chunk_len=16)
    assert pred.generate(prompt, max_tokens=4) == \
        Scheduler(ref).generate(prompt, max_tokens=4)
    with pytest.raises(ValueError, match="draft"):
        c2 = inference.Config().enable_llm_engine(paged=True,
                                                  speculative=True)
        inference.create_llm_predictor(c2, model=model)
