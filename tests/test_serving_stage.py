"""A stage puts nothing on the device's queue but the program itself
(serving/engine.py's module comment): a program's small arguments are
one numpy array packed afresh, per-slot state lives in numpy arrays
written in place, the PRNG key is split INSIDE the program and comes
back beside its outputs, and a bias that is all zeros is never uploaded.

Every test runs over the four engines that share the staging code: the
dense, paged and speculative engines of a small GPT and the paged engine
of a small Nemotron-H (slot state beside the pages, experts).
"""
import logging
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as pt
from paddle_tpu.nlp import (GPTConfig, GPTForPretraining, NemotronHConfig,
                            NemotronHForCausalLM)
from paddle_tpu.serving import (PagedServingEngine, Scheduler, ServingEngine,
                                SpeculativePagedEngine)
from paddle_tpu.serving import engine as dense_mod
from paddle_tpu.serving.paged import engine as paged_mod
from paddle_tpu.utils import chaos

VOCAB, MAX_LEN, BLOCK, CHUNK, SLOTS, SPEC_K = 96, 64, 8, 16, 4, 2
KINDS = ("dense", "paged", "spec", "hybrid")
PROMPT = list(range(3, 23))            # 20 tokens: two chunks when paged
SAMPLED = dict(do_sample=True, temperature=0.8, top_k=10, top_p=0.9)
#: the jitted functions an engine may compile, by its own names
OWN_PROGRAMS = {"decode_wave", "prefill", "prefill_chunk", "state_reset",
                "draft_wave", "spec_verify"}


def _gpt(hidden, layers, seed):
    pt.seed(seed)
    return GPTForPretraining(GPTConfig(
        vocab_size=VOCAB, hidden_size=hidden, num_layers=layers,
        num_heads=2, max_seq_len=MAX_LEN, dropout=0.0, attn_dropout=0.0))


@pytest.fixture(scope="module")
def models():
    pt.seed(5)
    hybrid = NemotronHForCausalLM(NemotronHConfig(
        hybrid_override_pattern="MEM*E", vocab_size=VOCAB, hidden_size=64,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
        chunk_size=8, n_routed_experts=8, num_experts_per_tok=3,
        moe_intermediate_size=64, moe_shared_expert_intermediate_size=96,
        initializer_range=0.2)).eval()
    return {"gpt": _gpt(32, 2, 3), "draft": _gpt(16, 1, 4),
            "hybrid": hybrid}


def _engine(models, kind, seed=5, jit_compile=True):
    kw = dict(num_slots=SLOTS, max_len=MAX_LEN, seed=seed,
              jit_compile=jit_compile)
    if kind == "dense":
        return ServingEngine(models["gpt"], prefill_len=32, **kw)
    kw.update(block_size=BLOCK, prefill_chunk_len=CHUNK)
    if kind == "spec":
        return SpeculativePagedEngine(models["gpt"], models["draft"],
                                      spec_k=SPEC_K, **kw)
    return PagedServingEngine(
        models["hybrid" if kind == "hybrid" else "gpt"], **kw)


def _admit(eng, slot, prompt=PROMPT, **sampling):
    """Admission chunk by chunk; (first token, programs dispatched)."""
    eng.begin_prefill(slot, prompt, **sampling)
    steps = 1
    while not eng.prefill_step(slot):
        steps += 1
    return eng.collect_first_tokens()[slot], steps


def _key(eng):
    return np.asarray(eng._key).copy()


def _chain(seed, n):
    """(key after n dispatches, the n subkeys): what n times
    `key, sub = jax.random.split(key)` on the host gives."""
    key, subs = jax.random.PRNGKey(seed), []
    for _ in range(n):
        key, sub = jax.random.split(key)
        subs.append(np.asarray(sub))
    return np.asarray(key), subs


def test_packed_arguments_come_back_bit_for_bit():
    """`pack_lanes` / `pack_prompt` and their readers inside a program:
    tables and integers as they were, flags as bool, the float32 knobs
    by their bits (0.1 and 1/3 have no short binary form)."""
    rng = np.random.default_rng(0)
    tables = rng.integers(0, 99, (SLOTS, 5)).astype(np.int32)
    lanes = dict(
        active=np.array([True, False, True, True]),
        sample=np.array([False, True, True, False]),
        top_k=np.array([0, 3, 50, 1], np.int32),
        poison=np.array([False, False, True, False]),
        spec_len=np.int32(2),
        temp=np.array([0.1, 1 / 3, 1.0, 2.5], np.float32),
        top_p=np.array([1.0, 0.9, 1e-3, 0.5], np.float32))
    block = dense_mod.pack_lanes(tables, **lanes)
    assert block.dtype == np.int32
    assert block.shape == (SLOTS, 5 + len(dense_mod.LANE_FIELDS))
    got_tables, got = jax.jit(dense_mod.unpack_lanes)(block)
    assert np.array_equal(got_tables, tables)
    assert set(got) == set(dense_mod.LANE_FIELDS)
    for name, want in lanes.items():
        want = np.broadcast_to(want, (SLOTS,))
        assert got[name].dtype == want.dtype, name
        assert np.array_equal(np.asarray(got[name]), want), name
    # no table columns on a dense engine
    no_tables, _ = dense_mod.unpack_lanes(dense_mod.pack_lanes(**lanes))
    assert no_tables.shape == (SLOTS, 0)

    tokens = rng.integers(0, VOCAB, CHUNK).astype(np.int32)
    scalars = dict(start=32, valid=11, frontier=10, slot=3, last=True,
                   sample=True, top_k=7, temp=0.1, top_p=1 / 3)
    vec = dense_mod.pack_prompt(tokens, tables[1], **scalars)
    assert vec.dtype == np.int32 and vec.shape == (
        5 + CHUNK + len(dense_mod.PROMPT_FIELDS),)
    row, toks, got = jax.jit(dense_mod.unpack_prompt, static_argnums=1)(
        vec, CHUNK)
    assert np.array_equal(row, tables[1]) and np.array_equal(toks, tokens)
    for name, want in scalars.items():
        want = np.float32(want) if name in ("temp", "top_p") else want
        assert np.asarray(got[name]) == want, name
    assert got["sample"].dtype == got["last"].dtype == bool
    assert got["temp"].dtype == np.float32


@pytest.fixture
def compiled_names(caplog):
    """Names of the functions XLA compiles while the test runs, as
    `jax.log_compiles` reports them: `names()` returns those since the
    last call."""
    pattern = re.compile(r"Finished XLA compilation of jit\((.*?)\) in")

    def names():
        found = [m.group(1) for r in caplog.records
                 for m in [pattern.search(r.getMessage())] if m]
        caplog.clear()
        return found

    with jax.log_compiles(True), caplog.at_level(logging.WARNING, "jax"):
        yield names


def test_an_eager_scalar_is_seen_by_the_compile_log(compiled_names):
    """The detector of the test below detects: one `jnp.int32(..)` of a
    stage as it was is one compiled program."""
    jax.clear_caches()
    compiled_names()
    jnp.int32(3).block_until_ready()
    assert compiled_names() == ["convert_element_type"]


@pytest.mark.parametrize("kind", KINDS)
def test_a_stage_compiles_and_runs_no_program_of_its_own(models, kind,
                                                         compiled_names):
    """With every cache of compiled code emptied, an admission (its
    chunks) and three waves compile the engine's own named programs and
    nothing else: no `_threefry_split`, `_unstack` or
    `convert_element_type` runs from a stage, for a sampled request (the
    key in use) as for a greedy one."""
    eng = _engine(models, kind)
    _admit(eng, 0, **SAMPLED)
    eng.decode_wave()
    jax.clear_caches()
    compiled_names()
    _admit(eng, 1)
    _admit(eng, 2, **SAMPLED)
    for _ in range(3):
        assert eng.decode_wave()
    names = compiled_names()
    assert "decode_wave" in names or "spec_verify" in names
    assert set(names) <= OWN_PROGRAMS, names


@pytest.mark.parametrize("kind", KINDS)
def test_the_key_chain_and_the_sampled_stream_are_the_hosts(models, kind,
                                                            monkeypatch):
    """After n dispatches the engine's key is the n-fold
    `jax.random.split(.)[0]` of `PRNGKey(seed)`, and the subkey each
    program's selection tail draws with is that split's second half:
    the chain an eager split a dispatch walked. Read off an engine that
    runs its programs op by op (so the tails see values), whose tokens
    the compiled engine's equal."""
    seen = []

    def spy(module, name):
        plain = getattr(module, name)

        def tail(*args):
            if not isinstance(args[-1], jax.core.Tracer):
                seen.append(np.asarray(args[-1]))   # the key, always last
            return plain(*args)
        monkeypatch.setattr(module, name, tail)

    spy(dense_mod, "_select_wave_tokens")
    spy(dense_mod, "_select_first_token")
    spy(paged_mod, "_select_wave_tokens")
    spy(paged_mod, "_select_first_token")
    spy(paged_mod, "_spec_verify_tail")
    waves = 2
    streams = []
    for jit_compile in (False, True):
        eng = _engine(models, kind, seed=11, jit_compile=jit_compile)
        first, chunks = _admit(eng, 1, **SAMPLED)
        streams.append([first] + [eng.decode_wave() for _ in range(waves)])
    assert streams[0] == streams[1]
    # a speculative wave is two programs: the draft's split, then the
    # verify's (the tail above sees the second)
    per_wave = 2 if kind == "spec" else 1
    n = chunks + waves * per_wave
    key, subs = _chain(11, n)
    assert np.array_equal(_key(eng), key)
    want = subs[:chunks] + subs[chunks + per_wave - 1::per_wave]
    assert len(seen) == len(want) == chunks + waves
    for got, sub in zip(seen, want):
        assert np.array_equal(got, sub)


def test_the_draft_wave_draws_from_its_own_subkey(models, monkeypatch):
    """The speculative wave's first split feeds the draft's steps, each
    a further split of it, as when the host split twice a wave."""
    eng = _engine(models, "spec", seed=11, jit_compile=False)
    _, chunks = _admit(eng, 1, **SAMPLED)
    drawn = []
    plain = jax.random.categorical

    def categorical(key, *args, **kw):
        drawn.append(key)
        return plain(key, *args, **kw)

    monkeypatch.setattr(jax.random, "categorical", categorical)
    eng.decode_wave()
    _, subs = _chain(11, chunks + 1)
    step = jnp.asarray(subs[-1])                    # the draft's subkey
    for j in range(SPEC_K):
        step, sub = jax.random.split(step)
        assert np.array_equal(np.asarray(drawn[j]), np.asarray(sub))


@pytest.mark.parametrize("kind", KINDS)
def test_a_raise_before_dispatch_leaves_the_key(models, kind):
    """`chaos.PREFILL` and `chaos.DECODE_WAVE` fire on the host before
    the program is called: the key advances only when a program returns
    the next one."""
    eng = _engine(models, kind)
    _admit(eng, 0, **SAMPLED)
    before = _key(eng)
    monkey = chaos.ChaosMonkey([chaos.Fault(chaos.PREFILL),
                                chaos.Fault(chaos.DECODE_WAVE)])
    with chaos.active(monkey):
        eng.begin_prefill(1, PROMPT, **SAMPLED)
        with pytest.raises(chaos.ChaosError):
            eng.prefill_step(1)
        with pytest.raises(chaos.ChaosError):
            eng.decode_wave()
    assert len(monkey.fired) == 2
    assert np.array_equal(_key(eng), before)
    eng.retire_slot(1)
    assert eng.decode_wave()                        # and serves on
    assert not np.array_equal(_key(eng), before)


def _compiles(eng):
    return (eng.decode_compiles, eng.prefill_compiles,
            getattr(eng, "draft_compiles", 0))


@pytest.mark.parametrize("kind", KINDS)
def test_other_values_a_retire_and_a_rearm_compile_nothing(models, kind):
    """The warm-up's signature is the window's: other scalars, a bias
    row, a retired and a re-armed slot reuse the compiled programs."""
    eng = _engine(models, kind)
    _admit(eng, 0)
    eng.decode_wave()
    warm = _compiles(eng)
    assert warm[:2] == (1, 1)
    _admit(eng, 1, PROMPT[:7], do_sample=True, temperature=0.5, top_k=3,
           top_p=0.7)
    _admit(eng, 2, PROMPT[:13], logit_bias={5: -1e9, 7: 2.0})
    eng.decode_wave()
    eng.retire_slot(0)
    eng.retire_slot(2)
    eng.decode_wave()
    _admit(eng, 0, PROMPT[:9], do_sample=True, temperature=1.3)
    eng.decode_wave()
    assert _compiles(eng) == warm


@pytest.mark.parametrize("kind", KINDS)
def test_per_slot_state_is_numpy_of_the_programs_dtypes(models, kind):
    """Armed, waved and retired in place: the arrays a wave stage packs
    stay the engine's own, and the public names index as they did."""
    eng = _engine(models, kind)
    vectors = {"slot_tok": np.int32, "slot_pos": np.int32,
               "slot_active": np.bool_, "slot_sample": np.bool_,
               "slot_temp": np.float32, "slot_top_k": np.int32,
               "slot_top_p": np.float32}
    held = {name: getattr(eng, name) for name in vectors}
    _admit(eng, 2, do_sample=True, temperature=0.5, top_k=3, top_p=0.75)
    out = eng.decode_wave()
    for name, dtype in vectors.items():
        arr = getattr(eng, name)
        assert arr is held[name], name
        assert arr.dtype == dtype and arr.shape == (SLOTS,), name
    n_emitted = len(out[2]) if kind == "spec" else 1
    assert eng.slot_pos[2] == len(PROMPT) + n_emitted
    assert list(eng.slot_active) == [False, False, True, False]
    assert any(eng.slot_active) and sum(eng.slot_active) == 1
    assert eng.active_slots() == [2] and eng.free_slots() == [0, 1, 3]
    assert (eng.slot_temp[2], eng.slot_top_k[2], eng.slot_top_p[2]) == \
        (0.5, 3, 0.75)
    eng.retire_slot(2)
    assert not eng.slot_active.any() and not eng.slot_sample.any()
    assert eng.slot_temp[2] == 1.0 and eng.slot_top_p[2] == 1.0


@pytest.mark.parametrize("kind", KINDS)
def test_bias_uploads_counts_what_a_bias_sends(models, kind):
    """0 over bias-free requests (the zero row and the zero matrix are
    resident); a biased request sends its row once, however many chunks
    it has, and the matrix once when its slot is armed; the matrix goes
    up once more when the slot's row is zero again. The tokens follow
    the bias as they did."""
    sched = Scheduler(_engine(models, kind))

    def run(**kw):
        req = sched.submit(prompt=PROMPT, max_tokens=4, **kw)
        sched.run()
        return req.output_tokens, sched.metrics.snapshot()["bias_uploads"]

    plain, uploads = run()
    assert uploads == 0
    assert run() == (plain, 0)
    unused = next(t for t in range(VOCAB) if t not in plain)
    # a bias on a token the request never picks changes nothing
    same, uploads = run(logit_bias={unused: -1e9})
    assert same == plain and uploads == 2
    # the matrix is zeros again: sent once, then resident again
    assert run() == (plain, 3)
    assert run() == (plain, 3)
    # a forbidden first choice is not served
    other, uploads = run(logit_bias={plain[0]: -1e9})
    assert plain[0] not in other and uploads == 5


# ---------------------------------------------------------------------------
# a round dispatches its next programs before it reads the last wave's
# tokens (serving/scheduler.py): what that must not change
# ---------------------------------------------------------------------------

PIPELINED = ("dense", "paged", "hybrid")


def _all_allowed(_request):
    """A dynamic token mask that forbids nothing: the tokens are those of
    no mask at all, and the pipeline is held empty while it is in a slot
    (the next mask is a function of the token not read yet)."""
    return np.ones(VOCAB, bool)


def _tokens(kind, seed, n):
    """A prompt of n tokens (a dense engine's bucket holds 32)."""
    n = min(n, 30) if kind == "dense" else n
    return np.random.default_rng(seed).integers(0, VOCAB, n).tolist()


class _Waves:
    """Wraps `engine.dispatch_wave`: for every wave that went out, the
    request each of its lanes served ({slot: request})."""

    def __init__(self, sched):
        self.sched, self.waves = sched, []
        self.plain = sched.engine.dispatch_wave
        sched.engine.dispatch_wave = self

    def __call__(self, skip=()):
        ticket = self.plain(skip)
        if ticket is not None:
            self.waves.append({s: self.sched._slot_req[s]
                               for s in ticket.lanes.tolist()})
        return ticket

    def rode(self, req):
        """How many waves the request was in."""
        return sum(req in wave.values() for wave in self.waves)


def _slot_keeper():
    """({request_id: the slot it was served in}, the `on_token` that
    fills it): a finished request has no slot any more."""
    slots = {}
    return slots, lambda req, _tok: slots.setdefault(req.request_id,
                                                     req.slot)


def _serve_script(sched, script):
    """Submit each (round, kwargs) of `script` before that round and
    drive the scheduler until all are done; (the requests in order, the
    slot each was served in)."""
    reqs, rnd = [], 0
    slots, keep = _slot_keeper()
    script = sorted(script, key=lambda e: e[0])
    while len(reqs) < len(script) or any(not r.done for r in reqs):
        while len(reqs) < len(script) and script[len(reqs)][0] <= rnd:
            reqs.append(sched.submit(on_token=keep,
                                     **script[len(reqs)][1]))
        sched.step()
        rnd += 1
        assert rnd < 400, "the load never drained"
    return reqs, [slots[r.request_id] for r in reqs]


def _mixed_script(kind):
    """Three generations of three requests over slots 1-3, greedy and
    sampled, prompts of one to three chunks (two and three full blocks
    come again as prefix hits), each generation arriving when the one
    before has been gone for rounds; slot 0 decodes throughout."""
    def p(seed, n):
        return _tokens(kind, seed, n)

    first, third = p(1, 20), p(3, 40)
    return [(0, dict(prompt=p(0, 3), max_tokens=58))] + [
        (rnd, dict(prompt=prompt[:30] if kind == "dense" else prompt,
                   max_tokens=m, **kw))
        for rnd, prompt, m, kw in [
            (1, first, 5, SAMPLED), (1, p(2, 7), 4, {}), (1, third, 6, SAMPLED),
            (15, first, 5, {}), (15, p(4, 33), 3, SAMPLED),
            (15, third[:24] + p(5, 9), 7, SAMPLED),
            (30, p(6, 17), 6, SAMPLED), (30, first[:16] + p(7, 2), 5, {}),
            (30, p(8, 1), 8, dict(do_sample=True, temperature=1.2))]]


@pytest.mark.parametrize("kind", PIPELINED)
def test_streams_are_those_of_the_same_load_with_the_pipeline_held_empty(
        models, kind):
    """The same scripted load twice: once as it is (every wave but the
    first goes out before the wave before it is read), once with a
    dynamic-mask lane in slot 0 that forbids nothing (each wave is read
    before the next goes out, the parent's order). Every request lands
    in the same slot, ends for the same reason and streams the same
    tokens, greedy and sampled: the programs go out in the same order,
    so the key chain is the same; and a greedy request's stream is the
    one it has when served alone."""
    runs = []
    for hold_empty in (False, True):
        script = _mixed_script(kind)
        if hold_empty:
            script[0][1]["token_mask"] = _all_allowed
        sched = Scheduler(_engine(models, kind, seed=7))
        reqs, slots = _serve_script(sched, script)
        snap = sched.metrics.snapshot()
        runs.append([(slot, r.finish_reason, r.output_tokens)
                     for r, slot in zip(reqs, slots)])
        assert all(r.finish_reason == "max_tokens" and
                   len(r.output_tokens) == r.max_tokens for r in reqs)
        waves, ahead = snap["waves_dispatched"], \
            snap["waves_dispatched_ahead"]
        assert ahead == (0 if hold_empty else waves - 1), (waves, ahead)
        if kind == "paged":
            assert snap["prefix_hits"] >= 4
    assert runs[0] == runs[1]
    assert sorted({slot for slot, _, _ in runs[0]}) == [0, 1, 2, 3]
    solo = Scheduler(_engine(models, kind))
    for (_, kw), (_, _, out) in zip(_mixed_script(kind), runs[0]):
        if not kw.get("do_sample"):
            assert solo.generate(kw["prompt"],
                                 max_tokens=kw["max_tokens"]) == out


@pytest.mark.parametrize("ends_on", ["eos", "stop"])
@pytest.mark.parametrize("kind", PIPELINED)
def test_a_request_that_ends_on_a_tokens_value_is_found_one_wave_late(
        models, kind, ends_on):
    """`eos_token_id` / a stop sequence: the request ends on the same
    token and is handed nothing after it, though its lane rode one wave
    more (dispatched before that token was read) than when each wave is
    read first; the request queued behind it takes its slot and its
    blocks and streams what it streams alone, as its neighbours do."""
    prompt = _tokens(kind, 11, 20)
    others = [_tokens(kind, 12 + i, n) for i, n in enumerate((9, 25, 14))]
    behind = _tokens(kind, 16, 18)
    solo = Scheduler(_engine(models, kind))
    alone = [solo.generate(p, max_tokens=14) for p in others + [behind]]

    def serve(hold_empty, **ending):
        """The load; (the request that ends, its tokens as `on_token`
        saw them, the others, each one's slot, the waves)."""
        sched = Scheduler(_engine(models, kind, seed=13))
        waves = _Waves(sched)
        slots, keep = _slot_keeper()
        seen = []
        # sampled hot, so that its tokens differ from one another
        req = sched.submit(
            prompt=prompt, max_tokens=12, do_sample=True, temperature=1.5,
            on_token=lambda r, t: (keep(r, t), seen.append(t)), **ending)
        rest = [sched.submit(prompt=p, max_tokens=14, on_token=keep,
                             **(dict(token_mask=_all_allowed)
                                if hold_empty and i == 0 else {}))
                for i, p in enumerate(others + [behind])]
        sched.run()
        return req, seen, rest, slots, waves

    ref = serve(True)[0].output_tokens
    cut = next(i for i in range(2, 12) if ref[i] not in ref[:i]
               and ref[i - 1:i + 1] != ref[i - 2:i])
    ending = (dict(eos_token_id=ref[cut]) if ends_on == "eos"
              else dict(stop_sequences=[ref[cut - 1:cut + 1]]))
    rode = {}
    for hold_empty in (False, True):
        req, seen, rest, slots, waves = serve(hold_empty, **ending)
        assert req.finish_reason == ends_on
        assert req.output_tokens == seen == ref[:cut + 1]
        assert [r.output_tokens for r in rest] == alone
        assert slots[rest[-1].request_id] == slots[req.request_id] == 0
        rode[hold_empty] = waves.rode(req)
    # the first token comes with the prompt, the others a wave each
    assert rode == {True: cut, False: cut + 1}


@pytest.mark.parametrize("ends_on", ["max_tokens", "length"])
@pytest.mark.parametrize("kind", PIPELINED)
def test_a_lane_is_never_in_a_wave_it_has_no_token_left_for(models, kind,
                                                            ends_on):
    """A request that ends by its budget or at the cache horizon ends
    where the host can count: its lane rides exactly the waves whose
    tokens it is handed, with the pipeline full as with it held empty,
    and the tokens are the same."""
    n = MAX_LEN - 4 if ends_on == "length" else 20
    jobs = [dict(prompt=_tokens(kind, 21, n),
                 max_tokens=50 if ends_on == "length" else 6),
            dict(prompt=_tokens(kind, 22, 5), max_tokens=1),
            dict(prompt=_tokens(kind, 23, 11), max_tokens=2, **SAMPLED)]
    outs = []
    for hold_empty in (False, True):
        sched = Scheduler(_engine(models, kind))
        waves = _Waves(sched)
        reqs = [sched.submit(**job) for job in jobs]
        sched.submit(prompt=[1, 2, 3], max_tokens=60 if hold_empty else 2,
                     **(dict(token_mask=_all_allowed) if hold_empty
                        else {}))
        sched.run()
        assert [r.finish_reason for r in reqs] == [ends_on, "max_tokens",
                                                   "max_tokens"]
        for r in reqs:
            assert waves.rode(r) == len(r.output_tokens) - 1
        if ends_on == "length":
            # the last legal write is at max_len - 1
            assert len(reqs[0].prompt) + len(reqs[0].output_tokens) - 1 \
                == MAX_LEN
        outs.append([r.output_tokens for r in reqs])
    assert outs[0] == outs[1]


@pytest.mark.parametrize("kind", PIPELINED)
def test_a_poisoned_lane_is_retired_alone_one_wave_late(models, kind):
    """Non-finite logits in one lane of the second wave: that request
    fails alone, holding what it was handed before, one wave after the
    poison (the wave dispatched meanwhile carried its lane, and what it
    made there is dropped); the neighbours stream what they stream
    without the fault."""
    prompts = [_tokens(kind, 31 + i, n) for i, n in enumerate((6, 12, 9))]
    clean = Scheduler(_engine(models, kind))
    ref = [clean.submit(prompt=p, max_tokens=8) for p in prompts]
    clean.run()
    sched = Scheduler(_engine(models, kind))
    waves = _Waves(sched)
    monkey = chaos.ChaosMonkey([chaos.Fault(
        chaos.DECODE_WAVE_NAN, action="payload", payload=1, times=(2,))])
    with chaos.active(monkey):
        reqs = [sched.submit(prompt=p, max_tokens=8) for p in prompts]
        sched.run()
    assert monkey.fired
    assert [r.finish_reason for r in reqs] == ["max_tokens", "error",
                                               "max_tokens"]
    assert reqs[1].output_tokens == ref[1].output_tokens[:2]
    assert waves.rode(reqs[1]) == 3
    for i in (0, 2):
        assert reqs[i].output_tokens == ref[i].output_tokens
    assert sched.metrics.snapshot()["faults"] == {"nonfinite": 1}
    assert sched.engine.free_slots() == list(range(SLOTS))


@pytest.mark.parametrize("kind", KINDS)
def test_a_dispatch_that_raises_is_retried_with_nothing_mutated(models,
                                                                kind):
    """`chaos.DECODE_WAVE` fires on the host before the third wave goes
    out: what was in flight is read, the dispatch is made again, and
    every stream (a sampled one among them) and the engine's key are
    those of the run without the fault."""
    jobs = [dict(prompt=_tokens(kind, 41, 20), max_tokens=7, **SAMPLED),
            dict(prompt=_tokens(kind, 42, 6), max_tokens=9),
            dict(prompt=_tokens(kind, 43, 13), max_tokens=3)]
    runs = []
    for faults in ([], [chaos.Fault(chaos.DECODE_WAVE, times=(3,))]):
        sched = Scheduler(_engine(models, kind, seed=9),
                          retry_backoff_s=0.001)
        with chaos.active(chaos.ChaosMonkey(faults)):
            reqs = [sched.submit(**job) for job in jobs]
            sched.run()
        snap = sched.metrics.snapshot()
        assert snap["wave_retries"] == len(faults)
        assert snap["faults"] == ({"wave_error": 1} if faults else {})
        assert all(r.finish_reason == "max_tokens" for r in reqs)
        runs.append(([r.output_tokens for r in reqs], _key(sched.engine),
                     snap["waves_dispatched"]))
    assert runs[0][0] == runs[1][0]
    assert np.array_equal(runs[0][1], runs[1][1])
    assert runs[0][2] == runs[1][2]


@pytest.mark.parametrize("kind,held_by", [
    ("dense", None), ("paged", None), ("hybrid", None),
    ("spec", "engine"), ("paged", "mask"), ("dense", "mask"),
    ("paged", "drain")])
def test_waves_dispatched_ahead_counts_the_rounds_that_did_not_wait(
        models, kind, held_by):
    """A plain closed load: every wave but the first goes out while the
    wave before it is unread. None does where the next wave needs the
    last one's tokens on the host: the speculative engine, a dynamic
    token mask on a lane, a draining server."""
    sched = Scheduler(_engine(models, kind))
    mask = dict(token_mask=_all_allowed) if held_by == "mask" else {}
    reqs = [sched.submit(prompt=_tokens(kind, 51, 20), max_tokens=6),
            sched.submit(prompt=_tokens(kind, 52, 4), max_tokens=9, **mask)]
    if held_by == "drain":
        sched.drain()
    sched.run()
    assert all(len(r.output_tokens) == r.max_tokens for r in reqs)
    snap = sched.metrics.snapshot()
    waves = snap["waves_dispatched"]
    assert waves >= 2
    assert snap["waves_dispatched_ahead"] == (waves - 1 if held_by is None
                                              else 0)
    # nothing is left in flight when the load has drained
    assert not sched._waves and not sched.engine.first_tokens_pending
