"""The program's one span primitive and what the serving round and the
train step do with it (docs/observability.md "Spans").

`RecordEvent` has two sinks: a `jax.profiler.TraceAnnotation` on the
profiler's clock, and the chrome buffer after `start_profiler()`. The
scheduler and the engine meter the same spans into
`ServingMetrics.snapshot()["phase_seconds"]`, and the engine counts the
seconds it leaves the device unfed. Canonical tiny LLaMA scale, on the
CPU: these tests check names, nesting, ids and arithmetic, never a time.
"""
import glob
import json
import os
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import paddle_tpu as pt
from paddle_tpu.nlp import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import (PagedServingEngine, Scheduler,
                                ServingEngine, SpeculativePagedEngine)
from paddle_tpu.utils import profiler as prof
from paddle_tpu.utils.profiler import RecordEvent

VOCAB, MAX_LEN, BLOCK, CHUNK = 128, 64, 8, 16

#: span -> `phase_seconds` key, as docs/observability.md tabulates them
SPAN_PHASE = {
    "serving/round": "round",
    "serving/admission": "admission",
    "serving/prefill": "prefill_chunk",
    "serving/prefill/stage": "prefill.stage",
    "serving/prefill/dispatch": "prefill.dispatch",
    "serving/prefill/first_token": "prefill.first_token",
    "serving/token_masks": "token_masks",
    "serving/decode_wave": "decode_wave",
    "serving/wave/blocks": "wave.blocks",
    "serving/wave/stage": "wave.stage",
    "serving/wave/dispatch": "wave.dispatch",
    "serving/wave/wait": "wave.wait",
    "serving/host_dispatch": "host_dispatch",
    "serving/round_tail": "round_tail",
}
LEGACY = ("admission", "prefill_chunk", "decode_wave", "host_dispatch")


def _llama(layers=2, seed=7):
    pt.seed(seed)
    return LlamaForCausalLM(LlamaConfig(
        vocab_size=VOCAB, hidden_size=64, num_layers=layers, num_heads=4,
        num_kv_heads=2, max_seq_len=MAX_LEN))


@pytest.fixture(scope="module")
def model():
    return _llama()


def _paged(model):
    return PagedServingEngine(model, num_slots=4, max_len=MAX_LEN,
                              block_size=BLOCK, num_blocks=33,
                              prefill_chunk_len=CHUNK)


@pytest.fixture(scope="module")
def paged(model):
    return _paged(model)


def _engine(kind, model):
    if kind == "dense":
        return ServingEngine(model, num_slots=4, max_len=MAX_LEN)
    if kind == "speculative":
        return SpeculativePagedEngine(
            model, _llama(layers=1, seed=8), spec_k=2, num_slots=4,
            max_len=MAX_LEN, block_size=BLOCK, num_blocks=33,
            prefill_chunk_len=CHUNK)
    return _paged(model)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(0, VOCAB, (n,)).tolist()


def _serve(sched, n=3, max_tokens=4):
    reqs = [sched.submit(prompt=_prompt(5 + i, seed=i),
                         max_tokens=max_tokens) for i in range(n)]
    sched.run()
    assert all(r.done and len(r.output_tokens) == max_tokens for r in reqs)
    return sched.metrics.snapshot()["phase_seconds"]


def _host_events(trace_dir, prefix):
    """{thread line: [event]} of the host plane's events named
    `prefix...`, read with nothing but JAX."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert paths, f"the profiler wrote no trace under {trace_dir}"
    out = {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [e for e in line.events if e.name.startswith(prefix)]
            if evs:
                out.setdefault(line.name, []).extend(evs)
    return out


def _inside(inner, outer):
    return (outer.start_ns <= inner.start_ns and inner.start_ns
            + inner.duration_ns <= outer.start_ns + outer.duration_ns)


# ---------------------------------------------------------------------------
# the primitive
# ---------------------------------------------------------------------------

def test_without_a_session_nothing_is_recorded_and_elapsed_is_set():
    class Loud:
        def __str__(self):
            raise AssertionError("an id was formatted with no session")

    before = len(prof._events)
    with RecordEvent("quiet", round=Loud(), lanes=3) as ev:
        time.sleep(0.002)
    assert not prof.trace_enabled()
    assert len(prof._events) == before
    assert ev.elapsed >= 0.002 and ev.end >= ev.elapsed


def test_chrome_sink_keeps_ids_as_args_and_the_decorator_keeps_pid(tmp_path):
    prof.start_profiler()

    @RecordEvent("decorated", pid=3, slot=1)
    def work():
        return 7

    with RecordEvent("plain", round=2, request_id=11):
        assert work() == 7
    with RecordEvent("bare"):
        pass
    path = str(tmp_path / "t.json")
    prof.stop_profiler(profile_path=path)
    evs = {e["name"]: e for e in json.load(open(path))["traceEvents"]}
    assert evs["plain"]["args"] == {"round": 2, "request_id": 11}
    assert evs["decorated"]["pid"] == 3
    assert evs["decorated"]["args"] == {"slot": 1}
    assert "args" not in evs["bare"]


def test_spans_reach_a_jax_profiler_trace_nested_with_ids(paged, tmp_path):
    """An operator's `jax.profiler.start_trace` (not this module's
    start_profiler) sees the round, the wave's dispatch inside it and, a
    round later, its read with the device wait inside that, on one
    thread, with the number of the round that dispatched on both."""
    sched = Scheduler(paged)
    _serve(sched, n=1, max_tokens=2)            # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        req = sched.submit(prompt=_prompt(CHUNK + 3), max_tokens=3)
        sched.run()
    finally:
        jax.profiler.stop_trace()
    assert not prof._events or not prof.trace_enabled()
    by_thread = _host_events(str(tmp_path), "serving/")
    assert len(by_thread) == 1, sorted(by_thread)
    evs = next(iter(by_thread.values()))
    named = {}
    for e in evs:
        named.setdefault(e.name, []).append(e)
    assert set(named) >= set(SPAN_PHASE), set(SPAN_PHASE) - set(named)
    rounds = {dict(e.stats)["round"]: e for e in named["serving/round"]}
    waits = named["serving/wave/wait"]
    assert waits
    dispatched, collected = [], []
    for wave in named["serving/decode_wave"]:
        ids = dict(wave.stats)
        inside = [sum(1 for e in named[name] if _inside(e, wave))
                  for name in ("serving/wave/dispatch", "serving/wave/wait")]
        if "collect" in ids:
            # read in the round after the one that dispatched it
            assert inside == [0, 1]
            assert _inside(wave, rounds[ids["round"] + 1])
            collected.append(ids["round"])
        else:
            assert ids["lanes"] == 1 and inside == [1, 0]
            assert _inside(wave, rounds[ids["round"]])
            dispatched.append(ids["round"])
    assert dispatched == collected and len(waits) == len(collected) == 2
    # a two-chunk prompt: two prefill spans of this request, chunk 0 and
    # 1, and the read of its first token, a span of its own behind the
    # wave's dispatch
    chunks = [dict(e.stats) for e in named["serving/prefill"]]
    assert [c["first_tokens"] for c in chunks if "chunk" not in c] == [1]
    chunks = [c for c in chunks if "chunk" in c]
    assert [c["chunk"] for c in chunks] == [0, 1]
    assert {c["request_id"] for c in chunks} == {req.request_id}
    assert all("slot" in c for c in chunks)
    # the engine's part of a chunk lies inside the scheduler's span
    for name in ("serving/prefill/stage", "serving/prefill/dispatch",
                 "serving/prefill/first_token"):
        for e in named[name]:
            assert any(_inside(e, p) for p in named["serving/prefill"])
    ids = dict(named["serving/round"][0].stats)
    assert {"round", "lanes", "prefilling"} <= set(ids)


def test_train_step_emits_a_step_annotation(tmp_path):
    from paddle_tpu import nn, optimizer
    from paddle_tpu.jit import TrainStep
    pt.seed(3)
    net = nn.Linear(8, 4)
    step = TrainStep(net, lambda out, y: ((out - y) ** 2).mean(),
                     optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters()))
    x = pt.to_tensor(np.ones((2, 8), np.float32))
    y = pt.to_tensor(np.zeros((2, 4), np.float32))
    step(x, y)                                   # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        step(x, y)
        step(x, y)
    finally:
        jax.profiler.stop_trace()
    evs = next(iter(_host_events(str(tmp_path), "train").values()))
    steps = [e for e in evs if e.name == "train"]
    assert [dict(e.stats)["step_num"] for e in steps] == [2, 3]
    assert all(dict(e.stats).get("_r") == 1 for e in steps)   # a step event
    for name in ("train/stage", "train/dispatch"):
        inner = [e for e in evs if e.name == name]
        assert [dict(e.stats)["step"] for e in inner] == [2, 3]
        assert all(_inside(i, s) for i, s in zip(inner, steps))


# ---------------------------------------------------------------------------
# phase_seconds: what the spans meter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["dense", "paged", "speculative"])
def test_every_phase_is_its_spans_total(kind, model):
    """Each `phase_seconds` key is the summed `elapsed` of the spans of
    one name: the chrome sink (recording here) sees the same seconds."""
    sched = Scheduler(_engine(kind, model))
    prof.start_profiler()
    try:
        ph = _serve(sched)
    finally:
        rows = {r["name"]: r for r in prof.stop_profiler()}
    # `unfed` is there when the host saw the device run dry with a
    # request waiting: always where every wave is read before the next
    # is dispatched
    assert set(ph) - {"unfed"} == set(SPAN_PHASE.values())
    assert "unfed" in ph or kind != "speculative"
    for span, phase in SPAN_PHASE.items():
        if span == "serving/round":
            continue            # idle rounds have the span, not the phase
        assert ph[phase] == pytest.approx(rows[span]["total_ms"] / 1e3,
                                          rel=1e-9, abs=1e-12), span
    assert ph["round"] <= rows["serving/round"]["total_ms"] / 1e3 + 1e-12


def test_dotted_phases_sum_to_within_their_parent(paged):
    ph = _serve(Scheduler(paged))
    wave = sum(ph[k] for k in ph if k.startswith("wave."))
    chunk = sum(ph[k] for k in ph if k.startswith("prefill."))
    assert 0 < wave <= ph["decode_wave"]
    assert 0 < chunk <= ph["prefill_chunk"]
    assert ph["wave.wait"] > 0 and ph["prefill.first_token"] > 0


def test_round_covers_the_legacy_phases_which_keep_their_meaning(paged):
    """`round` is the whole of every round that had work, so it holds
    the four phases the scheduler always had; those still are what
    `sched_host_ms_per_round` reads (admission + host_dispatch: no wave,
    no chunk, no wait for the device in either)."""
    sched = Scheduler(paged)
    ph = _serve(sched)
    assert set(LEGACY) <= set(ph)
    assert ph["round"] >= sum(ph[k] for k in LEGACY)
    assert ph["round"] >= sum(ph[k] for k in LEGACY) + ph["token_masks"] \
        + ph["round_tail"]
    # the device is waited for inside decode_wave and prefill_chunk only
    assert ph["admission"] + ph["host_dispatch"] < ph["round"] \
        - ph["wave.wait"] - ph["prefill.first_token"]
    # an idle spin adds to no round
    before = sched.metrics.snapshot()["phase_seconds"]["round"]
    assert sched.step() == 0
    assert sched.metrics.snapshot()["phase_seconds"]["round"] == before


# ---------------------------------------------------------------------------
# unfed: the seconds the host leaves the device with nothing queued
# ---------------------------------------------------------------------------

def _unfed(sched):
    return sched.metrics.snapshot()["phase_seconds"].get("unfed", 0.0)


class _StillRunning:
    """A program's output that the device has not produced yet."""

    def is_ready(self):
        return False


@pytest.mark.parametrize("prompt_len, max_tokens",
                         [(3 * CHUNK + 5, 2), (5, 6)],
                         ids=["chunks-alone", "waves-read-one-late"])
def test_unfed_is_zero_while_the_newest_program_still_runs(
        model, prompt_len, max_tokens):
    """A four-chunk prompt alone (chunk after chunk is dispatched and
    nothing is read back until the last one), and a decoding lane whose
    every read is of the wave BEFORE the newest: while the newest program
    dispatched has not finished (here: its output never reads as ready),
    no read and no end of a round opens an unfed interval, whatever the
    host does in between."""
    sched = Scheduler(_paged(model))
    eng = sched.engine
    plain = eng._dispatched
    eng._dispatched = lambda phase, ev, out: plain(phase, ev,
                                                   _StillRunning())
    sched.submit(prompt=_prompt(prompt_len), max_tokens=max_tokens)
    for _ in range(3):
        assert sched.step() == 1
        time.sleep(0.02)
    assert eng.prefilling_slots() == ([0] if max_tokens == 2 else [])
    sched.run()
    assert _unfed(sched) == 0.0


def test_unfed_grows_by_a_sleep_between_a_read_back_and_the_next_dispatch(
        model):
    sched = Scheduler(_paged(model))
    sched.submit(prompt=_prompt(5), max_tokens=8)
    assert sched.step() == 1          # chunk, first token, wave: read back
    before = _unfed(sched)
    time.sleep(0.05)                  # the host dawdles; a lane is waiting
    assert sched.step() == 1
    assert _unfed(sched) - before >= 0.05


def test_unfed_ignores_an_empty_server(model):
    sched = Scheduler(_paged(model))
    sched.generate(_prompt(5), max_tokens=3)    # compiles: unfed, rightly
    before = _unfed(sched)
    for idle_spin in (False, True):
        time.sleep(0.2)               # nobody asks for anything
        if idle_spin:
            assert sched.step() == 0
            time.sleep(0.2)
        sched.generate(_prompt(5), max_tokens=3)
    assert _unfed(sched) - before < 0.2


# ---------------------------------------------------------------------------
# the collector's pauses, and the train step's label
# ---------------------------------------------------------------------------

def test_one_gc_hook_however_many_schedulers_are_built(paged):
    import gc
    from paddle_tpu.utils import telemetry
    for _ in range(3):
        Scheduler(paged)
    assert gc.callbacks.count(telemetry._on_gc) == 1


def test_a_collection_inside_a_round_is_one_serving_gc_span(paged):
    import gc
    from paddle_tpu.utils import telemetry
    sched = Scheduler(paged)                 # names the family `serving`
    before = sched.metrics.snapshot()
    prof.start_profiler()
    try:
        with RecordEvent("serving/round") as round_:
            gc.collect()
    finally:
        prof.stop_profiler()
    after = sched.metrics.snapshot()
    spans = [e for e in prof._events if e[0] == "serving/gc"
             and e[5]["generation"] == 2]
    assert len(spans) == 1                   # entered once, left once
    name, t0, dur, *_ = spans[0]
    assert round_.end - round_.elapsed <= t0 and t0 + dur <= round_.end
    assert after["gc_gen2_collections"] == before["gc_gen2_collections"] + 1
    assert after["gc_collections"] > before["gc_collections"]
    assert after["gc_pause_seconds"] >= before["gc_pause_seconds"] + dur
    assert telemetry.gc_totals() == {
        k: after[k] for k in ("gc_pause_seconds", "gc_collections",
                              "gc_gen2_collections")}


def test_a_plain_train_step_is_heard_under_its_label():
    """No recorder attached: the constructor installs the listener, and
    the first call's trace, lowering and compile (or load) are journaled
    as `train_step`; the build is a `startup/step_build` entry."""
    from paddle_tpu import nn, optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.utils import telemetry
    telemetry.clear_process_journal()
    net = nn.Linear(8, 4)
    step = TrainStep(net, lambda out, y: ((out - y) ** 2).mean(),
                     optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters()))
    assert step._recorder is None
    assert telemetry._install_state["installed"]
    before = telemetry.compile_count("train_step")
    step(pt.to_tensor(np.ones((2, 8), np.float32)),
         pt.to_tensor(np.zeros((2, 4), np.float32)))
    assert telemetry.compile_count("train_step") == before + 1
    kinds = telemetry.process_summary()["kinds"]
    assert "step_build" in kinds["startup"]["labels"]
    for stage in ("trace", "lower"):
        assert kinds[stage]["labels"]["train_step"]["seconds"] > 0
    assert any("train_step" in kinds.get(k, {"labels": ()})["labels"]
               for k in ("compile", "cache_load"))
