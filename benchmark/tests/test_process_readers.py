"""The readers of the process's own journal (`layer_metrics/_process.py`):
each on a made-up `ctx` and journal with entries on both sides of the
window's start, nothing to read on an empty journal or on a program from
before the journal, and `LAYER` / `SOURCE` as their entries say."""
import pytest

from benchmark import harness
from paddle_tpu.utils import telemetry

T0, T1 = 100.0, 145.0
SETUP = ("param_init_s", "build_s", "trace_lower_s", "compile_s",
         "cache_load_s", "cache_hit_share", "accounted_share")
GC = ("gc_pause_ms_per_round", "gc_pause_max_ms")


def _read(name, ctx):
    return harness.load_module("layer_metrics", name).read(ctx)


@pytest.fixture
def journal():
    """40 s of set-up as the program would journal it, then a window with
    the referee's compile and three pauses in it."""
    telemetry.clear_process_journal()
    put = telemetry.record_process_event
    put("startup", "param_init", 12.0, t_end=20.0)
    put("startup", "param_init", 8.0, t_end=30.0)        # one entry
    put("startup", "cast", 5.0, t_end=36.0)
    put("startup", "pool", 1.0, t_end=38.0)
    put("startup", "engine", 3.0, t_end=39.0)          # self: 2.0
    put("trace", "serving_decode_wave", 4.0, t_end=50.0)
    put("lower", "serving_decode_wave", 2.0, t_end=52.0)
    put("cache_miss", "serving_decode_wave", t_end=59.0)
    put("compile", "serving_decode_wave", 8.0, t_end=60.0)
    put("trace", "serving_prefill", 1.0, t_end=70.0)
    put("lower", "serving_prefill", 0.5, t_end=71.0)
    for t in (72.0, 73.0, 74.0):
        put("cache_hit", "serving_prefill", t_end=t)
    put("cache_load", "serving_prefill", 1.5, t_end=75.0)
    put("gc", 2, 0.050, t_end=90.0)                    # before the window
    # inside the window and after it: none of the set-up readers' business
    put("gc", 2, 0.120, t_end=110.0)
    put("gc", 1, 0.004, t_end=120.0)
    put("gc", 2, 0.900, t_end=130.5)                   # the profiler's stop
    put("compile", "reference_forward", 30.0, t_end=T1 + 40.0)
    yield
    telemetry.clear_process_journal()


def _ctx(rounds=True, gc=True):
    log = [(100.0 + i, 100.9 + i, 8, 400, 1, 9) for i in range(30)] \
        + [(131.0 + i, 131.9 + i, 8, 400, 0, 9) for i in range(10)] \
        + [(141.0, 141.5, 0, 0, 0, 9)]                 # a round with no work
    snap0 = {"phase_seconds": {}, "faults": {}}
    snap1 = {"phase_seconds": {}, "faults": {}}
    if gc:
        snap0.update(gc_pause_seconds=0.30, gc_collections=400,
                     gc_gen2_collections=3)
        snap1.update(gc_pause_seconds=0.30 + 1.224, gc_collections=900,
                     gc_gen2_collections=5)
    return {"setup_s": 80.0,
            "trace_host": (118.0, 130.2),    # its stop lies in 129.9-131.0
            "obs": {"window": (T0, T1), "rounds": log if rounds else [],
                    "snap0": snap0, "snap1": snap1}}


@pytest.mark.parametrize("name,want", [
    ("param_init_s", 25.0), ("build_s", 3.0), ("trace_lower_s", 7.5),
    ("compile_s", 8.0), ("cache_load_s", 1.5),
    ("cache_hit_share", 75.0), ("accounted_share", 100 * 45.0 / 80.0),
    # 1.224 s in the window, 0.9 of it where the profiler stopped, over
    # the 40 rounds with work; the longest of the rest
    ("gc_pause_ms_per_round", 1e3 * 0.324 / 40), ("gc_pause_max_ms", 120.0),
])
def test_reader_on_a_made_up_journal(journal, name, want):
    assert _read(name, _ctx()) == pytest.approx(want)


@pytest.mark.parametrize("name", SETUP)
def test_nothing_to_read_on_an_empty_journal(name):
    telemetry.clear_process_journal()
    assert _read(name, _ctx()) is None


@pytest.mark.parametrize("name", SETUP + GC)
def test_a_program_from_before_the_journal_reads_nothing(
        journal, monkeypatch, name):
    monkeypatch.delattr(telemetry, "process_summary")
    monkeypatch.delattr(telemetry, "process_events")
    assert _read(name, _ctx(gc=False)) is None


@pytest.mark.parametrize("ctx", [
    {"obs": {"window": (T0, T1)}},                      # no snapshots
    _ctx(gc=False),                                     # an older program
], ids=["no_snapshots", "older_program"])
@pytest.mark.parametrize("name", GC)
def test_gc_readers_return_none_without_the_counter(journal, name, ctx):
    assert _read(name, ctx) is None


def test_gc_readers_with_no_round_or_no_long_pause():
    telemetry.clear_process_journal()
    assert _read("gc_pause_ms_per_round", _ctx(rounds=False)) is None
    assert _read("gc_pause_max_ms", _ctx()) == 0.0


def test_layer_and_source_are_their_entries():
    bench = harness.load_benchmark()
    entries = [m for m in bench["per_layer"] if m["layer"] == "process"]
    assert {harness.reader_name(m["name"]) for m in entries} \
        == set(SETUP + GC)
    cells = [w["name"] for w in bench["workloads"]]
    for m in entries:
        reader = harness.load_module("layer_metrics",
                                     harness.reader_name(m["name"]))
        assert (reader.LAYER, reader.SOURCE) == (m["layer"], m["source"])
        if m["name"].startswith("setup."):
            assert (m["moves"], m["workloads"]) == ("setup_s", cells)
