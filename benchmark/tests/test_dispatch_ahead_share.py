"""`dispatch_ahead_share` on made-up snapshots (None for a program that
does not count its waves' dispatches, the window's share for one that
does), and its two entries at the end of `BENCHMARK.json`."""
import pytest

from benchmark import harness

NAMES = ("serve.dispatch_ahead_share", "tpot.dispatch_ahead_share")


def _read(ctx):
    return harness.load_module("layer_metrics",
                               "dispatch_ahead_share").read(ctx)


def _snaps(w0, a0, w1, a1):
    return {"obs": {"snap0": {"waves_dispatched": w0,
                              "waves_dispatched_ahead": a0},
                    "snap1": {"waves_dispatched": w1,
                              "waves_dispatched_ahead": a1}}}


@pytest.mark.parametrize("ctx", [
    {"obs": {}},                                  # a kind with no snapshots
    # a program from before the counters: the parent of the PR that
    # brought them reports nothing, and does not raise
    {"obs": {"snap0": {"tokens_generated": 1},
             "snap1": {"tokens_generated": 9}}},
    {"obs": {"snap0": {}, "snap1": {"waves_dispatched": 4}}},
    _snaps(7, 6, 7, 6),                           # no wave in the window
], ids=["no-snapshots", "no-counters", "one-counter", "no-waves"])
def test_nothing_to_read_is_none(ctx):
    assert _read(ctx) is None


@pytest.mark.parametrize("snaps,want", [
    (_snaps(0, 0, 400, 399), 99.75),       # a closed load: all but the first
    (_snaps(120, 119, 520, 419), 75.0),    # the window's waves, not the run's
    (_snaps(10, 0, 60, 0), 0.0),           # every wave read before the next
])
def test_share_is_the_windows_ahead_over_dispatched(snaps, want):
    assert _read(snaps) == pytest.approx(want)


def test_the_reader_names_its_layer_and_source():
    mod = harness.load_module("layer_metrics", "dispatch_ahead_share")
    assert (mod.LAYER, mod.SOURCE) == ("scheduler", "program_counter")
    assert {harness.reader_name(n) for n in NAMES} == {
        "dispatch_ahead_share"}


def test_the_two_entries_end_the_per_layer_list():
    bench = harness.load_benchmark()
    last = bench["per_layer"][-2:]
    assert tuple(m["name"] for m in last) == NAMES
    serve, tpot = last
    assert serve["moves"] == "serve_tokens_per_s"
    assert tpot["moves"] == "tpot_p99_ms"
    assert tpot["workloads"] == ["mistral7b-serve-chat"]
    for m in last:
        assert (m["unit"], m["better"], m["source"], m["layer"]) == (
            "%", "higher", "program_counter", "scheduler")
        # each listed cell reports the end-to-end metric the entry moves
        e2e = next(e for e in bench["end_to_end"]
                   if e["name"] == m["moves"])
        assert set(m["workloads"]) <= set(e2e["workloads"])
    assert set(serve["workloads"]) | set(tpot["workloads"]) == {
        w["name"] for w in bench["workloads"] if "serve" in w["name"]}
    # what stood before them stands as it did: the list's former end
    assert bench["per_layer"][-3]["name"] == "tpot.gc_pause_max_ms"
    assert len({m["name"] for m in bench["per_layer"]}) == len(
        bench["per_layer"]) == 71
