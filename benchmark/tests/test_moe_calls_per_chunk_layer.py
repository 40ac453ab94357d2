"""`moe_calls_per_chunk_layer` on made-up snapshots (None for a program
that does not count its chunks' kernel calls, the window's calls over
layers for one that does), and its entry in `BENCHMARK.json`."""
import pytest

from benchmark import harness

ROUTED = ["nemotron3n-serve-reason", "kanana2-serve-doc",
          "xing4-serve-repo-reason", "laguna-xs2-serve-agent-mix"]


def _read(ctx):
    return harness.load_module("layer_metrics",
                               "moe_calls_per_chunk_layer").read(ctx)


def _snaps(c0, l0, c1, l1):
    return {"obs": {"snap0": {"moe_chunk_calls": c0, "moe_chunk_layers": l0},
                    "snap1": {"moe_chunk_calls": c1,
                              "moe_chunk_layers": l1}}}


@pytest.mark.parametrize("ctx", [
    {"obs": {}},                                  # a kind with no snapshots
    # a program from before the counters (the parent of the PR that
    # brought them), or a model without experts: nothing, and no raise
    {"obs": {"snap0": {"tokens_generated": 1},
             "snap1": {"tokens_generated": 9}}},
    _snaps(14, 7, 14, 7),                         # no chunk in the window
], ids=["no-snapshots", "no-counters", "no-chunks"])
def test_nothing_to_read_is_none(ctx):
    assert _read(ctx) is None


@pytest.mark.parametrize("snaps,want", [
    # kanana2's chunk as two segments: 7 sparse layers x 2 calls
    (_snaps(0, 0, 14 * 90, 7 * 90), 2.0),
    # the window's chunks, not the run's: one call a layer
    (_snaps(140, 70, 140 + 5 * 40, 70 + 5 * 40), 1.0),
    # the counters reach the snapshot with an engine's first report
    ({"obs": {"snap0": {}, "snap1": {"moe_chunk_calls": 12,
                                     "moe_chunk_layers": 8}}}, 1.5),
])
def test_the_windows_calls_over_layers(snaps, want):
    assert _read(snaps) == pytest.approx(want)


def test_the_entry_lists_the_routed_cells():
    mod = harness.load_module("layer_metrics", "moe_calls_per_chunk_layer")
    assert (mod.LAYER, mod.SOURCE) == ("moe_experts_kernel",
                                       "program_counter")
    bench = harness.load_benchmark()
    entry, = (m for m in bench["per_layer"]
              if m["name"] == "serve.moe_calls_per_chunk_layer")
    assert harness.reader_name(entry["name"]) == "moe_calls_per_chunk_layer"
    assert entry["workloads"] == ROUTED
    assert (entry["moves"], entry["unit"], entry["better"],
            entry["source"], entry["layer"]) == (
        "serve_tokens_per_s", "calls", "lower", "program_counter",
        "moe_experts_kernel")
    fetch, = (m for m in bench["per_layer"]
              if m["name"] == "serve.moe_fetch_share")
    assert entry["workloads"] == fetch["workloads"]
