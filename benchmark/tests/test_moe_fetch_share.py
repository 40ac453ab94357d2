"""`moe_fetch_share` on made-up snapshots (None for a program that does
not count the experts its waves fetch, the window's share for one that
does), and its entry in `BENCHMARK.json`."""
import pytest

from benchmark import harness

ROUTED = ["nemotron3n-serve-reason", "kanana2-serve-doc",
          "xing4-serve-repo-reason", "laguna-xs2-serve-agent-mix"]


def _read(ctx):
    return harness.load_module("layer_metrics", "moe_fetch_share").read(ctx)


def _snaps(f0, s0, f1, s1):
    return {"obs": {"snap0": {"moe_experts_fetched": f0,
                              "moe_experts_stored": s0},
                    "snap1": {"moe_experts_fetched": f1,
                              "moe_experts_stored": s1}}}


@pytest.mark.parametrize("ctx", [
    {"obs": {}},                                  # a kind with no snapshots
    # a program from before the counters (the parent of the PR that
    # brought them), or a model without experts: nothing, and no raise
    {"obs": {"snap0": {"tokens_generated": 1},
             "snap1": {"tokens_generated": 9}}},
    _snaps(50, 100, 50, 100),                     # no wave in the window
], ids=["no-snapshots", "no-counters", "no-waves"])
def test_nothing_to_read_is_none(ctx):
    assert _read(ctx) is None


@pytest.mark.parametrize("snaps,want", [
    # Laguna's wave: about 102 of 256 experts a layer, four sparse layers
    (_snaps(0, 0, 408 * 50, 1024 * 50), 39.84375),
    # the window's waves, not the run's
    (_snaps(1000, 4096, 1000 + 64 * 5 * 10, 4096 + 64 * 5 * 10), 100.0),
    # the counters reach the snapshot with an engine's first report
    ({"obs": {"snap0": {}, "snap1": {"moe_experts_fetched": 30,
                                     "moe_experts_stored": 120}}}, 25.0),
])
def test_share_is_the_windows_fetched_over_stored(snaps, want):
    assert _read(snaps) == pytest.approx(want)


def test_the_entry_lists_the_routed_cells():
    mod = harness.load_module("layer_metrics", "moe_fetch_share")
    assert (mod.LAYER, mod.SOURCE) == ("moe_experts_kernel",
                                       "program_counter")
    bench = harness.load_benchmark()
    entry, = (m for m in bench["per_layer"]
              if m["name"] == "serve.moe_fetch_share")
    assert harness.reader_name(entry["name"]) == "moe_fetch_share"
    assert entry["workloads"] == ROUTED
    assert (entry["moves"], entry["unit"], entry["layer"]) == (
        "serve_tokens_per_s", "%", "moe_experts_kernel")
    cells = {w["name"] for w in bench["workloads"]}
    assert set(ROUTED) <= cells
