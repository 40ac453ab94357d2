"""The readers of the program's finer phase seconds, on a hand-made
context: two snapshots of `phase_seconds`, three rounds with work (one
of them without a wave), an idle spin and a round that began before the
window. And `kernel_class` on the texts of the named kernels' events as
a v5e trace recorded them (data/named_kernels.json)."""
import json
import os

import pytest

from benchmark import harness
from benchmark import trace_reduce as tr

NEW = ("host_unfed_ms_per_round", "wave_host_ms", "chunk_host_ms",
       "sched_other_ms_per_round", "host_busy_share")
BEFORE = {"round": 1.0, "admission": 0.01, "prefill_chunk": 0.2,
          "decode_wave": 0.7, "host_dispatch": 0.02, "token_masks": 0.001,
          "round_tail": 0.002, "wave.blocks": 0.001, "wave.stage": 0.002,
          "wave.dispatch": 0.003, "wave.wait": 0.69,
          "prefill.stage": 0.004, "prefill.dispatch": 0.006,
          "prefill.first_token": 0.18, "unfed": 0.05}
ADDED = {"round": 3.0, "admission": 0.03, "prefill_chunk": 0.6,
         "decode_wave": 2.1, "host_dispatch": 0.06, "token_masks": 0.003,
         "round_tail": 0.006, "wave.blocks": 0.002, "wave.stage": 0.004,
         "wave.dispatch": 0.006, "wave.wait": 2.0, "prefill.stage": 0.009,
         "prefill.dispatch": 0.012, "prefill.first_token": 0.4,
         "unfed": 0.075 + 0.2}
#: by hand, over 3 rounds with work, 2 of them with a wave
EXPECTED = {
    # less the two gaps of 0.1 s in which the profiler starts and stops
    "host_unfed_ms_per_round": 1e3 * (0.275 - 2 * 0.1) / 3,        # 25
    "wave_host_ms": 1e3 * (0.002 + 0.004 + 0.006) / 2,             # 6
    "chunk_host_ms": 1e3 * (0.009 + 0.012) / 3,                    # 7
    "sched_other_ms_per_round":
        1e3 * (3.0 - (0.03 + 0.6 + 2.1 + 0.06)) / 3,               # 70
    "host_busy_share": 100 * (3.0 - 2.0 - 0.4) / 3.0,              # 20
}


def _ctx(before=BEFORE, added=ADDED):
    # (start, end, lanes decoding, positions attended, prefilling, blocks)
    rounds = [(9.5, 10.5, 4, 100, 1, 7),       # began before the window
              (10.5, 11.4, 4, 100, 1, 7),
              (11.5, 12.4, 4, 100, 0, 7),      # the traced round
              (12.5, 13.0, 0, 0, 2, 7),        # chunks only: no wave
              (13.0, 13.002, 0, 0, 0, 7)]      # an idle spin
    obs = {"window": (10.0, 20.0), "rounds": rounds}
    if before is not None:
        obs["snap0"] = {"phase_seconds": dict(before)}
        obs["snap1"] = {"phase_seconds": {k: before.get(k, 0.0) + v
                                          for k, v in added.items()}}
    # the profiler starts in the gap before the traced round, and stops
    # (and the trace is reduced) in the gap after it
    return {"obs": obs, "trace_host": (11.45, 12.45)}


def _read(name, ctx):
    return harness.load_module("layer_metrics", name).read(ctx)


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_the_hand_computed_number(name):
    assert _read(name, _ctx()) == pytest.approx(EXPECTED[name], rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_reader_returns_none_where_there_is_nothing_to_read(name):
    # a kind of cell that takes no snapshots of the program
    assert _read(name, _ctx(before=None)) is None
    # a program from before these counters: the four old phases only
    old = ("admission", "prefill_chunk", "decode_wave", "host_dispatch")
    assert _read(name, _ctx({k: BEFORE[k] for k in old},
                            {k: ADDED[k] for k in old})) is None
    # no round of the window had work
    ctx = _ctx()
    ctx["obs"]["rounds"] = ctx["obs"]["rounds"][-1:]
    ctx["obs"]["snap1"] = ctx["obs"]["snap0"]
    assert _read(name, ctx) is None


def test_unfed_that_never_accrued_reads_zero_not_none():
    added = {k: v for k, v in ADDED.items() if k != "unfed"}
    before = {k: v for k, v in BEFORE.items() if k != "unfed"}
    ctx = _ctx(before, added)
    ctx["trace_host"] = None                   # an untraced window
    assert _read("host_unfed_ms_per_round", ctx) == 0.0


def test_new_entries_name_the_new_readers_in_both_serving_cells():
    per_layer = harness.load_benchmark()["per_layer"]
    got = {(m["name"], m["workloads"][0], m["moves"]) for m in per_layer
           if harness.reader_name(m["name"]) in NEW}
    want = {(f"serve.{r}", "gpt2s-serve-batch", "serve_tokens_per_s")
            for r in NEW}
    want |= {(("ttft." if r == "chunk_host_ms" else "tpot.") + r,
              "mistral7b-serve-chat",
              "ttft_p90_ms" if r == "chunk_host_ms" else "tpot_p99_ms")
             for r in NEW}
    assert got == want


def test_kernel_class_still_classes_the_named_kernels_events():
    """The program now names its kernels; `kernel_class` goes by operand
    types and must class the new texts as it classed the old ones."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "named_kernels.json")
    with open(path) as f:
        texts = json.load(f)["events"]
    assert set(texts) == {"paged_attention", "flash_fwd", "flash_bwd_dkv",
                          "flash_bwd_dq"}
    for name, text in texts.items():
        assert tr.kernel_class(text) == name
        # and the instruction carries the name the program gave it
        assert tr.parse_hlo(text) == (name, "custom-call")
