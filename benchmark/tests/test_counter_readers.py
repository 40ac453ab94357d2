"""The counters the program keeps reach their readers, and the share of
the whole decode step is the hand count: `_serving.snapshot` on a fake
predictor, `paged_visit_share` on two snapshots, `wave_mfu` on a
hand-made trace summary."""
import pytest

from benchmark import flops, harness
from benchmark.kinds import _serving

V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _read(name, ctx):
    return harness.load_module("layer_metrics", name).read(ctx)


class _Metrics:
    phases = {"round": 1.5}

    def snapshot(self):
        return {"tokens_generated": 7, "tokens_per_s": None, "rejected": 0,
                "slot_occupancy": 0.5, "faults": {"nan": 1},
                "phase_seconds": self.phases, "paged_pages_visited": 30,
                "paged_pages_spanned": 120, "a_later_counter": 4,
                "a_flag": True, "first_token_time": None}


class _Pred:
    metrics = _Metrics()


def test_snapshot_hands_on_every_number_and_the_two_groups():
    snap = _serving.snapshot(_Pred())
    assert snap == {"tokens_generated": 7, "rejected": 0,
                    "slot_occupancy": 0.5, "faults": {"nan": 1},
                    "phase_seconds": {"round": 1.5},
                    "paged_pages_visited": 30, "paged_pages_spanned": 120,
                    "a_later_counter": 4}
    # a copy: the program goes on counting into its own
    assert snap["phase_seconds"] is not _Metrics.phases


def _snaps(v0, s0, v1, s1):
    return {"obs": {"snap0": {"paged_pages_visited": v0,
                              "paged_pages_spanned": s0},
                    "snap1": {"paged_pages_visited": v1,
                              "paged_pages_spanned": s1}}}


def test_paged_visit_share_is_the_windows_visited_over_spanned():
    # 100 lanes x 64 table entries a wave, 13 pages walked a lane, 5 waves
    assert _read("paged_visit_share", _snaps(900, 4000, 900 + 6500,
                                             4000 + 32000)) \
        == pytest.approx(100 * 6500 / 32000)


@pytest.mark.parametrize("ctx", [
    {"obs": {}},                                  # a kind with no snapshots
    {"obs": {"snap0": {"prefix_hits": 1}, "snap1": {"prefix_hits": 2}}},
    _snaps(900, 4000, 900, 4000),                 # no wave was staged
], ids=["no_snapshots", "older_program", "no_wave"])
def test_paged_visit_share_returns_none_with_nothing_to_read(ctx):
    assert _read("paged_visit_share", ctx) is None


GPT2 = {"layers": 12, "hidden": 768, "heads": 12, "kv_heads": 12,
        "head_dim": 64, "ffn": 3072, "ffn_matrices": 2, "vocab": 50257,
        "window": None, "tied_head": True}


def _wave_ctx(shapes=GPT2, rounds=None, modules=None):
    rounds = rounds if rounds is not None else [
        # (start, end, lanes decoding, positions attended, prefilling, blocks)
        (11.0, 11.1, 256, 50000, 2, 9), (11.1, 11.2, 240, 46000, 1, 9),
        (11.2, 11.25, 0, 0, 3, 9),                 # chunks only: no wave
        (13.0, 13.1, 256, 99999, 0, 9)]            # outside the traced window
    return {"shapes": shapes, "peaks": V5E, "trace_host": (10.9, 12.0),
            "cell": {"programs": {"decode": "decode_wave"}},
            "trace": {"module_s": modules if modules is not None else
                      {"decode_wave": [0.020, 0.022, 0.030]}},
            "obs": {"rounds": rounds}}


def test_wave_mfu_is_the_least_time_over_the_median_wave():
    lanes, attended = (256 + 240) / 2, (50000 + 46000) / 2
    weights = 12 * (768 * 3 * 768 + 768 * 768 + 2 * 768 * 3072) + 768 * 50257
    nbytes = 2 * weights + 12 * 2 * 768 * 2 * attended
    ops = 2 * lanes * weights + 12 * 4 * 768 * attended
    assert flops.decode_wave_cost(GPT2, lanes, attended) == \
        pytest.approx((ops, nbytes))
    assert nbytes / 819e9 > ops / 197e12            # memory binds
    assert _read("wave_mfu", _wave_ctx()) == pytest.approx(
        100 * (nbytes / 819e9) / 0.022)
    assert 5 < _read("wave_mfu", _wave_ctx()) < 100


@pytest.mark.parametrize("ctx", [
    dict(_wave_ctx(), trace=None),                        # an untraced run
    _wave_ctx(shapes=dict(GPT2, pattern="ME*")),          # a hybrid stack
    _wave_ctx(modules={"prefill_chunk": [0.001]}),        # no wave traced
    _wave_ctx(rounds=[(11.2, 11.25, 0, 0, 3, 9)]),        # no lane decoding
], ids=["untraced", "pattern", "no_wave_program", "no_lane"])
def test_wave_mfu_returns_none_with_nothing_to_read(ctx):
    assert _read("wave_mfu", ctx) is None


def test_gen_late_leaves_out_the_requests_due_while_the_profiler_starts():
    import types

    def rec(due, late):
        return types.SimpleNamespace(due_t=due, submit_t=due + late)
    # rounds end at 11.0 and 12.0; the profiler starts in the gap
    # 11.0-11.3 and stops in 12.0-12.6 (trace_host lies inside both)
    rounds = [(10.0, 11.0, 1, 1, 0, 1), (11.3, 12.0, 1, 1, 0, 1),
              (12.6, 13.0, 1, 1, 0, 1), (13.0, 14.0, 1, 1, 0, 1)]
    records = [rec(10.5, 0.001), rec(11.1, 0.2), rec(11.5, 0.002),
               rec(12.3, 0.3), rec(13.5, 0.004), rec(9.0, 0.5),
               types.SimpleNamespace(due_t=None, submit_t=10.0)]  # closed
    ctx = {"obs": {"window": (10.0, 14.0), "records": records,
                   "rounds": rounds}, "trace_host": (11.2, 12.1)}
    assert _read("gen_late_p99_ms", ctx) == pytest.approx(4.0)
    # an untraced window leaves nothing out
    assert _read("gen_late_p99_ms", dict(ctx, trace_host=None)) == \
        pytest.approx(300.0)
    assert _read("gen_late_p99_ms", {"obs": {"window": (0.0, 1.0),
                                             "records": []}}) is None
