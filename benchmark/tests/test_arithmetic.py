"""Percentiles, due-time arithmetic, the generator, and the FLOP and byte
functions against counts made by hand."""
import math

import pytest

from benchmark import flops, harness, loadgen


# ------------------------------------------------------------ percentiles
def test_nearest_rank_percentile():
    xs = list(range(1, 11))                       # 1..10
    assert loadgen.percentile(xs, 90) == 9
    assert loadgen.percentile(xs, 99) == 10
    assert loadgen.percentile(xs, 50) == 5
    assert loadgen.percentile([7.0], 99) == 7.0
    assert loadgen.percentile([], 50) is None
    # 60 samples: p90 is the 54th, six samples lie beyond it
    assert loadgen.percentile(list(range(60)), 90) == 53


def _rec(due, tokens, prompt=4):
    r = loadgen.Record(loadgen.Planned(0, due, [0] * prompt, len(tokens),
                                       None), due_t=due, submit_t=due)
    r.token_t = list(tokens)
    return r


def test_ttft_is_timed_from_due_time_and_counts_the_missing():
    recs = [_rec(10.0, [10.5, 10.7, 11.0]),        # ttft 0.5
            _rec(12.0, [14.0]),                    # ttft 2.0
            _rec(15.0, []),                        # none by the end: 5.0
            _rec(19.5, [19.6]),                    # due in the tail: out
            _rec(9.0, [9.5]),                      # due before the window
            _rec(14.0, [21.0])]                    # first token after t1
    waits, missing = loadgen.ttft_sample(recs, 10.0, 20.0, tail_s=2.0)
    assert sorted(waits) == pytest.approx([0.5, 2.0, 5.0, 6.0])
    assert missing == 2
    gaps = loadgen.token_gaps(recs, 10.0, 20.0)
    assert sorted(gaps) == pytest.approx([0.2, 0.3])
    assert loadgen.tokens_in(recs, 10.0, 20.0) == 5


# -------------------------------------------------------------- generator
TRAFFIC = {"shape_seed": 7, "block": 16,
           "arrivals": {"process": "poisson", "rate_per_s": 4.0},
           "prompt_len": {"dist": "lognormal", "median": 64, "sigma": 0.8,
                          "min": 8, "max": 256},
           "output_len": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                          "min": 2, "max": 64},
           "shared_prefix": {"share": 0.5, "count": 2, "len": 32,
                             "min_own": 4}}


def _shape(schedule):
    return [(p.due_s, len(p.prompt), p.max_tokens, p.prefix_id)
            for p in schedule]


def test_same_seed_same_schedule_and_a_pinned_pattern_keeps_its_shape():
    a = loadgen.make_schedule(TRAFFIC, 1000, 5, 64)
    b = loadgen.make_schedule(TRAFFIC, 1000, 5, 64)
    c = loadgen.make_schedule(TRAFFIC, 1000, 6, 64)
    assert a == b
    # the file pins the pattern: another seed, other token ids only
    assert _shape(a) == _shape(c)
    assert [p.prompt for p in a] != [p.prompt for p in c]


def test_without_shape_seed_the_pattern_comes_from_the_seed():
    free = {k: v for k, v in TRAFFIC.items() if k != "shape_seed"}
    a = loadgen.make_schedule(free, 1000, 5, 64)
    assert a == loadgen.make_schedule(free, 1000, 5, 64)
    c = loadgen.make_schedule(free, 1000, 6, 64)
    assert _shape(a) != _shape(c)
    # another order of the same strata: a block offers the same work
    for b0 in (0, 16, 32, 48):
        ta = sum(len(p.prompt) for p in a[b0:b0 + 16])
        tc = sum(len(p.prompt) for p in c[b0:b0 + 16])
        assert ta == pytest.approx(tc, rel=0.1)
    assert a[-1].due_s == pytest.approx(c[-1].due_s, rel=0.05)


def test_schedule_holds_the_distribution_block_by_block():
    s = loadgen.make_schedule(TRAFFIC, 1000, 1, 64)
    # stratified exponential gaps: every block of 16 lasts about 16 / rate
    for b0 in (16, 32, 48):
        assert s[b0 + 15].due_s - s[b0 - 1].due_s == pytest.approx(
            4.0, rel=0.15)
    assert all(x.due_s < y.due_s for x, y in zip(s, s[1:]))
    assert all(8 <= len(p.prompt) - (32 if p.prefix_id is not None else 0)
               or p.prefix_id is not None for p in s)
    sharers = [p for p in s if p.prefix_id is not None]
    assert len(sharers) == 32                      # half of every block
    heads = {p.prefix_id: p.prompt[:32] for p in sharers}
    assert len(heads) == 2
    assert all(p.prompt[:32] == heads[p.prefix_id] for p in sharers)
    assert all(len(p.prompt) >= 36 for p in sharers)
    assert all(2 <= p.max_tokens <= 64 for p in s)


def test_closed_loop_schedule_and_stagger():
    t = dict(TRAFFIC, arrivals={"process": "closed", "clients": 8})
    t.pop("shared_prefix")
    plain = loadgen.make_schedule(t, 1000, 1, 32)
    stag = loadgen.make_schedule(t, 1000, 1, 32, stagger=16)
    assert all(p.due_s is None for p in plain)
    assert [p.max_tokens for p in plain[16:]] == \
        [p.max_tokens for p in stag[16:]]
    assert all(1 <= q.max_tokens <= p.max_tokens
               for p, q in zip(plain[:16], stag[:16]))
    assert sum(q.max_tokens for q in stag[:16]) < \
        0.75 * sum(p.max_tokens for p in plain[:16])


def test_arrival_processes_and_distributions_are_the_ones_a_cell_uses():
    import numpy as np
    rng = np.random.default_rng(0)
    due = loadgen.arrival_times({"process": "poisson", "rate_per_s": 2.0},
                                256, 32, rng)
    gaps = np.diff(np.concatenate([[0.0], due]))
    assert gaps.mean() == pytest.approx(0.5, rel=0.05)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, rel=0.15)
    assert loadgen.arrival_times({"process": "closed"}, 4, 4, None) is None
    with pytest.raises(ValueError):
        loadgen.arrival_times({"process": "gamma", "rate_per_s": 1.0}, 4, 4,
                              rng)
    with pytest.raises(ValueError):
        loadgen.draw_lengths({"dist": "uniform", "min": 1, "max": 2}, 4, 4,
                             rng)


# ---------------------------------------------------------- FLOPs, bytes
def _shapes(name):
    return harness.shapes(harness.read_json(
        f"{harness.BENCH_DIR}/configs/{name}.json"))


def test_gpt2_small_counts_by_hand():
    sh = _shapes("gpt2-small")
    # a block: qkv 768x2304, out 768x768, two MLP matrices 768x3072
    block = 768 * 2304 + 768 * 768 + 2 * 768 * 3072
    assert block == 7_077_888
    assert flops.matmul_params(sh) == 12 * block + 768 * 50257
    assert flops.matmul_params(sh) == 123_532_032
    # causal at 1024: a query sees 512.5 keys on average
    assert flops.mean_attended_keys(1024) == 512.5
    att = 4 * 12 * 768 * 512.5
    assert flops.attention_flops_per_token(sh, 1024) == att
    assert flops.train_flops_per_token(sh, 1024) == \
        6 * 123_532_032 + 3 * att == 797_870_592
    # 125,000 tok/s on one v5e: 0.506 of 197 TFLOP/s
    assert flops.mfu(797_870_592, 125_000, 1, 197e12) == \
        pytest.approx(0.50626, abs=1e-4)


def test_mistral_d8_counts_by_hand():
    sh = _shapes("mistral-7b-d8")
    block = 4096 * (4096 + 2 * 1024) + 4096 * 4096 + 3 * 4096 * 14336
    assert block == 218_103_808
    assert flops.matmul_params(sh) == 8 * block + 4096 * 32000 \
        == 1_875_902_464
    # window 4096 at sequence 4096 never clips
    assert flops.mean_attended_keys(4096, 4096) == 2048.5
    # window 1024 at sequence 4096: 1024 ramp-up queries, 3072 full
    assert flops.mean_attended_keys(4096, 1024) == \
        (1024 * 1025 / 2 + 3072 * 1024) / 4096
    assert flops.train_flops_per_token(sh, 4096) == \
        6 * 1_875_902_464 + 3 * 4 * 8 * 4096 * 2048.5


def test_paged_decode_cost_by_hand():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    g = _shapes("gpt2-small")
    # 256 lanes at 300 cached positions each = 76,800 positions; a
    # position is 12 layers x K and V x 768 x 2 bytes = 36,864 bytes
    ops, nbytes = flops.paged_decode_cost(g, 76_800)
    assert nbytes == 76_800 * 36_864
    assert ops == 12 * 4 * 768 * 76_800
    least, bound = flops.roofline_seconds(ops, nbytes, peaks)
    assert bound == "memory" and least == pytest.approx(3.457e-3, rel=1e-3)
    m = _shapes("mistral-7b-d8")
    ops, nbytes = flops.paged_decode_cost(m, 1000)
    assert nbytes == 1000 * 8 * 2 * 8 * 128 * 2            # 32,768 a position
    assert ops == 1000 * 8 * 4 * 32 * 128
    assert ops / nbytes == 4.0                             # GQA 32/8


def test_decode_wave_cost_by_hand():
    """The whole decode step of the two dense configurations at the sizes
    their cells run: counts only."""
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    g = _shapes("gpt2-small")
    # 256 lanes at 200 cached positions each: 123.5 M matmul weights and
    # the tied head read once (0.247 GB), 51,200 positions of 36,864 bytes
    ops, nbytes = flops.decode_wave_cost(g, 256, 51_200)
    assert flops.matmul_params(g) == 123_532_032
    assert nbytes == 2 * 123_532_032 + 51_200 * 36_864
    assert ops == 2 * 256 * 123_532_032 + 12 * 4 * 768 * 51_200
    least, bound = flops.roofline_seconds(ops, nbytes, peaks)
    assert bound == "memory" and least == pytest.approx(2.606e-3, rel=1e-3)
    m = _shapes("mistral-7b-d8")
    # 36 lanes at 500 positions: 3.75 GB of weights, 0.59 GB of K/V
    ops, nbytes = flops.decode_wave_cost(m, 36, 18_000)
    assert nbytes == 2 * 1_875_902_464 + 18_000 * 32_768
    assert ops == 2 * 36 * 1_875_902_464 + 8 * 4 * 32 * 128 * 18_000
    least, bound = flops.roofline_seconds(ops, nbytes, peaks)
    assert bound == "memory" and least == pytest.approx(5.301e-3, rel=1e-3)
    # no lane, nothing attended: the weights alone; and lanes only add
    # operations, never bytes
    assert flops.decode_wave_cost(m, 0, 0) == (0.0, 2 * 1_875_902_464)
    assert flops.decode_wave_cost(m, 64, 18_000)[1] == nbytes


def test_flash_train_cost_by_hand():
    g = _shapes("gpt2-small")
    ops, nbytes = flops.flash_train_cost(g, 16, 1024)
    per_matmul = 2 * 16 * 1024 * 768 * 512.5
    assert ops == 12 * 7 * per_matmul
    assert nbytes == 12 * 12 * 16 * 1024 * 768 * 2
    least, bound = flops.roofline_seconds(
        ops, nbytes, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9})
    assert bound == "compute"
    assert math.isclose(least, ops / 197e12)
