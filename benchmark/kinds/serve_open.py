"""Kind `serve_open`: requests arrive on a clock (the traffic file's
arrival process at its fixed rate), whether or not earlier ones have
finished. The schedule starts `preroll_s` before the window so that the
window opens on a working system; a request is timed from the moment it
was due. `attempted` and `failed` count the requests due in the window.
"""
import time

from .. import harness, loadgen
from . import _serving


def offer(ctx, pred, traffic, seconds):
    """Start the traffic's schedule for its pre-roll and `seconds` more.
    Returns (generator, rounds, turn, when the pre-roll ends): `turn()`
    is one scheduler round."""
    preroll = float(traffic["preroll_s"])
    rate = float(traffic["arrivals"]["rate_per_s"])
    n = int((preroll + seconds) * rate * 1.25) + 8
    schedule = loadgen.make_schedule(
        traffic, harness.shapes(ctx.config)["vocab"], ctx.seed, n)
    gen = loadgen.OpenLoop(pred, schedule, time.perf_counter() + 0.05)
    rounds = _serving.Rounds(ctx, pred, lambda: gen.records)
    gen.start()
    return gen, rounds, rounds.one, gen.t0 + preroll


def run(ctx):
    traffic = ctx.cell["load"]
    sh = harness.shapes(ctx.config)
    _, weights, pred = _serving.build(ctx)
    _serving.warm(ctx, pred, sh["vocab"])

    gen, rounds, turn, t_open = offer(ctx, pred, traffic, ctx.seconds)
    try:
        with ctx.phase("preroll"):
            while time.perf_counter() < t_open:
                turn()
        snap0 = _serving.snapshot(pred)
        t0, t1 = _serving.measure(ctx, turn)
        snap1 = _serving.snapshot(pred)
    finally:
        gen.stop()
    records = list(gen.records)
    tail = float(traffic["ttft_tail_s"])
    due = [r for r in records if t0 <= r.due_t <= t1]
    waits, missing = loadgen.ttft_sample(records, t0, t1, tail)
    failed = sum(1 for r in due if r.failed) + missing
    # in every run, traced or not: a starved producer must not read as a
    # fast server (the per-layer `gen_late_p99_ms` is a traced run's)
    late = [1e3 * (r.submit_t - r.due_t) for r in due]
    ctx.note("generator", due=len(due), timed=len(waits),
             late_ms={p: loadgen.percentile(late, p) for p in (50, 99, 100)},
             awaiting_first_token={
                 "mid": loadgen.awaiting_first_token(records, (t0 + t1) / 2),
                 "end": loadgen.awaiting_first_token(records, t1)})
    gaps = loadgen.token_gaps(records, t0, t1)
    ctx.note("tails", gaps=len(gaps),
             tpot_ms={p: 1e3 * loadgen.percentile(gaps, p)
                      for p in (50, 90, 95, 99, 99.9)} if gaps else None,
             ttft_ms={p: 1e3 * loadgen.percentile(waits, p)
                      for p in (50, 90, 99)} if waits else None)
    return _serving.finish(
        ctx, pred, weights, records, rounds, (t0, t1), snap0, snap1,
        attempted=len(due), failed=failed,
        extra_obs={"kind": "serve_open", "ttft_tail_s": tail,
                   "ttft_missing": missing,
                   "rate_per_s": float(traffic["arrivals"]["rate_per_s"])})
