"""Kind `serve_closed`: `clients` callers, each with one request
outstanding, more callers than slots, so there is always a queue and the
system runs at its capacity whatever that is. The pre-roll runs until
every slot has emitted a token (or `preroll_max_s`); the first requests'
outputs are shortened to evenly spread fractions, so the window opens on
requests at every stage of their life. `attempted` and `failed` count the
requests that were in the system during the window.
"""
import time

from .. import harness, loadgen
from . import _serving


def offer(ctx, pred, traffic, slots):
    """Send the traffic's first requests and pre-roll. Returns (loop,
    rounds, turn): `turn()` is one scheduler round and the callers'
    answer to it."""
    schedule = loadgen.make_schedule(
        traffic, harness.shapes(ctx.config)["vocab"], ctx.seed,
        int(traffic["requests"]), stagger=slots)
    loop = loadgen.ClosedLoop(pred, schedule,
                              int(traffic["arrivals"]["clients"]))
    rounds = _serving.Rounds(ctx, pred, lambda: loop.live)

    def turn():
        rounds.one()
        with ctx.span("bench/poll"):
            loop.poll()

    with ctx.phase("preroll"):
        t_max = time.perf_counter() + float(traffic["preroll_max_s"])
        while time.perf_counter() < t_max:
            turn()
            if sum(1 for r in loop.records if r.token_t) >= slots:
                break
    return loop, rounds, turn


def run(ctx):
    cell, traffic = ctx.cell, ctx.cell["load"]
    sh = harness.shapes(ctx.config)
    _, weights, pred = _serving.build(ctx)
    _serving.warm(ctx, pred, sh["vocab"])

    loop, rounds, turn = offer(ctx, pred, traffic,
                               int(cell["engine"]["num_slots"]))
    snap0 = _serving.snapshot(pred)
    t0, t1 = _serving.measure(ctx, turn)
    snap1 = _serving.snapshot(pred)
    ctx.note("schedule", requests=int(traffic["requests"]),
             sent=len(loop.records), pending=len(loop.pending))
    if not loop.pending:
        raise harness.BenchmarkError(
            f"the schedule of {traffic['requests']} requests ran out "
            "inside the window: raise `requests` in the traffic file")
    records = list(loop.records)
    seen = [r for r in records
            if r.submit_t <= t1 and not (r.done and r.token_t
                                         and r.token_t[-1] < t0)]
    return _serving.finish(
        ctx, pred, weights, records, rounds, (t0, t1), snap0, snap1,
        attempted=len(seen), failed=sum(1 for r in seen if r.failed),
        extra_obs={"kind": "serve_closed"})
