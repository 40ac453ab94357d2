"""Find the knee of a serving cell, once, when the cell is defined: run
the cell's traffic at several fixed rates (and, as "closed", with every
slot always full) and print what each did. Not part of a check: the cell
then offers load at the one rate written into its file.

    python -m benchmark.knee --workload <cell> --seed 1 --seconds 60 \\
        --rates closed,0.6,0.9,1.2

One process, one model; each rate gets a fresh predictor (and cache) and
the cell's own pre-roll. A rate is sustained if the backlog (requests
waiting for a slot) at the window's end is no larger than at its middle
and no request was shed. Prints notes only.
"""
import argparse
import gc
import sys
import time

from . import harness, loadgen
from .kinds import _serving, serve_closed, serve_open
from .run import Context


def _one_rate(ctx, model, rate, seconds):
    """The cell's traffic at `rate` through the loops its kinds run."""
    cell, traffic = ctx.cell, dict(ctx.cell["load"])
    sh = harness.shapes(ctx.config)
    slots = int(cell["engine"]["num_slots"])
    pred = _serving.build_predictor(ctx, model)
    _serving.warm(ctx, pred, sh["vocab"])
    preroll = float(traffic.get("preroll_s", 20.0))
    gen = loop = None
    if rate == "closed":
        traffic.update(arrivals={"process": "closed", "clients": 2 * slots},
                       requests=64 * slots, preroll_max_s=preroll)
        loop, rounds, turn = serve_closed.offer(ctx, pred, traffic, slots)
        t0 = time.perf_counter()
    else:
        traffic["arrivals"] = dict(traffic["arrivals"], rate_per_s=rate)
        gen, rounds, turn, t0 = serve_open.offer(ctx, pred, traffic, seconds)
    backlog = []
    try:
        while time.perf_counter() < t0 + seconds:
            turn()
            backlog.append((time.perf_counter(),
                            pred.scheduler.queue_depth(),
                            pred.scheduler.in_flight()))
        t1 = time.perf_counter()
    finally:
        if gen is not None:
            gen.stop()
    records = list(gen.records if gen is not None else loop.records)
    waits, missing = loadgen.ttft_sample(records, t0, t1, 10.0)
    gaps = loadgen.token_gaps(records, t0, t1)
    done = [r for r in records if r.done and r.token_t
            and t0 <= r.token_t[-1] <= t1]
    life = [r.token_t[-1] - (r.due_t or r.submit_t) for r in done]
    backlog = [b for b in backlog if b[0] >= t0]
    mid = [b for b in backlog if b[0] >= (t0 + t1) / 2][0]
    rs = [r[1] - r[0] for r in rounds.log
          if r[0] >= t0 and r[1] <= t1 and (r[2] or r[4])]
    snap = pred.metrics.snapshot()
    late = [1e3 * (r.submit_t - r.due_t) for r in records
            if r.due_t is not None and t0 <= r.due_t <= t1]
    ctx.note(
        "rate", rate=rate, seconds=round(t1 - t0, 1),
        due=sum(1 for r in records if r.due_t and t0 <= r.due_t <= t1),
        completed_per_s=round(len(done) / (t1 - t0), 3),
        tokens_per_s=round(loadgen.tokens_in(records, t0, t1) / (t1 - t0), 1),
        queued_mid=mid[1], queued_end=backlog[-1][1],
        in_flight_mid=mid[2], in_flight_end=backlog[-1][2],
        ttft_ms={p: round(1e3 * loadgen.percentile(waits, p), 1)
                 for p in (50, 90)} if waits else None,
        ttft_n=len(waits), ttft_missing=missing,
        gen_late_ms_p99=loadgen.percentile(late, 99),
        tpot_ms={p: round(1e3 * loadgen.percentile(gaps, p), 1)
                 for p in (50, 99)} if gaps else None,
        life_s_p50=round(loadgen.percentile(life, 50), 1) if life else None,
        round_ms_p50=round(1e3 * loadgen.percentile(rs, 50), 1) if rs else None,
        rounds=len(rs), faults=snap["faults"], rejected=snap["rejected"],
        prefix_hit_rate=snap["prefix_hit_rate"])
    pred.close(drain=False)
    del pred, rounds, turn, gen, loop
    gc.collect()


def main(argv=None):
    ap = argparse.ArgumentParser(prog="python -m benchmark.knee")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=60.0)
    ap.add_argument("--rates", required=True,
                    help='comma-separated requests/s, or "closed"')
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.trace, args.keep_trace = 0, None
    if args.rehearse:
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
    bench = harness.load_benchmark()
    entry, cell, config = harness.load_cell(bench, args.workload,
                                            rehearse=args.rehearse)
    if not args.rehearse:
        harness.device_info(int(entry["chips"]))
    harness.enable_compile_cache()
    ctx = Context(args, entry, cell, config, harness.CompileCounter())
    model, _ = harness.build_model(config, args.seed)
    for rate in args.rates.split(","):
        _one_rate(ctx, model, rate if rate == "closed" else float(rate),
                  args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
