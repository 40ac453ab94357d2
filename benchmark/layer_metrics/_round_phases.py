"""What the readers of the program's finer per-round phase seconds
share (`host_unfed_ms_per_round`, `wave_host_ms`, `chunk_host_ms`,
`sched_other_ms_per_round`, `host_busy_share`).

The keys are those of `ServingMetrics.snapshot()["phase_seconds"]`
(paddle_tpu/serving/metrics.py, `PHASES`): `round` is the whole of every
scheduler round that had work, dotted keys nest inside `decode_wave` and
`prefill_chunk`, `unfed` is the time from a blocking read of a program's
output to the next dispatch. A program from before these counters has no
`round` key: every reader then returns None.
"""
from .. import readers


def seconds(ctx, *phases):
    """Seconds the window added to `phases`, or None where the program
    does not count them."""
    obs = ctx["obs"]
    if "snap0" not in obs or "round" not in obs["snap1"]["phase_seconds"]:
        return None
    return readers.phase_delta(ctx, *phases)


def worked(ctx, waves_only=False):
    """How many of the window's rounds had work by the benchmark's own
    record, taken before each round: a lane decoding or a request
    prefilling; with `waves_only`, a lane decoding."""
    return sum(1 for r in readers.rounds_in(ctx, *readers.window(ctx))
               if r[2] or (r[4] and not waves_only))


def ms_per_round(ctx, *phases, waves_only=False):
    s, n = seconds(ctx, *phases), worked(ctx, waves_only)
    return None if s is None or not n else 1e3 * s / n


def profiler_gap_intervals(ctx):
    """[(start, end)] of the gaps between consecutive rounds of the window
    that hold an end of the traced window: there the benchmark itself
    starts the profiler, and stops it and reduces the trace."""
    ends = ctx.get("trace_host") or ()
    rounds = readers.rounds_in(ctx, *readers.window(ctx))
    return [(a[1], b[0]) for a, b in zip(rounds, rounds[1:])
            if any(a[1] <= t <= b[0] for t in ends)]


def profiler_gaps(ctx):
    """Seconds of the window that the benchmark itself took between two
    rounds to start the profiler, and to stop it and reduce the trace.
    Requests are in flight then, so the program rightly counts them as
    time it left the device unfed; they are the benchmark's doing
    (1.5-1.7 s of a traced run on the chip), not the program's."""
    return sum(b - a for a, b in profiler_gap_intervals(ctx))
