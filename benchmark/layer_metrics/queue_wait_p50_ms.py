"""Scheduler: due time to admission into a slot (the program's
`Request.prefill_time`), median over the requests due in the window."""
from .. import readers

LAYER, SOURCE = "scheduler", "program_span"


def read(ctx):
    t0, t1 = readers.window(ctx)
    waits = [r.request.prefill_time + readers.PERF_MINUS_MONOTONIC
             - r.due_t
             for r in readers.records(ctx) or ()
             if r.due_t is not None and t0 <= r.due_t <= t1
             and r.request is not None and r.request.prefill_time]
    m = readers.median(waits)
    return None if m is None else 1e3 * m
