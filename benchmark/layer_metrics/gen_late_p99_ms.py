"""Load generator: how late a request was handed to the server after
it was due, 99th percentile. A starved generator makes a slow server
look fast. Requests due while the benchmark itself starts or stops its
profiler between two rounds are left out: the interpreter is held then,
by the benchmark and not by the server, and at hundreds of requests a
window those few would be all the 99th percentile reads. Every run,
traced or not, notes the lateness of all its requests (`generator`)."""
from .. import loadgen, readers
from . import _round_phases

LAYER, SOURCE = "load_generator", "host_clock"


def read(ctx):
    t0, t1 = readers.window(ctx)
    gaps = _round_phases.profiler_gap_intervals(ctx)
    late = [r.submit_t - r.due_t for r in readers.records(ctx) or ()
            if r.due_t is not None and t0 <= r.due_t <= t1
            and not any(a <= r.due_t <= b for a, b in gaps)]
    p = loadgen.percentile(late, 99)
    return None if p is None else 1e3 * p
