"""Load generator: how late a request was handed to the server after
it was due, 99th percentile. A starved generator makes a slow server
look fast."""
from .. import loadgen, readers

LAYER, SOURCE = "load_generator", "host_clock"


def read(ctx):
    t0, t1 = readers.window(ctx)
    late = [r.submit_t - r.due_t for r in readers.records(ctx) or ()
            if r.due_t is not None and t0 <= r.due_t <= t1]
    p = loadgen.percentile(late, 99)
    return None if p is None else 1e3 * p
