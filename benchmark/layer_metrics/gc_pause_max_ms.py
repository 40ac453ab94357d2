"""Process: the longest single pause of the collector inside the window,
in milliseconds: what one request's token gap can be stretched by. 0
where no pause reached the millisecond from which they are journaled;
pauses where the benchmark starts and stops its own profiler are left
out."""
from . import _counters, _process

LAYER, SOURCE = "process", "program_counter"


def read(ctx):
    pauses = _process.gc_pauses(ctx)
    if pauses is None or _counters.delta(ctx, "gc_pause_seconds") is None:
        return None
    return 1e3 * max(pauses[0], default=0.0)
