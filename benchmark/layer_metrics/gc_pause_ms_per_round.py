"""Process: milliseconds a round that the interpreter's collector held
the scheduler's process: the window's delta of the program's
`gc_pause_seconds` (every collection, however short), less the pauses
that fell where the benchmark starts and stops its own profiler, over
the rounds with work."""
from . import _counters, _process, _round_phases

LAYER, SOURCE = "process", "program_counter"


def read(ctx):
    pause, n = _counters.delta(ctx, "gc_pause_seconds"), \
        _round_phases.worked(ctx)
    pauses = _process.gc_pauses(ctx)
    if pause is None or pauses is None or not n:
        return None
    return 1e3 * max(0.0, pause - sum(pauses[1])) / n
