"""Scheduler: the program's own `admission` + `host_dispatch` phase
seconds over the rounds of the window."""
from .. import readers

LAYER, SOURCE = "scheduler", "program_counter"


def read(ctx):
    if "snap0" not in ctx["obs"]:
        return None
    n = len(readers.rounds_in(ctx, *readers.window(ctx)))
    if not n:
        return None
    return 1e3 * readers.phase_delta(ctx, "admission",
                                     "host_dispatch") / n
