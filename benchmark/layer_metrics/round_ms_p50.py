"""Service: the benchmark's clock around `scheduler.step()`, median
over the rounds of the window that had work."""
from .. import readers

LAYER, SOURCE = "service", "host_clock"


def read(ctx):
    rs = [r for r in readers.rounds_in(ctx, *readers.window(ctx))
          if r[2] or r[4]]
    m = readers.median([r[1] - r[0] for r in rs])
    return None if m is None else 1e3 * m
