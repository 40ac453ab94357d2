"""Block pool, the window pool: pages in use over the lanes holding any,
as the program counts them at the end of each round (`window_pages_held`
and `window_lanes_held`, each summed a round), over the window's rounds:
the mean pages a lane holds. A lane needs at most its window and the
chunk it runs (65 pages at a window and a chunk of 512, 33 in decode);
lower is better. None for a program without a window pool."""
from ._counters import delta

LAYER, SOURCE = "block_pool", "program_counter"


def read(ctx):
    held, lanes = (delta(ctx, k) for k in ("window_pages_held",
                                           "window_lanes_held"))
    return held / lanes if lanes and held is not None else None
