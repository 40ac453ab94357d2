"""Due time to first token, 90th percentile by nearest rank, over the
requests due in the window's first T - tail seconds."""
from .. import loadgen, readers


def read(ctx):
    p = loadgen.percentile(readers.ttft_waits(ctx), 90)
    return None if p is None else 1e3 * p
