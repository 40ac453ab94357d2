"""Collectives: time in which a collective was in flight and no other
instruction ran on that chip, over the traced window; mean over chips."""
from .. import readers

LAYER, SOURCE = "collectives", "device_trace"


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["collective_s"]:
        return None
    return readers.percent(tr["exposed_collective_s"], tr["window_s"])
