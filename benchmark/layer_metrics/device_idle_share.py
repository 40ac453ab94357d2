"""Device: 1 - (union of the intervals in which an instruction ran)
over the traced window; mean over chips."""
from .. import readers

LAYER, SOURCE = "device", "device_trace"


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["chips"] or not tr["window_s"]:
        return None
    return 100.0 - readers.percent(tr["busy_s"], tr["window_s"])
