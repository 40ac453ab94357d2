"""Service: how many requests the time-to-first-token percentile was
taken over."""
from .. import readers

LAYER, SOURCE = "service", "host_clock"


def read(ctx):
    waits = readers.ttft_waits(ctx)
    return None if waits is None else len(waits)
