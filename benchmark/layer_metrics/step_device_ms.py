"""Train step: device time of one execution of the step program,
median over the traced steps."""
from .. import readers

LAYER, SOURCE = "train_step", "device_trace"


def read(ctx):
    if not ctx["trace"]:
        return None
    m = readers.median(ctx["trace"]["module_s"].get(
        readers.program(ctx, "step"), []))
    return None if m is None else 1e3 * m
