"""Paged engine: device time of one execution of the decode-wave
program, median over the traced window."""
from .. import readers

LAYER, SOURCE = "paged_engine", "device_trace"


def read(ctx):
    if not ctx["trace"]:
        return None
    m = readers.median(ctx["trace"]["module_s"].get(
        readers.program(ctx, "decode"), []))
    return None if m is None else 1e3 * m
