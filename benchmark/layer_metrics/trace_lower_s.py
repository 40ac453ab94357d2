"""Process: seconds of set-up tracing programs and lowering them to
modules (`jaxpr_trace_duration`, `jaxpr_to_mlir_module_duration`), every
program and eager operation: the part of a first call that no compile
cache takes away."""
from . import _process

LAYER, SOURCE = "process", "program_counter"


def read(ctx):
    trace = _process.setup_seconds(ctx, "trace")
    return None if trace is None else \
        trace + _process.setup_seconds(ctx, "lower")
