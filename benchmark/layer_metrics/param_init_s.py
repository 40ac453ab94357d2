"""Process: seconds of set-up inside parameter initialisers and the cast
to the serving or training dtype (`startup/param_init`, `startup/cast`):
values drawn on the host and converted, before the benchmark's own
weights replace them."""
from . import _process

LAYER, SOURCE = "process", "program_counter"


def read(ctx):
    return _process.setup_seconds(ctx, "startup", "param_init", "cast")
