"""Process: seconds of set-up in backend compilations, requests that the
persistent cache did not hold (near 0 in a run from the cache, tens of
seconds in one against an empty directory)."""
from . import _process

LAYER, SOURCE = "process", "program_counter"


def read(ctx):
    return _process.setup_seconds(ctx, "compile")
