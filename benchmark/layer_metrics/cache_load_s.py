"""Process: seconds of set-up loading executables from the persistent
compile cache: the retrieval, and the cache key around it."""
from . import _process

LAYER, SOURCE = "process", "program_counter"


def read(ctx):
    return _process.setup_seconds(ctx, "cache_load")
