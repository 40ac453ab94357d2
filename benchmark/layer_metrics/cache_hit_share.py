"""Process: of the executables the compile cache was asked for during
set-up, the share it held: the run's cache state. A pair whose sides read
apart here compares a compiling run with a loading one."""
from .. import readers
from . import _process

LAYER, SOURCE = "process", "program_counter"


def read(ctx):
    hits = _process.setup_count(ctx, "cache_hit")
    if hits is None:
        return None
    return readers.percent(hits, hits + _process.setup_count(ctx,
                                                             "cache_miss"))
