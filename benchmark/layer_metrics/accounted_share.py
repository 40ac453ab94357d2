"""Process: the share of `setup_s` that the program's own record
explains: initialisers and cast, engine or step build, trace and lower,
compile, cache load. The rest is the interpreter and its imports, the
backend's start, and the harness's weights, warm-up requests and
pre-roll as far as they run programs already loaded."""
from .. import readers
from . import (build_s, cache_load_s, compile_s, param_init_s,
               trace_lower_s)

LAYER, SOURCE = "process", "program_counter"


def read(ctx):
    parts = [m.read(ctx) for m in (param_init_s, build_s, trace_lower_s,
                                   compile_s, cache_load_s)]
    if None in parts:
        return None
    return readers.percent(sum(parts), ctx["setup_s"])
