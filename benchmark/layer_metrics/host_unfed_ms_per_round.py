"""Paged engine: milliseconds a round in which the host leaves the
device with nothing queued, by the engine's own clock: from each
blocking read of a program's output (the wave's tokens, a prompt's first
token) to the next program dispatch, an empty server left out, and less
the two gaps between rounds in which the benchmark starts and stops its
own profiler. What `device_idle_share` x `round_ms_p50` should come to,
less the gaps between instructions inside a program."""
from . import _round_phases

LAYER, SOURCE = "paged_engine", "program_counter"


def read(ctx):
    unfed, n = _round_phases.seconds(ctx, "unfed"), _round_phases.worked(ctx)
    if unfed is None or not n:
        return None
    return 1e3 * (unfed - _round_phases.profiler_gaps(ctx)) / n
