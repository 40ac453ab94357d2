"""Scheduler: share of the rounds' time in which the host works instead
of waiting for the device: `round` less the blocking reads (`wave.wait`,
`prefill.first_token`), over `round`. The host sets the pace when this
nears 100."""
from .. import readers
from . import _round_phases

LAYER, SOURCE = "scheduler", "program_counter"


def read(ctx):
    whole = _round_phases.seconds(ctx, "round")
    if not whole:
        return None
    waits = _round_phases.seconds(ctx, "wave.wait", "prefill.first_token")
    return readers.percent(whole - waits, whole)
