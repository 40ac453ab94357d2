"""Scheduler: milliseconds of a round outside the four phases it always
counted: `round` less admission, prefill_chunk, decode_wave and
host_dispatch. Token masks, the wave journal, the non-finite sweep,
preemption, `round_tail` (pool sample, SLO, sampler, alerts) and the
glue between them."""
from . import _round_phases

LAYER, SOURCE = "scheduler", "program_counter"


def read(ctx):
    whole = _round_phases.ms_per_round(ctx, "round")
    four = _round_phases.ms_per_round(ctx, "admission", "prefill_chunk",
                                      "decode_wave", "host_dispatch")
    return None if whole is None else whole - four
