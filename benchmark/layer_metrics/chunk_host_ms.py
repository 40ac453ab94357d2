"""Paged engine: host milliseconds a round to enqueue its prefill
chunks (building each chunk, the uploads, the jit calls), over the
window's rounds that had work."""
from . import _round_phases

LAYER, SOURCE = "paged_engine", "program_counter"


def read(ctx):
    return _round_phases.ms_per_round(ctx, "prefill.stage",
                                      "prefill.dispatch")
