"""Paged engine: host milliseconds before a decode wave is enqueued
(block allocation and copy-on-write, the uploads, the jit call), over
the window's rounds that had a lane decoding."""
from . import _round_phases

LAYER, SOURCE = "paged_engine", "program_counter"


def read(ctx):
    return _round_phases.ms_per_round(
        ctx, "wave.blocks", "wave.stage", "wave.dispatch", waves_only=True)
