"""Paged engine: host milliseconds a round that slot state adds (the
`state.*` phases: `state.reset`, the zeroing of a slot's recurrent
record when the slot begins a prompt), over the window's rounds with
work. None for a program that keeps no slot state."""
from . import _round_phases

LAYER, SOURCE = "paged_engine", "program_counter"


def read(ctx):
    obs = ctx["obs"]
    if "snap0" not in obs:
        return None
    phases = [p for p in obs["snap1"]["phase_seconds"]
              if p.startswith("state.")]
    return _round_phases.ms_per_round(ctx, *phases) if phases else None
