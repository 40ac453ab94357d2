"""Block pool, the window pool: host milliseconds a round spent giving
back the window pages behind the window (phase `window.release`, inside
the wave and chunk stages), over the window's rounds with work. None for
a program without a window pool."""
from . import _round_phases

LAYER, SOURCE = "block_pool", "program_counter"


def read(ctx):
    obs = ctx["obs"]
    if "snap0" not in obs or \
            "window.release" not in obs["snap1"]["phase_seconds"]:
        return None
    return _round_phases.ms_per_round(ctx, "window.release")
