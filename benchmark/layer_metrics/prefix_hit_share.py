"""Block pool: full prompt blocks served from the prefix cache over
all full prompt blocks admitted in the window (the pool's counters)."""
from .. import readers

LAYER, SOURCE = "block_pool", "program_counter"


def read(ctx):
    obs = ctx["obs"]
    if "snap0" not in obs:
        return None
    hits = obs["snap1"]["prefix_hits"] - obs["snap0"]["prefix_hits"]
    miss = obs["snap1"]["prefix_misses"] - obs["snap0"]["prefix_misses"]
    return readers.percent(hits, hits + miss)
