"""Output tokens that reached their callers in the window over its
seconds (the window opens and closes on a scheduler round's end)."""
from .. import loadgen


def read(ctx):
    obs = ctx["obs"]
    if "records" not in obs:
        return None
    t0, t1 = obs["window"]
    return loadgen.tokens_in(obs["records"], t0, t1) / (t1 - t0)
