"""Gap between one request's consecutive tokens, 99th percentile by
nearest rank over every gap that closed in the window."""
from .. import loadgen


def read(ctx):
    obs = ctx["obs"]
    if "records" not in obs:
        return None
    t0, t1 = obs["window"]
    p = loadgen.percentile(loadgen.token_gaps(obs["records"], t0, t1), 99)
    return None if p is None else 1e3 * p
