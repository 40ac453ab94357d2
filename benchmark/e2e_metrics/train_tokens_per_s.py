"""Tokens trained in the window over its seconds; the window closes on
the last step's loss arriving on the host."""


def read(ctx):
    obs = ctx["obs"]
    if obs["kind"] != "train" or not obs["steps"]:
        return None
    t0, t1 = obs["window"]
    return obs["tokens"] / (t1 - t0)
