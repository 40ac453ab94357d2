"""What the readers of the program's own counters share: a counter of
`ServingMetrics.snapshot()` over the window, from the snapshots the
serving kinds take at its two ends."""


def delta(ctx, key):
    """What the window added to counter `key`; None where the kind of
    cell took no snapshots or the program has no such counter."""
    obs = ctx["obs"]
    if "snap0" not in obs or key not in obs["snap1"]:
        return None
    return obs["snap1"][key] - obs["snap0"].get(key, 0)
