"""Kind `serve_closed_routed_shared`: `serve_closed_routed` as it stands
(closed loop, the routed referee's two limits), for traffic in which
some requests begin with a shared prefix and others bring a prompt
nobody else has: the referee's sample holds one of each.

Why the plain sample does not do. `_serving.sampled` takes the longest
request and `check.requests` - 1 drawn from the seed; with three in four
requests sharing, a sample of three holds no stranger in four runs of
ten. A request that hits a resident prefix starts its prefill behind it
(one chunk over pages another request wrote) and a stranger runs every
chunk of its prompt: the comparison has to see both in every run.

So for this one call `_serving.sampled` is a wrapper: the plain order
(the longest, then the seed's permutation), its first `check.requests`,
and if they are all of one kind the last gives way to the first request
of the other kind in that order. One process runs one cell, and no file
the benchmark had is edited (`serve_closed_routed.run` does the same
with `_serving.referee`).
"""
import types

from . import _serving, serve_closed_routed


def both_kinds(order, n):
    """The first `n` of `order`; if none of them (or every one) began
    with a shared prefix, the last is replaced by the first of the other
    kind, where there is one (a sample of one keeps its one)."""
    picks = order[:n]
    kinds = {r.planned.prefix_id is None for r in picks}
    if len(picks) > 1 and len(kinds) == 1:
        other = [r for r in order[n:]
                 if (r.planned.prefix_id is None) not in kinds]
        if other:
            picks = picks[:-1] + other[:1]
    return picks


def run(ctx):
    plain = _serving.sampled

    def sampled(ctx, records):
        # `check.requests` lifted to every record: the plain sample then
        # returns its whole order
        check = {**ctx.cell["check"], "requests": len(records)}
        whole = types.SimpleNamespace(seed=ctx.seed,
                                      cell={**ctx.cell, "check": check})
        return both_kinds(plain(whole, records),
                          int(ctx.cell["check"]["requests"]))

    _serving.sampled = sampled
    try:
        return serve_closed_routed.run(ctx)
    finally:
        _serving.sampled = plain
