"""Paged engine, a model with several residual streams: token rows the
window's chunks and waves put through the streams' maps
(`mhc_rows_mixed`: staged tokens times the sub-layers each passes) over
the output tokens that reached their callers in the window. Pure
decoding reads 2 x layers (12 at depth 6); every prompt token prefilled
for a served one adds as much again. Nothing for a program without the
counter, and nothing where it stayed 0 (one stream: there are no
maps)."""
from .. import loadgen
from ._counters import delta

LAYER, SOURCE = "paged_engine", "program_counter"


def read(ctx):
    obs, rows = ctx["obs"], delta(ctx, "mhc_rows_mixed")
    if not rows or "records" not in obs:
        return None
    served = loadgen.tokens_in(obs["records"], *obs["window"])
    return rows / served if served else None
