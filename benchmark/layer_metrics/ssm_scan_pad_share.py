"""Paged engine, prefill of a model that scans: the share of the chunk
program's rows that carried no prompt token, 1 - `prefill_tokens` /
(`prefill_chunks` x the engine's chunk). The scan, the projections and
the MLPs run over every row of a chunk, padded or not; only a prompt's
last chunk is padded. Nothing where the program counts no chunks."""
from .. import readers
from ._counters import delta

LAYER, SOURCE = "paged_engine", "program_counter"


def read(ctx):
    chunks, tokens = delta(ctx, "prefill_chunks"), delta(ctx,
                                                         "prefill_tokens")
    if not chunks or delta(ctx, "ssm_records_stepped") is None:
        return None
    rows = chunks * int(ctx["obs"]["engine"]["chunk"])
    return readers.percent(rows - tokens, rows)
