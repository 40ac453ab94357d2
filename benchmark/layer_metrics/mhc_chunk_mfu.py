"""Hyper-connected latent-attention model step, the whole prefill chunk:
the least time the chip could take for the window's mean chunk
(`flops_xing4.prefill_chunk_cost` at the program's own counts: prompt
tokens a chunk and latent rows a chunk expands, `prefill_tokens` and
`mla_rows_expanded` over `prefill_chunks`) over the median device time of
the prefill-chunk program. The chunk's share of the whole step's peak.
Nothing where the program counts no such chunks or the configuration has
no residual streams to mix (`hc_streams`)."""
from .. import flops, flops_xing4, readers
from ._counters import delta

LAYER, SOURCE = "hyper_connected_latent_moe_step", "device_trace"


def read(ctx):
    tr, sh = ctx["trace"], ctx["shapes"]
    prefill = readers.program(ctx, "prefill")
    if not tr or not prefill or "hc_streams" not in sh \
            or "latent_rank" not in sh:
        return None
    chunk = readers.median(tr["module_s"].get(prefill, []))
    chunks, rows = (delta(ctx, k) for k in ("prefill_chunks",
                                            "mla_rows_expanded"))
    if not chunk or not chunks or rows is None:
        return None
    ops, nbytes = flops_xing4.prefill_chunk_cost(
        sh, delta(ctx, "prefill_tokens") / chunks, rows / chunks)
    least, _ = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    return readers.percent(least, chunk)
