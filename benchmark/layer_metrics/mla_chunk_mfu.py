"""Latent-attention model step, the whole prefill chunk: the least time
the chip could take for the window's mean chunk
(`flops_mla_moe.prefill_chunk_cost` at the program's own counts: prompt
tokens a chunk and latent rows a chunk expands, `prefill_tokens` and
`mla_rows_expanded` over `prefill_chunks`) over the median device time of
the prefill-chunk program. The chunk's share of the whole step's peak.
Nothing where the program counts no such chunks."""
from .. import flops, flops_mla_moe, readers

LAYER, SOURCE = "latent_moe_model_step", "device_trace"


def _delta(obs, key):
    return obs["snap1"].get(key, 0) - obs["snap0"].get(key, 0)


def read(ctx):
    tr, obs = ctx["trace"], ctx["obs"]
    prefill = readers.program(ctx, "prefill")
    if not tr or not prefill or "snap0" not in obs or \
            "latent_rank" not in ctx["shapes"]:
        return None
    chunk = readers.median(tr["module_s"].get(prefill, []))
    chunks = _delta(obs, "prefill_chunks")
    if not chunk or not chunks:
        return None
    ops, nbytes = flops_mla_moe.prefill_chunk_cost(
        ctx["shapes"], _delta(obs, "prefill_tokens") / chunks,
        _delta(obs, "mla_rows_expanded") / chunks)
    least, _ = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    return readers.percent(least, chunk)
