"""Window-and-full model step with small experts, the whole prefill
chunk: the least time the chip could take for the window's mean chunk
(`flops_laguna.prefill_chunk_cost` at the program's own counts: prompt
tokens a chunk and the keys they attend in a full layer,
`prefill_tokens` and `prefill_keys` over `prefill_chunks`) over the
median device time of the prefill-chunk program. The chunk's share of the
whole step's peak. Nothing where the program counts no such keys or the
configuration has no layers of both kinds."""
from .. import flops, flops_laguna, readers
from ._counters import delta

LAYER, SOURCE = "window_moe_model_step", "device_trace"


def read(ctx):
    tr, sh = ctx["trace"], ctx["shapes"]
    prefill = readers.program(ctx, "prefill")
    if not tr or not prefill or "layer_types" not in sh \
            or not sh.get("window"):
        return None
    chunk = readers.median(tr["module_s"].get(prefill, []))
    chunks, keys = (delta(ctx, k) for k in ("prefill_chunks",
                                            "prefill_keys"))
    if not chunk or not chunks or not keys:
        return None
    ops, nbytes = flops_laguna.prefill_chunk_cost(
        sh, delta(ctx, "prefill_tokens") / chunks, keys / chunks)
    least, _ = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    return readers.percent(least, chunk)
