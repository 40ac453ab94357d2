"""Paged attention core in a hybrid stack: `paged_attn_roofline` with the
attention counted once for every `*` of the configuration's pattern
(`flops_hybrid.paged_attention_cost`) and not for every layer: the least
time the attended K and V rows of one decode wave could take over the
chip's memory bandwidth (or the operations over peak, whichever is
longer), over the kernel's device time in one decode wave."""
from .. import flops, flops_hybrid, readers

LAYER, SOURCE = "paged_attention_core", "device_trace"


def read(ctx):
    tr, host = ctx["trace"], ctx["trace_host"]
    decode = readers.program(ctx, "decode")
    if not tr or not host or not decode or "pattern" not in ctx["shapes"]:
        return None
    waves = tr["module_s"].get(decode, [])
    kernel = tr["kernel_by_module"].get(decode, {}).get("paged_attention")
    attended = [r[3] for r in readers.rounds_in(ctx, *host) if r[2]]
    if not waves or not kernel or not attended:
        return None
    ops, nbytes = flops_hybrid.paged_attention_cost(
        ctx["shapes"], sum(attended) / len(attended))
    least, _ = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    return readers.percent(least, kernel / len(waves))
