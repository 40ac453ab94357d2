"""Paged attention core: the least time one decode wave's attention
could take (the K and V rows its lanes attend, once, over the chip's
memory bandwidth; or its operations over peak, whichever is longer)
over the kernel's device time in one decode wave. Memory binds: 1 to 4
operations per byte."""
from .. import flops, readers

LAYER, SOURCE = "paged_attention_core", "device_trace"


def read(ctx):
    tr, host = ctx["trace"], ctx["trace_host"]
    decode = readers.program(ctx, "decode")
    if not tr or not host or not decode:
        return None
    waves = tr["module_s"].get(decode, [])
    kernel = tr["kernel_by_module"].get(decode, {}).get("paged_attention")
    attended = [r[3] for r in readers.rounds_in(ctx, *host) if r[2]]
    if not waves or not kernel or not attended:
        return None
    ops, nbytes = flops.paged_decode_cost(
        ctx["shapes"], sum(attended) / len(attended))
    least, _ = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    return readers.percent(least, kernel / len(waves))
