"""Grouped expert kernel: the least time one decode wave's expert
layers could take (`flops_hybrid.expert_mlp_cost` for the traced rounds'
mean lanes decoding, once a layer that has experts) over the kernel's
device time in one decode wave. Memory binds: a wave reads every expert
some lane chose for six rows each."""
from .. import flops, flops_hybrid, readers

LAYER, SOURCE = "moe_experts_kernel", "device_trace"


def read(ctx):
    tr, host = ctx["trace"], ctx["trace_host"]
    decode = readers.program(ctx, "decode")
    if not tr or not host or not decode or "pattern" not in ctx["shapes"]:
        return None
    waves = tr["module_s"].get(decode, [])
    kernel = tr["kernel_by_module"].get(decode, {}).get("pallas_other")
    lanes = [r[2] for r in readers.rounds_in(ctx, *host) if r[2]]
    if not waves or not kernel or not lanes:
        return None
    ops, nbytes = flops_hybrid.expert_mlp_cost(
        ctx["shapes"], sum(lanes) / len(lanes))
    least, _ = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    return readers.percent(ctx["shapes"]["pattern"].count("E") * least,
                           kernel / len(waves))
