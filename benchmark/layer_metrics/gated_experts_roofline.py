"""Grouped expert kernel, gated form: the least time one decode wave's
expert layers could take (`flops_mla_moe.gated_expert_cost` for the
traced rounds' mean lanes decoding: three matrices of the touched experts
read once, the picks' rows in and out; once a layer that has experts)
over the kernel's device time in one decode wave. Memory binds. The
kernel is the one `trace_reduce` classes `pallas_other`: in a
configuration with a latent cache the only Pallas kernel beside the
latent core."""
from .. import flops, flops_mla_moe, readers

LAYER, SOURCE = "moe_experts_kernel", "device_trace"


def read(ctx):
    tr, host, sh = ctx["trace"], ctx["trace_host"], ctx["shapes"]
    decode = readers.program(ctx, "decode")
    if not tr or not host or not decode or "latent_rank" not in sh:
        return None
    waves = tr["module_s"].get(decode, [])
    kernel = tr["kernel_by_module"].get(decode, {}).get("pallas_other")
    lanes = [r[2] for r in readers.rounds_in(ctx, *host) if r[2]]
    if not waves or not kernel or not lanes:
        return None
    ops, nbytes = flops_mla_moe.gated_expert_cost(
        sh, sum(lanes) / len(lanes))
    least, _ = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    return readers.percent((sh["layers"] - sh["dense_layers"]) * least,
                           kernel / len(waves))
