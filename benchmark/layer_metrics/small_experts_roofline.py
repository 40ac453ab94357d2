"""Grouped expert kernel, gated form, at many small experts: the least
time one decode wave's expert layers could take
(`flops_mla_moe.gated_expert_cost` for the traced rounds' mean lanes decoding:
three matrices of the touched experts read once, the picks' rows in and
out; once a sparse layer) over the kernel's device time in one decode
wave. Memory binds. The kernel fetches every expert whether a pick chose
it or not, so the share is at most the touched experts over all of them.
It is the one `trace_reduce` classes `pallas_other`: in a configuration
of full and window layers the only Pallas kernel beside paged
attention."""
from .. import flops, flops_laguna, flops_mla_moe, readers

LAYER, SOURCE = "moe_experts_kernel", "device_trace"


def read(ctx):
    tr, host, sh = ctx["trace"], ctx["trace_host"], ctx["shapes"]
    decode = readers.program(ctx, "decode")
    if not tr or not host or not decode or "layer_types" not in sh \
            or not sh.get("window"):
        return None
    waves = tr["module_s"].get(decode, [])
    kernel = tr["kernel_by_module"].get(decode, {}).get("pallas_other")
    lanes = [r[2] for r in readers.rounds_in(ctx, *host) if r[2]]
    if not waves or not kernel or not lanes:
        return None
    ops, nbytes = flops_mla_moe.gated_expert_cost(
        sh, sum(lanes) / len(lanes))
    least, _ = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    return readers.percent(flops_laguna.sparse_layers(sh) * least,
                           kernel / len(waves))
