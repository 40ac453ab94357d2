"""Latent attention core: the least time one decode wave's absorbed
attention could take (`flops_mla_moe.latent_decode_cost`: the latent rows
its lanes attend read once a layer, every head scoring and mixing each;
about 60 operations a byte, so either bound may bind) over the kernel's
device time in one decode wave. The kernel is the one `trace_reduce`
classes `paged_attention` (see `mla_decode_attn_device_share`)."""
from .. import flops, flops_mla_moe, readers

LAYER, SOURCE = "latent_attention_core", "device_trace"


def read(ctx):
    tr, host = ctx["trace"], ctx["trace_host"]
    decode = readers.program(ctx, "decode")
    if not tr or not host or not decode or \
            "latent_rank" not in ctx["shapes"]:
        return None
    waves = tr["module_s"].get(decode, [])
    kernel = tr["kernel_by_module"].get(decode, {}).get("paged_attention")
    attended = [r[3] for r in readers.rounds_in(ctx, *host) if r[2]]
    if not waves or not kernel or not attended:
        return None
    ops, nbytes = flops_mla_moe.latent_decode_cost(
        ctx["shapes"], sum(attended) / len(attended))
    least, _ = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    return readers.percent(least, kernel / len(waves))
