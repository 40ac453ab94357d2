"""Hyper-connected latent-attention model step, the whole decode wave:
the least time the chip could take for one wave
(`flops_xing4.decode_wave_cost` at the traced rounds' mean lanes decoding
and positions attended, through `flops.roofline_seconds`) over the median
device time of the decode-wave program. The cell's share of the whole
step's peak; memory binds (the touched experts, the head, the attended
latent rows, every sub-layer's Phi and the streams), and the name says
`mfu` all the same. Nothing for a configuration without residual streams
to mix (`hc_streams`) on a latent cache."""
from .. import flops, flops_xing4, readers

LAYER, SOURCE = "hyper_connected_latent_moe_step", "device_trace"


def read(ctx):
    tr, host, sh = ctx["trace"], ctx["trace_host"], ctx["shapes"]
    decode = readers.program(ctx, "decode")
    if not tr or not host or not decode or "hc_streams" not in sh \
            or "latent_rank" not in sh:
        return None
    wave = readers.median(tr["module_s"].get(decode, []))
    waves = [r for r in readers.rounds_in(ctx, *host) if r[2]]
    if not wave or not waves:
        return None
    ops, nbytes = flops_xing4.decode_wave_cost(
        sh, sum(r[2] for r in waves) / len(waves),
        sum(r[3] for r in waves) / len(waves))
    least, _ = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    return readers.percent(least, wave)
