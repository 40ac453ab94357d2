"""Latent-attention model step, the whole decode wave: the least time
the chip could take for one wave (`flops_mla_moe.decode_wave_cost` at the
traced rounds' mean lanes decoding and positions attended, through
`flops.roofline_seconds`) over the median device time of the decode-wave
program. The cell's share of the whole step's peak, so that a change
which takes a kernel off the path still has a share to answer to; memory
binds (a wave reads the touched experts, the head and the attended latent
rows once), and the name says `mfu` all the same. Nothing for a
configuration without a latent cache."""
from .. import flops, flops_mla_moe, readers

LAYER, SOURCE = "latent_moe_model_step", "device_trace"


def read(ctx):
    tr, host = ctx["trace"], ctx["trace_host"]
    decode = readers.program(ctx, "decode")
    if not tr or not host or not decode or \
            "latent_rank" not in ctx["shapes"]:
        return None
    wave = readers.median(tr["module_s"].get(decode, []))
    waves = [r for r in readers.rounds_in(ctx, *host) if r[2]]
    if not wave or not waves:
        return None
    ops, nbytes = flops_mla_moe.decode_wave_cost(
        ctx["shapes"], sum(r[2] for r in waves) / len(waves),
        sum(r[3] for r in waves) / len(waves))
    least, _ = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    return readers.percent(least, wave)
