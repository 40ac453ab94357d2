"""Hybrid model step: the least time the chip could take for one decode
wave of the configuration's block mix (`flops_hybrid.decode_wave_cost` at
the traced rounds' mean lanes decoding and positions attended, through
`flops.roofline_seconds`) over the median device time of the decode-wave
program. The cell's share of the whole step's peak; memory binds (a wave
reads every expert, the Mamba state and the head once for one token a
lane), and the name says `mfu` all the same."""
from .. import flops, flops_hybrid, readers

LAYER, SOURCE = "hybrid_model_step", "device_trace"


def read(ctx):
    tr, host = ctx["trace"], ctx["trace_host"]
    decode = readers.program(ctx, "decode")
    if not tr or not host or not decode or "pattern" not in ctx["shapes"]:
        return None
    wave = readers.median(tr["module_s"].get(decode, []))
    waves = [r for r in readers.rounds_in(ctx, *host) if r[2]]
    if not wave or not waves:
        return None
    ops, nbytes = flops_hybrid.decode_wave_cost(
        ctx["shapes"], sum(r[2] for r in waves) / len(waves),
        sum(r[3] for r in waves) / len(waves))
    least, _ = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    return readers.percent(least, wave)
