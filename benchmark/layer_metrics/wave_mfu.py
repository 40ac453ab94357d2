"""Paged engine, the whole decode step: the least time the chip could
take for one decode wave of a dense decoder (`flops.decode_wave_cost` at
the traced rounds' mean lanes decoding and positions attended, through
`flops.roofline_seconds`) over the median device time of the decode-wave
program. The cell's share of the whole step's peak, beside the kernels'
rooflines: a change that takes a kernel off the path silences its
roofline and still has this to answer to. Memory binds (a wave reads
every weight and the attended K/V once for one token a lane); the name
says `mfu` all the same. A configuration with a block `pattern` has
`hybrid_wave_mfu`."""
from .. import flops, readers

LAYER, SOURCE = "paged_engine", "device_trace"


def read(ctx):
    tr, host = ctx["trace"], ctx["trace_host"]
    decode = readers.program(ctx, "decode")
    if not tr or not host or not decode or "pattern" in ctx["shapes"]:
        return None
    wave = readers.median(tr["module_s"].get(decode, []))
    waves = [r for r in readers.rounds_in(ctx, *host) if r[2]]
    if not wave or not waves:
        return None
    ops, nbytes = flops.decode_wave_cost(
        ctx["shapes"], sum(r[2] for r in waves) / len(waves),
        sum(r[3] for r in waves) / len(waves))
    least, _ = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    return readers.percent(least, wave)
