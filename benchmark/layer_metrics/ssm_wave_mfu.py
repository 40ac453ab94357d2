"""Dense hybrid model step, the whole decode wave: the least time the
chip could take for one wave (`flops_granite.decode_wave_cost` at the
traced rounds' mean lanes decoding and positions attended, and the
engine's slots: the program reads and writes every slot's record)
through `flops.roofline_seconds`, over the median device time of the
decode-wave program. The cell's share of the whole step's peak; memory
binds (weights, tied head, state), and the name says `mfu` all the same.
Nothing for a configuration without a scan."""
from .. import flops, flops_granite, readers

LAYER, SOURCE = "ssm_dense_model_step", "device_trace"


def read(ctx):
    tr, host = ctx["trace"], ctx["trace_host"]
    decode = readers.program(ctx, "decode")
    if not tr or not host or not decode or \
            "scan_chunk" not in ctx["shapes"]:
        return None
    wave = readers.median(tr["module_s"].get(decode, []))
    waves = [r for r in readers.rounds_in(ctx, *host) if r[2]]
    if not wave or not waves:
        return None
    ops, nbytes = flops_granite.decode_wave_cost(
        ctx["shapes"], sum(r[2] for r in waves) / len(waves),
        sum(r[3] for r in waves) / len(waves),
        int(ctx["obs"]["engine"]["num_slots"]))
    least, _ = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    return readers.percent(least, wave)
