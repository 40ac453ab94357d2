"""Paged attention core in a stack of full and window layers: the least
time the attended K and V rows of one decode wave could take over the
chip's memory bandwidth (or the operations over peak, whichever is
longer), both kinds of layer counted (`flops_laguna.paged_attention_cost`:
a full layer's lanes attend every cached position, a window layer's at
most the window), over the kernel's device time in one decode wave.
Nothing for a configuration without layers of both kinds."""
from .. import flops, flops_laguna, readers

LAYER, SOURCE = "paged_attention_core", "device_trace"


def read(ctx):
    tr, host, sh = ctx["trace"], ctx["trace_host"], ctx["shapes"]
    decode = readers.program(ctx, "decode")
    if not tr or not host or not decode or "layer_types" not in sh \
            or not sh.get("window"):
        return None
    waves = tr["module_s"].get(decode, [])
    kernel = tr["kernel_by_module"].get(decode, {}).get("paged_attention")
    rounds = [r for r in readers.rounds_in(ctx, *host) if r[2]]
    if not waves or not kernel or not rounds:
        return None
    lanes = sum(r[2] for r in rounds) / len(rounds)
    attended = sum(r[3] for r in rounds) / len(rounds)
    ops, nbytes = flops_laguna.paged_attention_cost(
        sh, attended, flops_laguna.window_rows(sh, lanes, attended))
    least, _ = flops.roofline_seconds(ops, nbytes, ctx["peaks"])
    return readers.percent(least, kernel / len(waves))
