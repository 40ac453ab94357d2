"""Flash attention: the least time the chip could take for one step's
attention (7 matmuls over the causal band at peak, or its tensors once
over the memory bandwidth, whichever is longer; per chip) over the
three kernels' device time in one step."""
from .. import flops, readers
from .flash_attn_device_share import kernel_seconds

LAYER, SOURCE = "flash_attention", "device_trace"


def read(ctx):
    tr = ctx["trace"]
    steps = tr and tr["module_s"].get(readers.program(ctx, "step"), [])
    if not steps or not kernel_seconds(tr):
        return None
    ops, nbytes = flops.flash_train_cost(
        ctx["shapes"], ctx["obs"]["batch"], ctx["obs"]["seq"])
    least, _ = flops.roofline_seconds(ops / ctx["chips"],
                                      nbytes / ctx["chips"], ctx["peaks"])
    return readers.percent(least, kernel_seconds(tr) / len(steps))
