"""Flash attention: device time of the three kernels (forward, dq,
dk/dv) over all device busy time in the traced window."""
from .. import readers

LAYER, SOURCE = "flash_attention", "device_trace"

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def kernel_seconds(tr):
    return sum(tr["kernel_s"].get(k, 0.0) for k in KERNELS)


def read(ctx):
    tr = ctx["trace"]
    if not tr or not kernel_seconds(tr):
        return None
    return readers.percent(kernel_seconds(tr), tr["busy_s"])
