"""Paged attention core: the kernel's device time over all device busy
time in the traced window."""
from .. import readers

LAYER, SOURCE = "paged_attention_core", "device_trace"


def read(ctx):
    tr = ctx["trace"]
    if not tr or "paged_attention" not in tr["kernel_s"]:
        return None
    return readers.percent(tr["kernel_s"]["paged_attention"],
                           tr["busy_s"])
