"""Grouped expert kernel (`moe_experts`, the one Pallas kernel that is
neither paged attention nor flash): its device time over all device busy
time in the traced window."""
from .. import readers

LAYER, SOURCE = "moe_experts_kernel", "device_trace"


def read(ctx):
    tr = ctx["trace"]
    if not tr or "pallas_other" not in tr["kernel_s"]:
        return None
    return readers.percent(tr["kernel_s"]["pallas_other"], tr["busy_s"])
