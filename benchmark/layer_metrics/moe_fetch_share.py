"""Grouped expert kernel: the experts its calls in the window's decode
waves fetched (`moe_experts_fetched`: counted on the device, summed over
the sparse layers, read with each wave's tokens) over the experts those
layers store, once a wave (`moe_experts_stored`): the share of the
stored expert matrices a wave reads. 100 where every wave touches every
expert; under independent routing of `lanes` lanes at top-k of E, about
1 - (1 - k/E)^lanes, the touched share the roofline readers price.
Nothing for a program without the counters."""
from .. import readers
from ._counters import delta

LAYER, SOURCE = "moe_experts_kernel", "program_counter"


def read(ctx):
    fetched, stored = (delta(ctx, k) for k in ("moe_experts_fetched",
                                               "moe_experts_stored"))
    return None if fetched is None else readers.percent(fetched, stored)
