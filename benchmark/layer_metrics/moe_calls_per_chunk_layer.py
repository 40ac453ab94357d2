"""Grouped expert kernel: the calls the window's prefill chunks' sparse
layers made of it (`moe_chunk_calls`, by the rule the routed layer cuts
its picks by: one where the kernel's VMEM plan holds a chunk's picks,
else a segment as large as fits at a time) over those layers, a chunk's
each time (`moe_chunk_layers`): 1.00 where every sparse layer takes a
chunk's picks in one call. Nothing for a program without the counters."""
from ._counters import delta

LAYER, SOURCE = "moe_experts_kernel", "program_counter"


def read(ctx):
    calls, layers = (delta(ctx, k) for k in ("moe_chunk_calls",
                                             "moe_chunk_layers"))
    return None if not layers else calls / layers
