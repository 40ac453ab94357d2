"""Latent attention core (`paged_latent_attention`, the absorbed kernel
of the decode wave): its device time over all device busy time in the
traced window. `trace_reduce.kernel_class` classes it `paged_attention`
(an s32 block table comes first); in a configuration with a latent cache
it is the only kernel of that class, which is how this reader knows it."""
from .. import readers

LAYER, SOURCE = "latent_attention_core", "device_trace"


def read(ctx):
    tr = ctx["trace"]
    if not tr or "latent_rank" not in ctx["shapes"] or \
            "paged_attention" not in tr["kernel_s"]:
        return None
    return readers.percent(tr["kernel_s"]["paged_attention"], tr["busy_s"])
