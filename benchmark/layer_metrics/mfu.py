"""Train step: operations the forward and backward passes need per
token (6 per matmul weight plus causal attention, nothing recomputed)
times tokens per second, over chips times the published bf16 peak. The
rate is tokens a step over the median time from one step's loss to the
next, so that the pause a traced run makes for its profile does not
count."""
import statistics

from .. import flops

LAYER, SOURCE = "train_step", "host_clock"


def read(ctx):
    obs = ctx["obs"]
    done = obs.get("step_done_t", [])
    if obs.get("kind") != "train" or len(done) < 3:
        return None
    rate = obs["tokens_per_step"] / statistics.median(
        b - a for a, b in zip(done, done[1:]))
    per_token = flops.train_flops_per_token(ctx["shapes"], obs["seq"])
    return 100.0 * flops.mfu(per_token, rate, ctx["chips"],
                             ctx["peaks"]["bf16_flops_per_s"])
