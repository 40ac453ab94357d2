"""Process: seconds of set-up building the engine or the train step,
outside what they compile: `startup/engine` with the pool's allocation
(`startup/pool`) in a serving cell, `startup/step_build` with the
optimizer's moments (`startup/opt_state`) and the placement on the mesh
(`startup/shard`) in a training cell. Self times, so the sum counts
nothing twice."""
from . import _process

LAYER, SOURCE = "process", "program_counter"


def read(ctx):
    return _process.setup_seconds(ctx, "startup", "engine", "pool",
                                  "step_build", "opt_state", "shard")
