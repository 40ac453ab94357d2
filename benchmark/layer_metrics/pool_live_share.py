"""Block pool: blocks referenced by a request, as the pool counts them
after each round, over the blocks the deployment reserved (slots x
max_len / block size); the median over the window's rounds. What the
runtime reports as memory is the reservation; this is the part of it
that holds a live position."""
from .. import readers

LAYER, SOURCE = "block_pool", "program_counter"


def read(ctx):
    eng = ctx["obs"].get("engine")
    used = [r[5] for r in readers.rounds_in(ctx, *readers.window(ctx))
            if len(r) > 5 and r[5] is not None]
    if not eng or not used:
        return None
    reserved = (int(eng["num_slots"]) * int(eng["max_len"])
                // int(eng["block_size"]))
    return readers.percent(readers.median(used), reserved)
