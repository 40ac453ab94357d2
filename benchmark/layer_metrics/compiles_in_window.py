"""Device: backend compilations (or loads from the compile cache)
inside the measured window. Must be 0, else `correct` is false."""
LAYER, SOURCE = "device", "program_counter"


def read(ctx):
    return ctx["compiles_in_window"]
