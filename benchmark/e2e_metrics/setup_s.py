"""Process start to window start: imports, model, weights, compilation
or cache loads, warm-up, pre-roll."""


def read(ctx):
    return ctx["setup_s"]
