"""Service: output tokens delivered in the window over its seconds.
Below the knee this is the offered rate, so it decides nothing there."""
from ..e2e_metrics import serve_tokens_per_s

LAYER, SOURCE = "service", "host_clock"


def read(ctx):
    return serve_tokens_per_s.read(ctx)
