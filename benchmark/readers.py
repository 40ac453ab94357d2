"""Small helpers the metric readers share. A reader is a module with
one `read(ctx)` that returns a number, or None when what it reads is
not there; `ctx` is the dict `run.py` builds:

  entry, cell, config, shapes, chips, peaks   the cell and its sizes
  obs        what the kind of cell observed on the host clock
  trace      `trace_reduce.summarize`'s dict (traced runs only)
  trace_host (start, end) of the traced window on the host clock
  e2e        {name: value} of the cell's end-to-end metrics
  setup_s, compiles_in_window
"""
import statistics
import time

from . import loadgen

# the program stamps requests with time.monotonic(); the benchmark's
# clock is time.perf_counter(). Same clock on Linux; measured, not assumed.
PERF_MINUS_MONOTONIC = time.perf_counter() - time.monotonic()


def window(ctx):
    return ctx["obs"]["window"]


def records(ctx):
    return ctx["obs"].get("records")


def rounds_in(ctx, t0, t1):
    return [r for r in ctx["obs"].get("rounds", ())
            if r[0] >= t0 and r[1] <= t1]


def ttft_waits(ctx):
    """Times to first token of an open-loop cell's sample, or None."""
    obs = ctx["obs"]
    if obs.get("kind") != "serve_open":
        return None
    return loadgen.ttft_sample(obs["records"], *obs["window"],
                               obs["ttft_tail_s"])[0]


def median(xs):
    return statistics.median(xs) if xs else None


def phase_delta(ctx, *phases):
    a, b = ctx["obs"]["snap0"]["phase_seconds"], \
        ctx["obs"]["snap1"]["phase_seconds"]
    return sum(b.get(p, 0.0) - a.get(p, 0.0) for p in phases)


def program(ctx, role):
    """Name of the jitted program the cell's file gives for `role`."""
    return ctx["cell"].get("programs", {}).get(role)


def percent(part, whole):
    return None if not whole else 100.0 * part / whole
