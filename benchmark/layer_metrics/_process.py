"""What the readers of the process's own journal share
(`paddle_tpu.utils.telemetry`: start-up spans, compile stages by program,
the collector's pauses, each `(t_end, kind, label, seconds)` on the
benchmark's clock, seconds being self time). A reader runs in the
program's process after the window, so it asks the program directly and
cuts at the window's start: what the referee compiles afterwards is left
out. A program from before the journal has none: every reader then
returns None.
"""
from .. import readers
from . import _round_phases


def _telemetry(name):
    from paddle_tpu.utils import telemetry
    return getattr(telemetry, name, None)


def setup_seconds(ctx, kind, *labels):
    """Self seconds of `kind` (of `labels` within it, if given) journaled
    before the window opened; None where nothing at all was journaled by
    then or the program keeps no journal."""
    summary = _telemetry("process_summary")
    if summary is None:
        return None
    summary = summary(until=readers.window(ctx)[0])
    if not summary["entries"]:
        return None
    by = summary["kinds"].get(kind, {"seconds": 0.0, "labels": {}})
    if not labels:
        return by["seconds"]
    return sum(by["labels"][name]["seconds"] for name in labels
               if name in by["labels"])


def setup_count(ctx, kind):
    """How many entries of `kind` were journaled before the window."""
    summary = _telemetry("process_summary")
    if summary is None:
        return None
    kinds = summary(until=readers.window(ctx)[0])["kinds"]
    return kinds.get(kind, {"count": 0})["count"]


def gc_pauses(ctx):
    """Seconds of each pause of the collector journaled inside the window
    (those of a millisecond or more), as two lists: the program's, and
    those that ended where the benchmark starts or stops its own profiler
    between two rounds (`stop_trace` alone allocates for seconds). None
    where the program keeps no journal."""
    events = _telemetry("process_events")
    if events is None:
        return None
    gaps = _round_phases.profiler_gap_intervals(ctx)
    own, profilers = [], []
    for t, kind, _, seconds in events(*readers.window(ctx)):
        if kind == "gc":
            (profilers if any(a <= t <= b for a, b in gaps)
             else own).append(seconds)
    return own, profilers
