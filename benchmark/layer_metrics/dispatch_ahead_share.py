"""Scheduler: of the decode waves the program put on the device's queue
in the window, the share that went out while the wave before was still
unread (`waves_dispatched_ahead` over `waves_dispatched`, the program's
own counters): the one-deep pipeline of a serving round was full. Near
100 where every round dispatches before it reads; 0 where each wave is
read before the next goes out (a dynamic token mask, the speculative
engine, a draining server). None for a program that does not count
them."""
from .. import readers
from . import _counters

LAYER, SOURCE = "scheduler", "program_counter"


def read(ctx):
    waves = _counters.delta(ctx, "waves_dispatched")
    ahead = _counters.delta(ctx, "waves_dispatched_ahead")
    if waves is None or ahead is None:
        return None
    return readers.percent(ahead, waves)
