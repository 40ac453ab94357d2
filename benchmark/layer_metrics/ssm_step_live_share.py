"""Paged engine, decode waves of a model with slot state: lanes that
decoded (`ssm_lanes_stepped`) over the slots' records the waves read and
wrote (`ssm_records_stepped`: every slot's, each wave). What is missing
from 100% is state traffic for lanes that were free or prefilling.
Nothing for a program that counts no records."""
from .. import readers
from ._counters import delta

LAYER, SOURCE = "paged_engine", "program_counter"


def read(ctx):
    records = delta(ctx, "ssm_records_stepped")
    return readers.percent(delta(ctx, "ssm_lanes_stepped"),
                           records) if records else None
