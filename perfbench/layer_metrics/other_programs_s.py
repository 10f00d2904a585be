"""Seconds of set-up inside the trace, lowering and compile (or cache read) of
every program but the step: the state's ``make``, the reference check, JAX's
helper jits; a union, outside the step's own rows
(``perfbench/startup_reduce.py``)."""

from perfbench import startup_reduce


def read(ctx):
    return startup_reduce.metric(ctx, "other_programs_s")
