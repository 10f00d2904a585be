"""Milliseconds per step of forward device time on one device: ops whose
``op_name`` is under ``jvp(`` or a model scope and under no other phase's
mark, the forward kernel included (``perfbench/scope_reduce.py``)."""

from perfbench import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "fwd")
