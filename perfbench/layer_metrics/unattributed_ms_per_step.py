"""Milliseconds per step of device ops that no rule of
``perfbench/scope_reduce.py`` places: the tracing's own health.  A refactor
that drops a scope shows here.  None on a program without scopes."""

from perfbench import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "unattributed", needs_scopes=True)
