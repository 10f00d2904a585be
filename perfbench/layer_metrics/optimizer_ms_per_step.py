"""Milliseconds per step of the update on one device: ops under the scopes
``optimizer``, ``grad_reduce_scatter``, ``param_all_gather`` and
``step_guard`` that hold no matmul of another phase.  None on a program
without scopes."""

from perfbench import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "optimizer", needs_scopes=True)
