"""GiB of the step's argument allocations on one chip: parameters, optimizer
slots and the batch, from the compiled step's buffer assignment
(``perfbench/memory_reduce.py``)."""

from perfbench import memory_reduce


def read(ctx):
    return memory_reduce.metric(ctx, "hbm_state_gib")
