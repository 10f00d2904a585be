"""GiB of the step's HBM temporary allocations (what
``memory_analysis().temp_size_in_bytes`` counts, less the compiler's own
reserve), from the compiled step's buffer assignment
(``perfbench/memory_reduce.py``)."""

from perfbench import memory_reduce


def read(ctx):
    return memory_reduce.metric(ctx, "hbm_temp_gib")
