"""``hbm_temp_gib`` less the three phases' bytes at the peak instant:
fragmentation plus whatever no rule of ``perfbench/scope_reduce.py`` places.
The memory tracing's own health, as ``unattributed_ms_per_step`` is
time's (``perfbench/memory_reduce.py``)."""

from perfbench import memory_reduce


def read(ctx):
    return memory_reduce.metric(ctx, "hbm_peak_unplaced_gib")
