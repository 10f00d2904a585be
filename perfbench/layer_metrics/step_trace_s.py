"""Seconds of Python tracing of the step (the compile ledger's row
``role = step``, stage ``trace``): the parts' and kernels' bodies, the Python
half of ``lower_s`` (``perfbench/startup_reduce.py``)."""

from perfbench import startup_reduce


def read(ctx):
    return startup_reduce.metric(ctx, "step_trace_s")
