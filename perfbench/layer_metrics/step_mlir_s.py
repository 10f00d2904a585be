"""Seconds of lowering the step's jaxpr to StableHLO (the compile ledger's row
``role = step``, stage ``mlir``): Mosaic's lowering of each kernel instance
among it, the other half of ``lower_s`` (``perfbench/startup_reduce.py``)."""

from perfbench import startup_reduce


def read(ctx):
    return startup_reduce.metric(ctx, "step_mlir_s")
