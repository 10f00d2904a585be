"""Programs that reached the backend during set-up, compiled or read from the
persistent cache: the compile ledger's ``backend_compile`` rows
(``perfbench/startup_reduce.py``)."""

from perfbench import startup_reduce


def read(ctx):
    return startup_reduce.metric(ctx, "programs_built")
