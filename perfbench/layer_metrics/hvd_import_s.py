"""Seconds of ``import horovod_tpu`` top to bottom, the program's phase span
``import``; the harness's ``import_s`` around it also holds ``jax`` and the
harness's own modules (``perfbench/startup_reduce.py``)."""

from perfbench import startup_reduce


def read(ctx):
    return startup_reduce.metric(ctx, "hvd_import_s")
