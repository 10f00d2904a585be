"""Seconds inside the program's Python before anything is traced: the phase
spans ``init``, ``build_mesh`` and ``make_train_step``, as a union
(``perfbench/startup_reduce.py``)."""

from perfbench import startup_reduce


def read(ctx):
    return startup_reduce.metric(ctx, "hvd_init_s")
