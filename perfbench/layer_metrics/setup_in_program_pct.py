"""Share of set-up (the harness's stages but ``tpu_start_s``) that lies inside
the program's phase spans or JAX's trace, lowering and compile rows, as a
union; the remainder is execution on the device and the harness's own Python
(``perfbench/startup_reduce.py``)."""

from perfbench import startup_reduce


def read(ctx):
    return startup_reduce.metric(ctx, "setup_in_program_pct")
