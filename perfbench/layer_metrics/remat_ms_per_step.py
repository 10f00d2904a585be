"""Milliseconds per step of recomputation on one device: ops under
``rematted_computation`` (``jax.checkpoint``) and instructions the
compiler rematerialises itself (``*.remat``).  Model FLOPs never count
them, so each of these milliseconds lowers ``mfu_pct``."""

from perfbench import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "remat")
