"""Milliseconds per step in the leading dense layers' MLP of a model
whose other layers hold experts (``mlp/mlp_dense``: norm, the three
matmuls of SwiGLU, the residual add), every phase, on one device."""

from perfbench import mla_reduce


def read(ctx):
    return mla_reduce.part_ms(ctx, (mla_reduce.DENSE,))
