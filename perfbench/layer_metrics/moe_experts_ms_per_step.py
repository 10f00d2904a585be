"""Milliseconds per step under ``mlp/moe_experts``: the three grouped
matmuls of every expert forward and backward (the kernels ``moe_gmm``,
``moe_gmm_nt``, ``moe_tgmm``), ``silu *`` and the casts of the expert
weights, on one device."""

from perfbench import moe_reduce


def read(ctx):
    return moe_reduce.part_ms(ctx, ("moe_experts",))
