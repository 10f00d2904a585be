"""The masked attention kernels' share of their roofline: the least time
the chip could take for attention over the **selected** keys, forward and
backward (``perfbench.kernel_cost_dsa.sparse_attention_train``, peaks from
``peaks.json``), over the time the three kernels took.  A kernel that
computes a whole tile to keep the selected part of it gets that part of
the share."""

from perfbench import dsa_reduce


def read(ctx):
    return dsa_reduce.kernel_roofline(ctx, "dsa_flash", "dsa_flash_roofline")
