"""GiB occupied at the peak instant of the temporaries by buffers of phases
``bwd`` and ``remat``: gradients in flight, the backward's own work space,
recomputed activations (``perfbench/memory_reduce.py``)."""

from perfbench import memory_reduce


def read(ctx):
    return memory_reduce.metric(ctx, "hbm_peak_bwd_gib")
