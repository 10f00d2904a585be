"""GiB occupied at the peak instant of the temporaries by buffers of phases
``grad_mean`` and ``optimizer``: packed and reduced gradients, the update's
work space (``perfbench/memory_reduce.py``)."""

from perfbench import memory_reduce


def read(ctx):
    return memory_reduce.metric(ctx, "hbm_peak_update_gib")
