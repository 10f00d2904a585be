"""Milliseconds per step in the three attention kernels under the mask
(``dsa_fwd``, ``dsa_bwd_dq``, ``dsa_bwd_dkv``), the recomputed forward
included, on one device: the denominator of ``dsa_flash_roofline``."""

from perfbench import dsa_reduce
from perfbench.layer_metrics import kernel_seconds


def read(ctx):
    seconds = kernel_seconds(ctx, "dsa_flash")
    if not seconds or dsa_reduce.for_ctx(ctx) is None:
        return None
    return seconds * 1e3 / ctx["trace_steps"]
