"""The flash kernels' share of their roofline: the least time the chip
could take for the causal attention the step needs (FLOPs and bytes from
``perfbench.kernel_cost``, peaks from ``peaks.json``) over the time the
kernels took."""

from perfbench import kernel_cost
from perfbench.layer_metrics import kernel_seconds
from perfbench.peaks import peak


def read(ctx):
    seconds = kernel_seconds(ctx, "flash")
    if not seconds:
        return None
    ideal, bound = kernel_cost.roofline_seconds(
        ctx["cell"].kernels["flash"],
        peak(ctx["peaks"], "bf16_flops_per_s"),
        peak(ctx["peaks"], "hbm_bytes_per_s"))
    print(f"flash_roofline: {bound}-bound, least {ideal * 1e3:.3f} ms per "
          f"step against {seconds * 1e3 / ctx['trace_steps']:.3f} ms taken",
          flush=True)
    return 100.0 * ideal * ctx["trace_steps"] / seconds
