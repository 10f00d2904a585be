"""The flash kernels' share of their roofline at latent attention's head
width: the least time the chip could take for the causal attention the
step needs (FLOPs and bytes from ``perfbench.kernel_cost`` at the cell's
own head width, peaks from ``peaks.json``) over the time the three
kernels took, recomputation included in the time and not in the need.
The kernels are found by the name the program's scopes give them
(``scope_reduce.kernel_ms``), whatever number XLA gave the instruction."""

from perfbench import kernel_cost, mla_reduce, scope_reduce
from perfbench.peaks import peak


def read(ctx):
    cost = ctx["cell"].kernels.get("flash")
    if not cost or mla_reduce.for_ctx(ctx) is None:
        return None
    kernels = [scope_reduce.kernel_ms(ctx, k)
               for k in scope_reduce.KERNEL_NAMES]
    taken_ms = sum(k for k in kernels if k is not None)
    if not taken_ms:
        return None
    ideal, bound = kernel_cost.roofline_seconds(
        cost, peak(ctx["peaks"], "bf16_flops_per_s"),
        peak(ctx["peaks"], "hbm_bytes_per_s"))
    print(f"mla_flash_roofline: {bound}-bound, least {ideal * 1e3:.3f} ms "
          f"per step against {taken_ms:.3f} ms taken", flush=True)
    return 100.0 * ideal * 1e3 / taken_ms
