"""The flash kernels' share of their roofline under the block-diffusion
mask: the least time the chip could take for the pairs the objective
**needs**, ``L ** 2 + L * block`` a head
(``perfbench.kernel_cost_bd.block_diffusion_attention_train``, peaks from
``peaks.json``), over the time the three kernels took, recomputation
included in the time and not in the need.  A kernel that computes a whole
tile to keep a 4 x 4 block of it gets that part of the share."""

from perfbench import bd_reduce, kernel_cost
from perfbench.peaks import peak


def read(ctx):
    taken_ms = bd_reduce.flash_kernels_ms(ctx)
    cost = taken_ms and ctx["cell"].kernels.get("flash")
    if not cost:
        return None
    ideal, bound = kernel_cost.roofline_seconds(
        cost, peak(ctx["peaks"], "bf16_flops_per_s"),
        peak(ctx["peaks"], "hbm_bytes_per_s"))
    print(f"bd_flash_roofline: {bound}-bound, least {ideal * 1e3:.3f} ms "
          f"per step against {taken_ms:.3f} ms taken", flush=True)
    return 100.0 * ideal * 1e3 / taken_ms
