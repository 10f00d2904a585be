"""The flash kernels' share of their roofline in the compressed
convolutional attention layers: the least time the chip could take for the
causal pairs 8 query heads on 2 key-value heads **need**
(``perfbench.kernel_cost_cca``, peaks from ``peaks.json``), over the time
the three kernels took, recomputation included in the time and not in the
need."""

from perfbench import cca_reduce, kernel_cost
from perfbench.peaks import peak


def read(ctx):
    taken_ms = cca_reduce.part_ms(ctx, ("flash",))
    cost = taken_ms and ctx["cell"].kernels.get("flash")
    if not cost:
        return None
    ideal, bound = kernel_cost.roofline_seconds(
        cost, peak(ctx["peaks"], "bf16_flops_per_s"),
        peak(ctx["peaks"], "hbm_bytes_per_s"))
    print(f"cca_flash_roofline: {bound}-bound, least {ideal * 1e3:.3f} ms "
          f"per step against {taken_ms:.3f} ms taken", flush=True)
    return 100.0 * ideal * 1e3 / taken_ms
