"""The expert matmuls' share of their roofline: the least time the chip
could take for the three grouped matmuls of every expert, forward and
backward (FLOPs and bytes from ``perfbench.kernel_cost_moe``, peaks from
``peaks.json``), over the time spent under ``mlp/moe_experts``."""

from perfbench import kernel_cost, moe_reduce
from perfbench.peaks import peak


def read(ctx):
    cost = ctx["cell"].kernels.get("moe_gmm")
    taken_ms = moe_reduce.part_ms(ctx, ("moe_experts",))
    if not cost or not taken_ms:
        return None
    ideal, bound = kernel_cost.roofline_seconds(
        cost, peak(ctx["peaks"], "bf16_flops_per_s"),
        peak(ctx["peaks"], "hbm_bytes_per_s"))
    print(f"moe_experts_roofline: {bound}-bound, least {ideal * 1e3:.3f} ms "
          f"per step against {taken_ms:.3f} ms taken", flush=True)
    return 100.0 * ideal * 1e3 / taken_ms
