"""The grouped matmuls' share of their roofline under the MLP router:
the least time the chip could take for the three matmuls of the one expert
a token chooses, forward and backward, at the rows a uniform router sends
to the held experts (``perfbench.kernel_cost_cca``, peaks from
``peaks.json``), over the time spent under ``mlp/moe_experts``,
recomputation included in the time and not in the need."""

from perfbench import cca_reduce, kernel_cost
from perfbench.peaks import peak


def read(ctx):
    cost = ctx["cell"].kernels.get("moe_gmm")
    taken_ms = cca_reduce.moe_part_ms(ctx, ("moe_experts",))
    if not cost or not taken_ms:
        return None
    ideal, bound = kernel_cost.roofline_seconds(
        cost, peak(ctx["peaks"], "bf16_flops_per_s"),
        peak(ctx["peaks"], "hbm_bytes_per_s"))
    print(f"zaya_experts_roofline: {bound}-bound, least {ideal * 1e3:.3f} "
          f"ms per step against {taken_ms:.3f} ms taken", flush=True)
    return 100.0 * ideal * 1e3 / taken_ms
