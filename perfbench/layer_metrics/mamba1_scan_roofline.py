"""The selective scan's share of its roofline: the least time the chip
could take for the Mamba-1 recurrence, forward, backward and (where the
cell recomputes layers) the second forward (FLOPs and bytes from
``perfbench.kernel_cost_mamba1``, peaks from ``peaks.json``), over the
time spent under ``attn/mamba_scan``.  The work is the vector unit's,
which ``peaks.json`` has no row for: the bytes set the bound, and a scan
whose vector unit is full reads near 10%
(``kernel_cost_mamba1``'s docstring)."""

from perfbench import kernel_cost, mamba1_reduce
from perfbench.peaks import peak


def read(ctx):
    cost = ctx["cell"].kernels.get("mamba_scan")
    taken_ms = mamba1_reduce.part_ms(ctx, (mamba1_reduce.SCAN,))
    if not cost or not taken_ms:
        return None
    ideal, bound = kernel_cost.roofline_seconds(
        cost, peak(ctx["peaks"], "bf16_flops_per_s"),
        peak(ctx["peaks"], "hbm_bytes_per_s"))
    print(f"mamba1_scan_roofline: {bound}-bound, least {ideal * 1e3:.3f} "
          f"ms per step against {taken_ms:.3f} ms taken", flush=True)
    return 100.0 * ideal * 1e3 / taken_ms
