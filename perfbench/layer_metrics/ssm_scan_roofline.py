"""The recurrence's share of its roofline: the least time the chip could
take for the Mamba-2 state-space recurrence in its recurrent form,
forward, backward and (where the cell recomputes layers) the second
forward (FLOPs and bytes from ``perfbench.kernel_cost_ssm``, peaks from
``peaks.json``), over the time spent under ``attn/ssm_scan``."""

from perfbench import kernel_cost, ssm_reduce
from perfbench.peaks import peak


def read(ctx):
    cost = ctx["cell"].kernels.get("ssm_scan")
    taken_ms = ssm_reduce.part_ms(ctx, ("ssm_scan",))
    if not cost or not taken_ms:
        return None
    ideal, bound = kernel_cost.roofline_seconds(
        cost, peak(ctx["peaks"], "bf16_flops_per_s"),
        peak(ctx["peaks"], "hbm_bytes_per_s"))
    print(f"ssm_scan_roofline: {bound}-bound, least {ideal * 1e3:.3f} ms "
          f"per step against {taken_ms:.3f} ms taken", flush=True)
    return 100.0 * ideal * 1e3 / taken_ms
