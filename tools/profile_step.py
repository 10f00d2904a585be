#!/usr/bin/env python
"""Per-step roofline + profiler probe for the flagship bench step.

Prints XLA cost-analysis (flops, bytes accessed) for the single-step
training program, derives the roofline lower bound, and attempts a
jax.profiler trace.
"""
import json
import os
import sys

import jax
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _bench_setup import setup  # noqa: E402
from horovod_tpu.benchmark import make_train_step, device_peak_tflops  # noqa


def main():
    mesh, ax, model, optimizer, state, inputs = setup()
    (params, batch_stats, opt_state), (images, labels) = state, inputs

    step = make_train_step(model, optimizer, mesh, ax, steps_per_call=1)
    lowered = step.lower(params, batch_stats, opt_state, images, labels)
    compiled = lowered.compile()

    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    print("== cost analysis keys ==")
    for k in sorted(ca):
        v = ca[k]
        if isinstance(v, float) and abs(v) > 1e4:
            print(f"  {k}: {v:.4g}")
        else:
            print(f"  {k}: {v}")

    flops = float(ca.get("flops", 0.0))
    byt = float(ca.get("bytes accessed", 0.0))
    peak_tf = device_peak_tflops(mesh.devices.ravel()[0])
    if peak_tf is None:
        raise SystemExit("profile_step: a roofline needs a chip; JAX found "
                         f"{mesh.devices.ravel()[0].platform!r}")
    hbm_gbs = float(os.environ.get("BENCH_PEAK_HBM_GBS", "819"))  # v5e
    t_flops = flops / (peak_tf * 1e12)
    t_bytes = byt / (hbm_gbs * 1e9)
    print("\n== roofline ==")
    print(f"flops/step            : {flops:.4g}")
    print(f"bytes accessed/step   : {byt:.4g}")
    print(f"arith intensity       : {flops / max(byt, 1):.1f} flop/byte")
    print(f"t_lower(compute)      : {t_flops * 1e3:.2f} ms")
    print(f"t_lower(bandwidth)    : {t_bytes * 1e3:.2f} ms")
    print(f"roofline bound        : {max(t_flops, t_bytes) * 1e3:.2f} ms")

    # measured single-step time (amortized over a scanned round)
    import time
    step90 = make_train_step(model, optimizer, mesh, ax, steps_per_call=30)
    c90 = step90.lower(params, batch_stats, opt_state, images, labels).compile()
    p, s, o, loss = c90(params, batch_stats, opt_state, images, labels)
    float(np.asarray(loss))
    t0 = time.perf_counter()
    p, s, o, loss = c90(p, s, o, images, labels)
    float(np.asarray(loss))
    dt = (time.perf_counter() - t0) / 30
    print(f"measured t_step       : {dt * 1e3:.2f} ms")
    print(f"implied MFU           : {flops / (peak_tf * 1e12) / dt * 100:.1f}%")
    print(f"implied HBM util      : {byt / (hbm_gbs * 1e9) / dt * 100:.1f}%")

    # HLO op histogram from the optimized module
    try:
        txt = compiled.as_text()
        with open("/tmp/step_hlo.txt", "w") as f:
            f.write(txt)
        print(f"\noptimized HLO -> /tmp/step_hlo.txt ({len(txt)} bytes)")
    except Exception as e:
        print(f"as_text failed: {e}")

    # profiler probe
    try:
        jax.profiler.start_trace("/tmp/jax_trace")
        p, s, o, loss = c90(p, s, o, images, labels)
        float(np.asarray(loss))
        jax.profiler.stop_trace()
        print("profiler trace: OK -> /tmp/jax_trace")
    except Exception as e:
        print(f"profiler trace failed: {type(e).__name__}: {e}")


if __name__ == "__main__":
    main()
