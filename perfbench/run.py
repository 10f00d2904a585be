#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the machine this is started on.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell's entry in ``BENCHMARK.json`` names a configuration and a traffic
mix; the configuration's file names the adapter (``kind``) that builds the
step through the program's own entry points.  Nothing in this file knows a
cell, a configuration or a metric by name (``perfbench/README.md``).

A run: import, start the backend, make weights and a pool of batches on
the device from ``--seed``, lower and compile the step, check the first
step against the plain reference, warm up.  All of that but the TPU
runtime's own start is ``setup_s``.  Then

``--trace 0``  dispatch single steps back to back for ``--seconds``,
               fenced with ``block_until_ready`` on the loss every
               ``fence_every`` steps, and report the end-to-end metrics
               from the median fenced chunk;
``--trace 1``  one untraced chunk (for ``dispatch_ms``), then
               ``trace_steps`` steps under ``jax.profiler``, reduced by
               ``perfbench/trace_reduce.py`` and read by one reader per
               per-layer metric (``perfbench/layer_metrics/``).

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``.  On any platform but ``tpu``, or with fewer chips than the
cell asks for, the run exits with code 2 and prints no result.
``--rehearse-cpu`` runs the same code at the tiny sizes the data files give
under ``rehearsal``; it says so and prints no metric.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse
import glob
import importlib
import json
import math
import os
import shutil
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# Keys of a traffic mix that the harness reads; the rest is the adapter's.
HARNESS_KEYS = ("pool", "warmup_steps", "fence_every", "trace_steps")
GIB = 2.0 ** 30


def _load(path: str, rehearse: bool) -> dict:
    with open(path) as f:
        data = json.load(f)
    tiny = data.pop("rehearsal", {})
    if rehearse:
        data.update(tiny)
    return data


def _cell_files(workload: str, rehearse: bool):
    """``(bench, cell entry, configuration, mix)`` by the names in
    BENCHMARK.json."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(there are: {sorted(cells)})")
    entry = cells[workload]
    config_entry = next(c for c in bench["configs"]
                        if c["name"] == entry["config"])
    config = _load(os.path.join(ROOT, config_entry["file"]), rehearse)
    mix = _load(os.path.join(HERE, "traffic", entry["traffic"] + ".json"),
                rehearse)
    mix.pop("doc", None)
    return bench, entry, config, mix


def _wanted(metrics: list, workload: str) -> list:
    return [m for m in metrics
            if workload in m.get("workloads", [workload])]


def _quartiles(values):
    if len(values) < 2:
        return values[0], values[0], values[0]
    q1, q2, q3 = statistics.quantiles(values, n=4, method="inclusive")
    return q1, q2, q3


class _CompileCounter:
    """Counts what JAX reports of tracing, lowering and compiling."""

    def __init__(self, jax):
        self.events = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kwargs):
        if event.startswith("/jax/core/compile") or event.startswith(
                "/jax/compilation_cache"):
            self.events += 1


def _bytes_on_fullest_device(jax) -> int:
    per_device = {}
    for array in jax.live_arrays():
        for shard in array.addressable_shards:
            per_device[shard.device] = (per_device.get(shard.device, 0)
                                        + shard.data.nbytes)
    return max(per_device.values(), default=0)


def _check_first_step(cell, compiled, state, batch, tolerance):
    """Checks (a) and (b): take the first step and hold its loss, and the
    gradient recovered from what it did to ``cell.checked`` leaves, to the
    plain reference, within the configuration's ``check`` tolerances.
    Returns ``(new state, {check: passed})``."""
    import jax.numpy as jnp
    import numpy as np

    ref_loss, ref_grads = cell.reference(state, batch)
    before = {k: jnp.copy(v) for k, v in cell.checked(state).items()}
    *state, loss = compiled(*state, *batch)
    loss, ref_loss = float(loss), float(ref_loss)
    loss_err = abs(loss - ref_loss) / abs(ref_loss)
    checks = {"loss_matches_reference": loss_err <= tolerance["loss_rtol"]}
    print(f"check (a): loss {loss:.6f}, float32 reference {ref_loss:.6f}, "
          f"relative error {loss_err:.2e} (tolerance "
          f"{tolerance['loss_rtol']:.0e})",
          flush=True)
    for name, new in cell.checked(tuple(state)).items():
        got = (np.asarray(new, np.float32)
               - np.asarray(before[name], np.float32)) * cell.grad_per_delta
        want = np.asarray(ref_grads[name], np.float32)
        err = float(np.linalg.norm(got - want) / np.linalg.norm(want))
        checks[f"gradient_matches_reference:{name}"] = (
            err <= tolerance["grad_rel_l2"])
        print(f"check (b): {name}: relative L2 error of the gradient "
              f"recovered from the first step {err:.4f} (tolerance "
              f"{tolerance['grad_rel_l2']})", flush=True)
    return state, checks


def _memory(compiled, live_bytes: int, devices):
    """``(bytes the cell needs on its fullest chip, bytes the backend says
    the process took there)``.

    The first is ``memory_analysis()`` of the step (arguments + outputs -
    aliased + temporaries + code) plus the live arrays that are not its
    arguments; it is the metric: known from the compile, the step's own.
    The second is ``memory_stats()``: ``peak_bytes_in_use`` holds arrays
    only, a loaded program's temporaries are *reserved* beside them
    (``peak_bytes_reserved``), and a step needs both (PERF.md, PR 22: a
    ballast that the sum says cannot fit makes the step fail to load).  It
    covers the whole process, the reference check included."""
    a = compiled.memory_analysis()
    program = (a.argument_size_in_bytes + a.output_size_in_bytes
               - a.alias_size_in_bytes + a.temp_size_in_bytes
               + a.generated_code_size_in_bytes)
    needed = program + live_bytes - a.argument_size_in_bytes
    stats = [d.memory_stats() or {} for d in devices]
    backend = max((s.get("peak_bytes_in_use", 0)
                   + s.get("peak_bytes_reserved", 0) for s in stats),
                  default=0) or None
    print(f"memory: memory_analysis() of the step {program} bytes "
          f"(arguments {a.argument_size_in_bytes}, outputs "
          f"{a.output_size_in_bytes}, aliased {a.alias_size_in_bytes}, "
          f"temporaries {a.temp_size_in_bytes}, code "
          f"{a.generated_code_size_in_bytes}); live arrays on the fullest "
          f"chip {live_bytes}; step plus live arrays that are not its "
          f"arguments {needed} = {needed / GIB:.3f} GiB; memory_stats() "
          f"peak_bytes_in_use + peak_bytes_reserved {backend}"
          + (f" = {backend / GIB:.3f} GiB ({stats[0]})" if backend else ""),
          flush=True)
    return needed, backend


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument(
        "--rehearse-cpu", action="store_true",
        help="tiny sizes on whatever JAX finds; prints no metric")
    args = parser.parse_args(argv)
    bench, entry, config, mix = _cell_files(args.workload,
                                            args.rehearse_cpu)
    chips = entry["chips"]
    timings = {}
    mark = _PROCESS_START

    def lap(name):
        nonlocal mark
        now = time.perf_counter()
        timings[name] = now - mark
        mark = now

    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.topology import build_mesh
    from horovod_tpu.utils.compile_cache import enable_compile_cache
    from perfbench import trace_reduce
    from perfbench.peaks import peak, peaks_for

    lap("import_s")
    devices = jax.devices()
    lap("tpu_start_s")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"device: {device}", flush=True)
    if args.rehearse_cpu:
        print("REHEARSAL at tiny sizes: exercises the code path only; "
              "nothing printed below is a chip result", flush=True)
    elif device["platform"] != "tpu":
        print(f"perfbench: JAX found platform {device['platform']!r}, not "
              f"'tpu'; a cell is measured on the chip only",
              file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"perfbench: cell {args.workload!r} needs {chips} chip(s), "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    on_tpu = device["platform"] == "tpu"
    peaks = peaks_for(device["kind"]) if on_tpu else {}

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    # Every program of a run goes to the cache, the sub-second ones too:
    # a run is a new process and pays for each again otherwise.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = _CompileCounter(jax)
    hvd.init()
    shape = mix.pop("mesh_shape", None)
    mesh = build_mesh(axes=tuple(mix["mesh_axes"]),
                      shape=tuple(shape) if shape else None,
                      devices=devices[:chips])
    lap("mesh_s")

    harness = {k: mix.pop(k) for k in HARNESS_KEYS}
    adapter = importlib.import_module(
        "perfbench.adapters." + config["kind"])
    cell = adapter.build(config, mix, mesh)
    lap("build_s")
    state, pool = cell.make(args.seed, harness["pool"])
    jax.block_until_ready((state, pool))
    lap("init_s")

    lowered = cell.step.lower(*state, *pool[0])
    has_kernel = "tpu_custom_call" in lowered.as_text()
    lap("lower_s")
    compiled = lowered.compile()
    lap("compile_s")
    checks = {"kernel_in_lowered_step":
              has_kernel or not (cell.kernels and on_tpu)}

    state, first_step = _check_first_step(
        cell, compiled, state, pool[0], config["check"])
    checks.update(first_step)
    lap("check_s")

    cursor = 1

    def chunk(steps):
        """``steps`` single steps back to back, then a fence on the last
        loss: ``(seconds, seconds of each call, losses)``."""
        nonlocal state, cursor
        calls, losses = [], []
        start = time.perf_counter()
        for _ in range(steps):
            with jax.profiler.TraceAnnotation("perfbench:batch"):
                batch = pool[cursor % len(pool)]
                cursor += 1
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("perfbench:dispatch"):
                *state, step_loss = compiled(*state, *batch)
            calls.append(time.perf_counter() - t0)
            losses.append(step_loss)
        with jax.profiler.TraceAnnotation("perfbench:fence"):
            jax.block_until_ready(step_loss)
        return time.perf_counter() - start, calls, losses

    chunk(harness["warmup_steps"])
    lap("warmup_s")
    # The TPU runtime's own start (the first jax.devices()) is left out:
    # no file of the repository runs inside it, and on one machine it
    # took 7.2 to 12.5 s from one run to the next, more than the bound on
    # the whole of set-up (PERF.md, PR 22).  It is printed beside it.
    setup_s = (time.perf_counter() - _PROCESS_START
               - timings["tpu_start_s"])
    print("set-up: " + ", ".join(f"{k} {v:.2f}" for k, v in timings.items())
          + f"; setup_s {setup_s:.2f} (all of these but tpu_start_s)",
          flush=True)

    live_bytes = _bytes_on_fullest_device(jax)
    events_before = compiles.events
    fence_every = harness["fence_every"]
    chunk_s, losses, reduced, dispatch_s = [], [], {}, []
    if args.trace:
        _, dispatch_s, first = chunk(fence_every)
        trace_dir = os.path.join(ROOT, ".perfbench", "trace", args.workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            _, _, traced = chunk(harness["trace_steps"])
        finally:
            jax.profiler.stop_trace()
        losses = first + traced
        files = glob.glob(os.path.join(
            trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
        reduced = trace_reduce.reduce_file(
            files[0], {k: v["match"] for k, v in cell.kernels.items()}
        ) if files else {}
    else:
        window_start = time.perf_counter()
        while time.perf_counter() - window_start < args.seconds:
            seconds, _, chunk_losses = chunk(fence_every)
            chunk_s.append(seconds)
            losses += chunk_losses
            print(f"chunk {len(chunk_s)}: {seconds:.4f} s for {fence_every}"
                  f" steps, loss at the fence "
                  f"{float(chunk_losses[-1]):.6f}", flush=True)
        window_s = time.perf_counter() - window_start
    checks["no_compilation_in_window"] = compiles.events == events_before
    values = np.asarray(jnp.stack(losses), np.float32)
    failed = int((~np.isfinite(values)).sum())
    checks["losses_finite"] = failed == 0
    print(f"checks: {checks}", flush=True)

    needed_bytes, device["memory_peak_bytes"] = _memory(
        compiled, live_bytes, devices[:chips])

    result = {"correct": all(checks.values()), "attempted": len(losses),
              "failed": failed, "device": device}
    if args.rehearse_cpu:
        result["rehearsal"] = True
        print(json.dumps(result), flush=True)
        return 0

    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    if args.trace:
        ctx = {"reduced": reduced, "trace_steps": harness["trace_steps"],
               "timings": timings, "dispatch_s": dispatch_s, "cell": cell,
               "peaks": peaks}
        if not reduced:
            raise SystemExit("the trace holds no device plane with an "
                             f"{trace_reduce.OPS_LINE!r} line")
        metrics = {}
        for m in _wanted(bench["per_layer"], args.workload):
            reader = importlib.import_module(
                "perfbench.layer_metrics." + m["name"])
            metrics[m["name"]] = reader.read(ctx)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]

        def top(table):
            return [[k, v] for k, v in sorted(
                table.items(), key=lambda kv: -kv[1])[:10]]

        result["breakdown"] = {"device_ops": top(reduced["op_s"]),
                               "idle_gaps": top(reduced["idle_gaps"])}
    else:
        q1, median, q3 = _quartiles(chunk_s)
        rate = cell.items_per_step * fence_every / median / chips
        flops_per_chip_s = cell.flops_per_step * fence_every / median / chips
        mfu = 100.0 * flops_per_chip_s / peak(peaks, "bf16_flops_per_s")
        whole = (100.0 * cell.flops_per_step * len(losses) / window_s / chips
                 / peak(peaks, "bf16_flops_per_s"))
        print(f"window: {len(chunk_s)} chunks of {fence_every} steps in "
              f"{window_s:.3f} s; chunk seconds q1 {q1:.4f} median "
              f"{median:.4f} q3 {q3:.4f}; {median / fence_every:.4f} "
              f"s/step; {rate:.1f} {cell.item}/s/chip; "
              f"{flops_per_chip_s / 1e12:.2f} model TFLOP/s/chip; MFU over "
              f"the whole window {whole:.3f}%; last loss "
              f"{float(values[-1]):.6f}", flush=True)
        measured = {"mfu_pct": mfu, "peak_hbm_gib": needed_bytes / GIB,
                    "setup_s": setup_s}
        metrics = {m["name"]: measured[m["name"]]
                   for m in _wanted(bench["end_to_end"], args.workload)}
    result["metrics"] = {k: {"value": v, "unit": units[k]}
                         for k, v in metrics.items()
                         if v is not None and math.isfinite(v)}
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
