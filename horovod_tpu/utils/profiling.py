"""Device-side profiling: per-op time aggregation from jax.profiler traces.

The reference ships a host-side timeline (chrome tracing of the
negotiation/collective state machine — ``timeline.cc`` here matches it);
this module is the DEVICE half the reference never had: run a traced
step, parse the trace-viewer JSON, and aggregate XLA op durations by
fusion category and by model layer (from HLO metadata `op_name`).  Used
by ``python -m horovod_tpu.benchmark --profile`` and by
``tools/profile_fusions.py`` (which layers a per-fusion byte analysis on
top of the same parse); it is how round 3's roofline analysis
(docs/benchmarks.md) was produced.

Works on any backend whose PJRT plugin supports ``jax.profiler``.
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import re
import tempfile
from typing import Callable, Dict, Optional, Tuple


def trace_once(run: Callable[[], None], trace_dir: Optional[str] = None):
    """Run ``run()`` under ``jax.profiler.trace``; returns the path of the
    trace-viewer ``*.trace.json.gz`` it produced."""
    import jax

    trace_dir = trace_dir or tempfile.mkdtemp(prefix="hvd_trace_")
    jax.profiler.start_trace(trace_dir)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    files = sorted(glob.glob(
        trace_dir + "/plugins/profile/*/*.trace.json.gz"))
    if not files:
        raise RuntimeError(
            f"no trace produced under {trace_dir} (profiler unsupported "
            f"on this backend?)")
    return files[-1]


def device_op_durations(trace_file: str) -> Dict[str, Tuple[float, int]]:
    """Parse a trace-viewer JSON: {op_name: (total_us, count)} for ops on
    ONE device track (host-side events are excluded; on a multi-chip SPMD
    mesh every device runs the same program, so a single track is the
    per-step time — summing all tracks would inflate by the chip
    count)."""
    with gzip.open(trace_file) as f:
        tr = json.load(f)
    pids = {e["pid"]: e["args"].get("name", "")
            for e in tr["traceEvents"]
            if e.get("ph") == "M" and e.get("name") == "process_name"}
    dev_pids = sorted(p for p, n in pids.items()
                      if "TPU" in n or "GPU" in n or "/device:" in n)
    if not dev_pids:
        raise RuntimeError(
            f"trace has no device track (processes: {sorted(pids.values())})"
            f" — XLA:CPU emits host events only; per-op breakdowns need an "
            f"accelerator backend")
    dev_pid = dev_pids[0]
    agg: Dict[str, list] = collections.defaultdict(lambda: [0.0, 0])
    for e in tr["traceEvents"]:
        if e.get("ph") == "X" and e.get("pid") == dev_pid:
            name = e["name"]
            if name == "0" or name.startswith(("jit_", "while")):
                continue   # container frames, not ops
            a = agg[name]
            a[0] += e.get("dur", 0.0)
            a[1] += 1
    return {k: (v[0], v[1]) for k, v in agg.items()}


def by_category(durs: Dict[str, Tuple[float, int]]):
    """Aggregate op durations by fusion category (name minus trailing
    numeric suffix): [(category, total_us)] sorted descending."""
    agg: Dict[str, float] = collections.defaultdict(float)
    for name, (us, _) in durs.items():
        agg[re.sub(r"\.\d+$", "", name)] += us
    return sorted(agg.items(), key=lambda kv: -kv[1])


DEFAULT_LAYER_PATTERN = (
    # ResNet blocks/stem, VGG/generic flax Conv/Dense, Inception modules,
    # transformer layers — first match in the HLO op_name wins.
    r"(BottleneckBlock_\d+|BasicBlock_\d+|Inception[A-E]_?\d*|"
    r"Reduction[A-B]_?\d*|conv_init|norm_init|head|layers_\d+|"
    r"Conv_\d+|Dense_\d+|reduce_window_max|select_and_scatter)")


def by_layer(durs: Dict[str, Tuple[float, int]], hlo_text: str,
             pattern: str = DEFAULT_LAYER_PATTERN):
    """Aggregate op durations by model layer using the optimized HLO's
    ``op_name`` metadata: [((layer, direction), total_us)] sorted
    descending.  ``direction`` is fwd/bwd (bwd = inside a transpose)."""
    rx = re.compile(pattern)
    meta: Dict[str, Tuple[str, str]] = {}
    for m in re.finditer(
            r"%([\w.-]+) = .*?op_name=\"([^\"]*)\"", hlo_text):
        name, op_name = m.group(1), m.group(2)
        lay = rx.search(op_name)
        direction = "bwd" if "transpose(" in op_name else "fwd"
        meta[name] = (lay.group(1) if lay else "other", direction)
    agg: Dict[Tuple[str, str], float] = collections.defaultdict(float)
    for name, (us, _) in durs.items():
        agg[meta.get(name, ("untracked", "?"))] += us
    return sorted(agg.items(), key=lambda kv: -kv[1])


def print_profile(trace_file: str, hlo_text: Optional[str] = None,
                  steps: int = 1, top: int = 20) -> None:
    """Human-readable summary: top fusion categories (and layers when the
    optimized HLO is supplied), normalized per step."""
    durs = device_op_durations(trace_file)
    total = sum(us for us, _ in durs.values())
    if total == 0:
        # Every op row had zero/absent duration (e.g. a trace captured
        # before any step ran, or a backend emitting bare markers) — the
        # percentage columns below would divide by zero.
        print(f"device time: 0.00 ms/step — trace {trace_file} contains "
              f"no timed device ops ({len(durs)} op rows, all with zero "
              f"duration); capture the trace around at least one "
              f"executed step")
        return
    print(f"device time: {total / steps / 1e3:.2f} ms/step "
          f"({len(durs)} distinct ops)")
    print("-- by fusion category --")
    for cat, us in by_category(durs)[:top]:
        print(f"  {us / steps / 1e3:9.3f} ms  {100 * us / total:5.1f}%  "
              f"{cat}")
    if hlo_text:
        print("-- by model layer (fwd/bwd) --")
        for (lay, d), us in by_layer(durs, hlo_text)[:top]:
            print(f"  {us / steps / 1e3:9.3f} ms  {100 * us / total:5.1f}%  "
                  f"{lay} [{d}]")
