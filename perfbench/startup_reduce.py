"""From the program's own account of its start-up to where a run's
``setup_s`` went: the harness's stages, each with the program's phase spans
and compile-ledger rows that fall inside it and the seconds no row covers.

``hvd.startup_report()`` (``horovod_tpu/telemetry/spans.py``, "Start-up") is
read in the run's own process: the phase spans (``import``, ``init``,
``build_mesh``, ``make_train_step``), the seconds of Python tracing by part
of a layer, and the compile ledger, one row a program and stage (``trace``,
``mlir``, ``backend_compile``) from JAX's own monitoring events.  Every
traced run prints the ``startup:`` table (:func:`for_ctx`), and seven
per-layer metrics read it (:func:`metric`).  A program without the report
(a parent of the PR that brought it) gives None, and the line leaves the
metrics out.

**The cut.**  The report is on the program's clock and the harness's stages
(``ctx["timings"]``) are durations, so the stages are laid end to end from
the one instant both know: ``import_s`` ends where the ``import`` span ends
(the harness imports three small modules of its own after it, some
milliseconds), and each later stage begins where the one before it ended.
Set-up ends where ``warmup_s`` does; rows made later (the memory readers
compile the step once more) are not counted, and a row that straddles a
boundary is cut at it.

**Intervals nest** (a span's children lie inside it, a program made while
another is traced lies inside that trace), so every sum here is the measure
of a union of intervals, never an addition: ``other_programs_s`` is what the
other programs' rows cover *outside* the step's own.  What is only traced
inside another program's stage (``add``, a kernel's wrapper: thousands a
step) the ledger keeps as a total by name, and the table prints the largest.
"""

from __future__ import annotations

import collections
import math
from typing import Dict, List, Optional, Sequence, Tuple

from perfbench.trace_reduce import Interval, measure, subtract, union

# The top-level phase spans that are the program's Python before anything
# is traced (their children, "init/backend" and the like, lie inside them).
PYTHON_PHASES = ("init", "build_mesh", "make_train_step")
NOT_SET_UP = "tpu_start_s"      # the harness leaves it out of setup_s
SHOWN = 5                       # rows a stage shows by name


def stages_of(report: dict, timings: Dict[str, float]
              ) -> Optional[List[Tuple[str, float, float]]]:
    """``[(stage, start, end)]`` on the report's clock, in the harness's
    order; None where the report holds no ``import`` span."""
    imported = next((s for s in report["spans"] if s["name"] == "import"),
                    None)
    if imported is None or "import_s" not in timings:
        return None
    at = imported["t1"]
    out = [("import_s", at - timings["import_s"], at)]
    later = list(timings)
    for name in later[later.index("import_s") + 1:]:
        out.append((name, at, at + timings[name]))
        at += timings[name]
    return out


def _clip(t0: float, t1: float, start: float, end: float
          ) -> Optional[Interval]:
    t0, t1 = max(t0, start), min(t1, end)
    return (t0, t1) if t1 > t0 else None


def _inside(rows: Sequence[dict], start: float, end: float
            ) -> List[Interval]:
    clipped = (_clip(r["t0"], r["t1"], start, end) for r in rows)
    return [c for c in clipped if c]


def reduce(report: dict, timings: Dict[str, float]) -> Optional[dict]:
    """The run's set-up by stage, and the seven metrics; None where the
    stages cannot be laid on the report's clock."""
    laid = stages_of(report, timings)
    if laid is None:
        return None
    begin, end = laid[0][1], laid[-1][2]
    spans = [s for s in report["spans"] if s["t0"] < end and s["t1"] > begin]
    rows = [r for r in report["compiles"] if r["t0"] < end
            and r["t1"] > begin]
    step = [r for r in rows if r["role"] == "step"]
    other = [r for r in rows if r["role"] != "step"]

    def seconds(chosen):
        return measure(_inside(chosen, begin, end))

    stages = []
    for name, start, stop in laid:
        covered = measure(_inside(spans, start, stop)
                          + _inside(rows, start, stop))
        by_label: Dict[str, List[Interval]] = collections.defaultdict(list)
        for s in spans:
            if s["parent"] is None and _clip(s["t0"], s["t1"], start, stop):
                by_label[f"span {s['name']}"].append(
                    _clip(s["t0"], s["t1"], start, stop))
        for r in rows:
            cut = _clip(r["t0"], r["t1"], start, stop)
            if cut:
                by_label[f"{r['fun_name']} {r['stage']}"].append(cut)
        inside = sorted(((label, measure(cuts), len(cuts))
                         for label, cuts in by_label.items()),
                        key=lambda row: -row[1])
        stages.append({"name": name, "seconds": stop - start,
                       "covered_s": covered,
                       "uncovered_s": stop - start - covered,
                       "rows": inside})
    counted = [s for s in stages if s["name"] != NOT_SET_UP]
    total = sum(s["seconds"] for s in counted)
    compiled = [r for r in rows if r["stage"] == "backend_compile"]
    imported = next(s for s in report["spans"] if s["name"] == "import")
    step_union = union(_inside(step, begin, end))
    metrics = {
        "hvd_import_s": imported["t1"] - imported["t0"],
        "hvd_init_s": seconds([s for s in spans
                               if s["name"] in PYTHON_PHASES]),
        "step_trace_s": seconds([r for r in step if r["stage"] == "trace"]),
        "step_mlir_s": seconds([r for r in step if r["stage"] == "mlir"]),
        "other_programs_s": sum(
            b - a for a, b in subtract(union(_inside(other, begin, end)),
                                       step_union)),
        "programs_built": float(len(compiled)),
        "setup_in_program_pct": (
            100.0 * sum(s["covered_s"] for s in counted) / total
            if total > 0 else None),
    }
    return {
        "stages": stages, "metrics": metrics, "setup_s": total,
        "step_compile_s": seconds(
            [r for r in step if r["stage"] == "backend_compile"]),
        "cache_states": dict(collections.Counter(
            r["cache"] for r in compiled)),
        "cache_read_s": sum(r["cache_read_s"] for r in compiled),
        "built_s": {state: seconds([r for r in compiled
                                    if r["cache"] == state])
                    for state in ("hit", "miss", "none")},
        "slowest": sorted(
            ((r["t1"] - r["t0"], r["fun_name"], r["stage"], r["cache"])
             for r in rows), reverse=True)[:8],
        "events": len(rows), "dropped": report.get("dropped", {}),
        "cache": report.get("cache"), "parts": report.get("parts", {}),
        "nested": report.get("nested_traces", {}),
    }


def _against(name: str, ours: float, theirs: Optional[float]) -> str:
    if not theirs:
        return f"{name} not lapped"
    return (f"{ours:.3f} against the harness's {name} {theirs:.3f} "
            f"({100.0 * (ours - theirs) / theirs:+.1f}%)")


def format_table(reduced: dict, timings: Dict[str, float]) -> str:
    m = reduced["metrics"]
    out = ["startup: the harness's stages, seconds, and of them inside the "
           "program's spans and JAX's trace / lowering / compile rows "
           "(a union), and outside both; then the rows inside, largest "
           "first"]
    out.append(f"  {'stage':<12} {'seconds':>9} {'in rows':>9} "
               f"{'outside':>9}")
    for stage in reduced["stages"]:
        inside = ", ".join(
            f"{label} {seconds:.3f}" + (f" x{count}" if count > 1 else "")
            for label, seconds, count in stage["rows"][:SHOWN])
        more = len(stage["rows"]) - SHOWN
        if more > 0:
            inside += f", {more} more " + format(
                sum(r[1] for r in stage["rows"][SHOWN:]), ".3f")
        out.append(f"  {stage['name']:<12} {stage['seconds']:>9.3f} "
                   f"{stage['covered_s']:>9.3f} {stage['uncovered_s']:>9.3f}"
                   f"  {inside}")
    out.append(
        f"  sum of the stages but {NOT_SET_UP} {reduced['setup_s']:.3f} s "
        f"(setup_s, less the moments after the last lap), "
        f"{m['setup_in_program_pct']:.2f}% of it inside rows")
    out.append(
        "  the step: trace + lowering "
        + _against("lower_s", m["step_trace_s"] + m["step_mlir_s"],
                   timings.get("lower_s"))
        + f" (trace {m['step_trace_s']:.3f}, lowering "
        f"{m['step_mlir_s']:.3f}; lower_s also holds the harness's "
        f"lowered.as_text()); compile or cache read "
        + _against("compile_s", reduced["step_compile_s"],
                   timings.get("compile_s")))
    states = reduced["cache_states"]
    built = reduced["built_s"]
    out.append(
        f"  programs: {int(m['programs_built'])} reached the backend in "
        f"set-up ({reduced['events']} ledger rows; dropped "
        f"{reduced['dropped'].get('compiles', 0)}): "
        f"{states.get('hit', 0)} read from the cache in {built['hit']:.3f} s"
        f" (the reads themselves {reduced['cache_read_s']:.3f}), "
        f"{states.get('miss', 0)} compiled in {built['miss']:.3f} s, "
        f"{states.get('none', 0)} outside the cache in {built['none']:.3f} s;"
        f" every program but the step's, outside the step's rows: "
        f"{m['other_programs_s']:.3f} s")
    cache = reduced["cache"]
    if cache:
        cap = cache.get("cap_bytes")
        out.append(
            f"  compile cache at the start: {cache['bytes']} bytes in "
            f"{cache['entries']} entries, "
            + (f"cap {cap} ({100.0 * cache['bytes'] / cap:.1f}% full)"
               if cap else "no cap") + f", {cache['dir']}")
    out.append("  slowest rows: " + "; ".join(
        f"{fun} {stage} {seconds:.3f}"
        + (f" ({cache})" if stage == "backend_compile" else "")
        for seconds, fun, stage, cache in reduced["slowest"]))
    if reduced["parts"]:
        out.append("  Python tracing by part (inside the step's trace): "
                   + ", ".join(f"{name} {p['seconds']:.3f} s x{p['count']}"
                               for name, p in sorted(
                                   reduced["parts"].items(),
                                   key=lambda kv: -kv[1]["seconds"])))
    if reduced["nested"]:
        ranked = sorted(reduced["nested"].items(),
                        key=lambda kv: -kv[1]["seconds"])
        out.append(
            f"  traced inside another program's stage, "
            f"{sum(n['count'] for n in reduced['nested'].values())} times in "
            f"{sum(n['seconds'] for n in reduced['nested'].values()):.3f} s "
            f"(each inside its caller's): " + ", ".join(
                f"{fun} {n['seconds']:.3f} x{n['count']}"
                for fun, n in ranked[:SHOWN + 3]))
    return "\n".join(out)


_MEMO: Dict[int, Optional[dict]] = {}


def for_ctx(ctx) -> Optional[dict]:
    """The run's :func:`reduce`, made once for all readers; the first call
    prints the ``startup:`` table.  ``ctx["startup_report"]`` where a test
    hands one in, else the program's own; None where the program has no
    such report."""
    timings = ctx.get("timings")
    if not timings:
        return None
    key = id(timings)
    if key not in _MEMO:
        report = ctx.get("startup_report")
        if report is None:
            import horovod_tpu as hvd

            read = getattr(hvd, "startup_report", None)
            report = read() if read else None
        _MEMO[key] = reduce(report, timings) if report else None
        if _MEMO[key]:
            print(format_table(_MEMO[key], timings), flush=True)
    return _MEMO[key]


def metric(ctx, name: str) -> Optional[float]:
    """One of the seven for a reader; None where there is no report, and
    where the number is not a finite one above 0 (a metric that can read 0
    belongs in the table: PERF.md, trap 4)."""
    reduced = for_ctx(ctx)
    value = reduced["metrics"][name] if reduced else None
    if value is None or not math.isfinite(value) or value <= 0:
        return None
    return value
