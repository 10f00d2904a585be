"""The device ops under one scope of a traced run, joined to the optimized
HLO inside the trace: what a ``perf_opt`` starts from (ROADMAP, "the method
of the last two"; PR 44 and PR 45 made this table from scratch scripts).

    python -m perfbench.ops_by_scope <cell | file.xplane.pb> <scope> [steps]

``<cell>`` is a workload of BENCHMARK.json whose traced run has left its file
under ``.perfbench/trace/<cell>/`` (``run.py --trace 1``); its mix's
``trace_steps`` divides the seconds.  ``<scope>`` is a whole component of an
``op_name``'s path, or several joined by ``/``: ``attn/out``, ``mamba_gate``,
``layer_3/mlp/moe_dispatch`` (``horovod_tpu/telemetry/scopes.py``).

An executed instruction is under the scope where the ``op_name`` that
``scope_reduce.classify`` books it by holds it (a fusion's matmul's, else
its own, else its successor's: a wait for data is booked where the data is
needed).  Instructions that differ only in their number (one a layer) are one
row: milliseconds a step, how many, the phase, the opcode and result shape,
what a fusion holds by opcode, and where under the scope it sits.
"""

from __future__ import annotations

import collections
import glob
import os
import re
import sys
from typing import Dict, List

from perfbench import moe_reduce, scope_reduce, trace_reduce

_NUMBER = re.compile(r"[.\d]+$")
_PLUMBING = ("parameter", "tuple", "get-tuple-element", "constant",
             "bitcast")


def under(op_name: str, scope: str) -> bool:
    """``scope`` as whole components of ``op_name``'s path."""
    return re.search(r"(?:^|(?<=[/(]))" + re.escape(scope) + r"(?=$|[/)])",
                     op_name) is not None


def _holds(instruction, hlo) -> str:
    """What a fusion's computation holds, by opcode: ``dot, multiply x3``."""
    if instruction.opcode != "fusion":
        return ""
    counts = collections.Counter(
        i.opcode for i in scope_reduce._fused(instruction, hlo)
        if i.opcode not in _PLUMBING)
    matmuls = [k for k in counts if k in ("dot", "convolution")]
    rest = [k for k, _ in counts.most_common() if k not in matmuls]
    return ", ".join(k + (f" x{counts[k]}" if counts[k] > 1 else "")
                     for k in matmuls + rest[:6])


def rows(op_s: Dict[str, float], hlo, scope: str, steps: int) -> List[dict]:
    """One row a kind of instruction under ``scope``, largest first."""
    kinds: Dict[tuple, dict] = {}
    for key, seconds in op_s.items():
        name = key.split(" ", 1)[0].lstrip("%")
        op_name = moe_reduce.op_name_of(name, hlo)
        if not under(op_name, scope):
            continue
        executed = hlo.instructions.get(name)
        opcode = executed.opcode if executed else ""
        phase = scope_reduce.classify(name, hlo)[0]
        shape = executed.shape if executed else ""
        # Where under the scope: the path from the scope on, less the layer.
        where = op_name[op_name.index(scope):].rstrip(")")
        kind = (_NUMBER.sub("", name), phase, opcode, shape, where)
        row = kinds.setdefault(kind, {
            "ms": 0.0, "count": 0, "name": _NUMBER.sub("", name),
            "phase": phase, "opcode": opcode, "shape": shape,
            "where": where,
            "holds": _holds(executed, hlo) if executed else ""})
        row["ms"] += seconds * 1e3 / steps
        row["count"] += 1
    return sorted(kinds.values(), key=lambda row: -row["ms"])


def format_table(found: List[dict], scope: str, steps: int,
                 top: int = 40) -> str:
    by_phase = collections.Counter()
    for row in found:
        by_phase[row["phase"]] += row["ms"]
    out = [f"ops under {scope!r}: {sum(by_phase.values()):.3f} ms a step "
           f"over {steps} step(s) ("
           + ", ".join(f"{p} {ms:.3f}" for p, ms in by_phase.most_common())
           + f"), {sum(r['count'] for r in found)} instructions of "
           f"{len(found)} kinds",
           f"  {'ms/step':>9} {'n':>4}  {'phase':<6} instruction, result, "
           f"what a fusion holds, where"]
    for row in found[:top]:
        out.append(
            f"  {row['ms']:>9.3f} {row['count']:>4}  {row['phase']:<6} "
            + (f"{row['name']} " if row["name"] != row["opcode"] else "")
            + f"{row['opcode']} {row['shape']}"
            + (f" [{row['holds']}]" if row["holds"] else "")
            + f"  {row['where']}")
    if len(found) > top:
        out.append(f"  {sum(r['ms'] for r in found[top:]):>9.3f} "
                   f"{sum(r['count'] for r in found[top:]):>4}  in "
                   f"{len(found) - top} smaller kinds")
    return "\n".join(out)


def of_file(path: str, scope: str, steps: int) -> List[dict]:
    texts = scope_reduce.trace_hlo(path)
    if not texts:
        raise SystemExit(f"{path} holds no optimized HLO")
    device_ops, host_spans, device_async = trace_reduce.read_xplane(path)
    op_s = trace_reduce.reduce_events(device_ops, host_spans, None,
                                      device_async).get("op_s", {})
    return rows(op_s, scope_reduce.parse_hlo(*texts), scope, steps)


def main(argv) -> int:
    if len(argv) < 2:
        print(__doc__)
        return 2
    target, scope = argv[0], argv[1]
    steps = int(argv[2]) if len(argv) > 2 else 1
    if not os.path.isfile(target):
        from perfbench import run

        steps = (int(argv[2]) if len(argv) > 2
                 else run._cell_files(target, False)[3]["trace_steps"])
        files = glob.glob(os.path.join(
            scope_reduce.ROOT, ".perfbench", "trace", target, "plugins",
            "profile", "*", "*.xplane.pb"))
        if not files:
            raise SystemExit(
                f"no trace of {target!r} under .perfbench/trace: run "
                f"perfbench/run.py --workload {target} --trace 1 first")
        target = max(files, key=os.path.getmtime)
    print(format_table(of_file(target, scope, steps), scope, steps))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
