"""Device time of a mixture-of-experts layer by its parts.

The program opens four sub-scopes under ``mlp`` as bare path components
(``horovod_tpu/telemetry/scopes.py``): ``moe_router`` (router matmul,
softmax, top-k, the auxiliary losses' sums), ``moe_dispatch`` (sort,
counts, gather), ``moe_experts`` (the three grouped matmuls and
``silu *``) and ``moe_combine`` (gather back, weighting, sum).
``scope_reduce.scope_of`` knows the model scopes only and answers ``mlp``
for all of them, which keeps its ``scopes:`` table and identity whole;
this file reads one level further down, from the same trace file and the
same optimized HLO inside it, by the same rule as
``scope_reduce.classify``: a fusion is booked by the ``dot`` or
``convolution`` inside it, else by its own ``op_name``, else by the last
instruction inside that has one; an instruction the compiler made without
an ``op_name`` is booked where its result is needed next.

On a program without these sub-scopes (a dense model, or a commit from
before them) every function here returns None.
"""

from __future__ import annotations

import collections
import re
from typing import Dict, Optional, Sequence

from perfbench import scope_reduce

SUB_SCOPES = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
# The part of an mlp's path right under it.
_SUB = re.compile(r"(?:^|(?<=[/(]))mlp\)*/(" + "|".join(SUB_SCOPES)
                  + r")(?=$|[/)])")
OTHER = "(mlp, no part)"


def _carriers(instruction, hlo):
    """The instructions whose ``op_name`` may speak for ``instruction``,
    in ``scope_reduce.classify``'s order."""
    if instruction.opcode != "fusion":
        return [instruction] if instruction.op_name else []
    inner = [i for i in scope_reduce._fused(instruction, hlo) if i.op_name]
    matmuls = [i for i in inner if i.opcode in ("dot", "convolution")]
    own = [instruction] if instruction.op_name else []
    return matmuls + own + inner[::-1]


def op_name_of(name: str, hlo) -> str:
    """The ``op_name`` that ``scope_reduce.classify`` books the executed
    instruction ``name`` by; ``""`` where it finds none."""
    executed = hlo.instructions.get(name)
    if executed is None:
        return ""
    host = executed
    for _ in range(9):
        placed = [c.op_name for c in _carriers(host, hlo)
                  if scope_reduce.phase_of(c.op_name, opcode=c.opcode)
                  != "unattributed"]
        if placed:
            return placed[0]
        then = hlo.successor.get(host.name)
        if then is None:
            break
        host = hlo.instructions[then]
    names = [c.op_name for c in _carriers(executed, hlo)]
    return names[0] if names else ""


def part_of(op_name: str) -> Optional[str]:
    """The expert layer's part an ``op_name`` lies in: one of
    ``SUB_SCOPES``, ``OTHER`` for the rest of an ``mlp`` (its norm, its
    residual add), None outside ``mlp``."""
    if scope_reduce.scope_of(op_name) != "mlp":
        return None
    found = _SUB.findall(op_name)
    return found[-1] if found else OTHER


def attribute(op_s: Dict[str, float], hlo) -> Dict[str, float]:
    """Seconds under ``mlp`` by part, from ``trace_reduce``'s ``op_s``."""
    parts: Dict[str, float] = collections.Counter()
    for key, seconds in op_s.items():
        name = key.split(" ", 1)[0].lstrip("%")
        part = part_of(op_name_of(name, hlo))
        if part:
            parts[part] += seconds
    return dict(parts)


_MEMO: Dict[int, Optional[Dict[str, float]]] = {}


def for_ctx(ctx) -> Optional[Dict[str, float]]:
    """:func:`attribute` of the run's trace, made once for all readers
    and printed; None where there is no trace, no HLO in it, or no
    sub-scope in the HLO."""
    reduced = ctx.get("reduced")
    if not reduced:
        return None
    key = id(reduced)
    if key not in _MEMO:
        path = scope_reduce._trace_file(ctx)
        texts = scope_reduce.trace_hlo(path) if path else []
        parts = (attribute(reduced["op_s"], scope_reduce.parse_hlo(*texts))
                 if texts else {})
        if not set(parts).intersection(SUB_SCOPES):
            parts = None
        else:
            ms = 1e3 / ctx["trace_steps"]
            print("expert layer: ms per step on one device under mlp, by "
                  "part: " + ", ".join(
                      f"{k} {parts.get(k, 0.0) * ms:.3f}"
                      for k in SUB_SCOPES + (OTHER,)), flush=True)
        _MEMO[key] = parts
    return _MEMO[key]


def part_ms(ctx, parts: Sequence[str]) -> Optional[float]:
    """Milliseconds per step under the named parts, every phase."""
    found = for_ctx(ctx)
    if found is None:
        return None
    return (sum(found.get(p, 0.0) for p in parts) * 1e3
            / ctx["trace_steps"])
