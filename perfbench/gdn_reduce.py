"""Device time of the gated-delta-rule linear-attention layers by part.

The program opens the recurrence as a route of its own, ``attn/gdn_scan``,
and the mixer's other parts as bare path components under ``attn/qkv``
(``gdn_proj``: norm, the six projections, the gates; ``gdn_conv``: the
causal convolution, ``silu``, the per-head normalisation) and ``attn/out``
(``gdn_gate_norm``: the gated per-head norm; ``gdn_out``: the out
projection and the residual add) (``horovod_tpu/telemetry/scopes.py``).
``scope_reduce.scope_of`` knows the model scopes only: it answers
``attn/qkv`` and ``attn/out`` for the parts under them and ``layer`` for
the recurrence, which keeps its ``scopes:`` table and identity whole; this
file reads the part itself, from the same trace file and the same
optimized HLO inside it, by ``scope_reduce.classify``'s rule
(``moe_reduce.op_name_of``): a fusion is booked by the ``dot`` or
``convolution`` inside it, else by its own ``op_name``, else by the last
instruction inside that has one; an instruction the compiler made without
an ``op_name`` is booked where its result is needed next.  Every phase
counts: forward, backward and what ``jax.checkpoint`` recomputes.

On a program without these scopes (another model, or a commit from before
them) every function here returns None.
"""

from __future__ import annotations

import collections
import re
from typing import Dict, Optional, Sequence

from perfbench import moe_reduce, scope_reduce

PARTS = ("gdn_proj", "gdn_conv", "gdn_scan", "gdn_gate_norm", "gdn_out")
# A part is a whole component of the path.
_PART = re.compile(r"(?:^|(?<=[/(]))(" + "|".join(PARTS) + r")(?=$|[/)])")


def part_of(op_name: str) -> Optional[str]:
    """The linear mixer's part an ``op_name`` lies in: one of ``PARTS``,
    None outside the mixer."""
    found = _PART.findall(op_name)
    return found[-1] if found else None


def attribute(op_s: Dict[str, float], hlo) -> Dict[str, float]:
    """Seconds in the linear mixers by part, from ``trace_reduce``'s
    ``op_s``."""
    parts: Dict[str, float] = collections.Counter()
    for key, seconds in op_s.items():
        name = key.split(" ", 1)[0].lstrip("%")
        part = part_of(moe_reduce.op_name_of(name, hlo))
        if part:
            parts[part] += seconds
    return dict(parts)


_MEMO: Dict[int, Optional[Dict[str, float]]] = {}


def for_ctx(ctx) -> Optional[Dict[str, float]]:
    """:func:`attribute` of the run's trace, made once for all readers
    and printed; None where there is no trace, no HLO in it, or no part
    of a linear mixer in the HLO."""
    reduced = ctx.get("reduced")
    if not reduced:
        return None
    key = id(reduced)
    if key not in _MEMO:
        path = scope_reduce._trace_file(ctx)
        texts = scope_reduce.trace_hlo(path) if path else []
        parts = (attribute(reduced["op_s"], scope_reduce.parse_hlo(*texts))
                 if texts else {})
        if not parts:
            parts = None
        else:
            ms = 1e3 / ctx["trace_steps"]
            print("linear-attention layers: ms per step on one device, "
                  "every phase, by part: " + ", ".join(
                      f"{k} {parts.get(k, 0.0) * ms:.3f}" for k in PARTS),
                  flush=True)
        _MEMO[key] = parts
    return _MEMO[key]


def part_ms(ctx, parts: Sequence[str] = PARTS) -> Optional[float]:
    """Milliseconds per step in the named parts, every phase."""
    found = for_ctx(ctx)
    if found is None:
        return None
    return (sum(found.get(p, 0.0) for p in parts) * 1e3
            / ctx["trace_steps"])
