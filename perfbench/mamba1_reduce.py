"""Device time of a Jamba-style step by part: the Mamba-1 mixers, the
dense MLPs and the multi-query attention layer.

The program opens (``horovod_tpu/telemetry/scopes.py``): the selective
scan as a route of its own, ``attn/mamba_scan``, and the mixer's other
parts as bare path components under ``attn/qkv`` (``mamba_proj``: norm
and in-projection; ``mamba_conv``: the causal convolution with its bias
and ``silu``; ``mamba_dt_bc``: x_proj, the three inner norms, dt_proj,
``softplus``) and ``attn/out`` (``mamba_gate``: ``y * silu(z)`` with the
scan's relayout; ``mamba_out``: the out projection and the residual add).
``scope_reduce.scope_of`` knows the model scopes only, which keeps its
``scopes:`` table and identity whole; this file reads the part itself,
from the same trace file and the same optimized HLO inside it, by
``scope_reduce.classify``'s rule (``moe_reduce.op_name_of``).  Every phase
counts: forward, backward and what ``jax.checkpoint`` recomputes.

On a program without these scopes (another model, or a commit from before
them) every function here returns None.
"""

from __future__ import annotations

import collections
import re
from typing import Dict, Optional, Sequence

from perfbench import moe_reduce, scope_reduce

SCAN = "mamba_scan"
PROJ_PARTS = ("mamba_proj", "mamba_dt_bc", "mamba_out")
PARTS = ("mamba_proj", "mamba_conv", "mamba_dt_bc", SCAN, "mamba_gate",
         "mamba_out")

# A part is a whole component of the path.
_PART = re.compile(r"(?:^|(?<=[/(]))(" + "|".join(PARTS) + r")(?=$|[/)])")


def part_of(op_name: str) -> Optional[str]:
    """The innermost of ``PARTS`` an ``op_name`` lies in, if any."""
    found = _PART.findall(op_name)
    return found[-1] if found else None


def attribute(op_s: Dict[str, float], hlo) -> Dict[str, float]:
    """Seconds by part, from ``trace_reduce``'s ``op_s``."""
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
    and printed; None where there is no trace, no HLO in it, or none of
    these parts in the HLO."""
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
            print("Mamba-1 parts: ms per step on one device, every phase: "
                  + ", ".join(f"{k} {parts.get(k, 0.0) * ms:.3f}"
                              for k in PARTS), flush=True)
        _MEMO[key] = parts
    return _MEMO[key]


def part_ms(ctx, parts: Sequence[str]) -> Optional[float]:
    """Milliseconds per step in the named parts, every phase; None where
    the program opens none of this file's parts."""
    found = for_ctx(ctx)
    if found is None:
        return None
    return (sum(found.get(p, 0.0) for p in parts) * 1e3
            / ctx["trace_steps"])
