"""Device time of a Nemotron-3-style step by part: the Mamba-2 mixers,
the latent mixture of experts and the multi-token-prediction module.

The program opens (``horovod_tpu/telemetry/scopes.py``): the state-space
recurrence as a route of its own, ``attn/ssm_scan``, and the mixer's other
parts as bare path components under ``attn/qkv`` (``ssm_proj``: norm, the
in-projection, the step and the decay; ``ssm_conv``: the causal
convolution with its bias, ``silu``, the split) and ``attn/out``
(``ssm_gate_norm``: the gated group norm; ``ssm_out``: the out projection
and the residual add); under ``mlp``, beside PR 26's ``moe_router``,
``moe_dispatch``, ``moe_experts`` and ``moe_combine``, ``moe_latent`` (the
projections into and out of the experts' width) and ``moe_shared`` (the
shared expert); and ``mtp``, a bare component that holds the prediction
module's own ``embed``, ``layer_<i>/...``, ``head`` and ``loss``.
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

SSM_PARTS = ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_gate_norm", "ssm_out")
ROUTED_PARTS = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
SHARED_PARTS = ("moe_latent", "moe_shared")
MTP = "mtp"
PARTS = SSM_PARTS + ROUTED_PARTS + SHARED_PARTS


def _component(*names):
    # A part is a whole component of the path.
    return re.compile(r"(?:^|(?<=[/(]))(" + "|".join(names)
                      + r")(?=$|[/)])")


_PART, _MTP = _component(*PARTS), _component(MTP)


def parts_of(op_name: str):
    """The parts an ``op_name`` lies in: the innermost of ``PARTS`` if
    any, and ``MTP`` if the prediction module holds it."""
    found = _PART.findall(op_name)
    return found[-1:] + ([MTP] if _MTP.search(op_name) else [])


def attribute(op_s: Dict[str, float], hlo) -> Dict[str, float]:
    """Seconds by part, from ``trace_reduce``'s ``op_s``.  ``MTP``
    overlaps the others: the module's layers have parts too."""
    parts: Dict[str, float] = collections.Counter()
    for key, seconds in op_s.items():
        name = key.split(" ", 1)[0].lstrip("%")
        for part in parts_of(moe_reduce.op_name_of(name, hlo)):
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
        if not set(parts).intersection(SSM_PARTS + SHARED_PARTS + (MTP,)):
            parts = None
        else:
            ms = 1e3 / ctx["trace_steps"]
            print("state-space, expert and prediction parts: ms per step "
                  "on one device, every phase (mtp overlaps the others): "
                  + ", ".join(f"{k} {parts.get(k, 0.0) * ms:.3f}"
                              for k in PARTS + (MTP,)), flush=True)
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
