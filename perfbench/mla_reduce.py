"""Device time of a GLM-4.7-Flash-style step by part: latent attention's
projections, the leading dense MLP, the sigmoid-routed expert layers and
the multi-token-prediction module.

The program opens (``horovod_tpu/telemetry/scopes.py``), as bare path
components: under ``attn/qkv``, ``mla_q`` (the query's way through its
latent: down-projection, norm, up-projection), ``mla_kv`` (the keys' and
values': down-projection to the latent and the rotary key, norm,
up-projection) and ``mla_rope`` (both rotations and the concatenations
that put a head together); under ``mlp``, ``mlp_dense`` around a leading
dense layer's MLP, and PR 26's ``moe_router``, ``moe_dispatch``,
``moe_experts``, ``moe_combine`` with PR 33's ``moe_shared`` in an expert
layer; and ``mtp``, a bare component that holds the prediction module's
own ``embed``, ``layer_<i>/...``, ``head`` and ``loss``.
``scope_reduce.scope_of`` knows the model scopes only, which keeps its
``scopes:`` table and identity whole; this file reads the part itself,
from the same trace file and the same optimized HLO inside it, by
``scope_reduce.classify``'s rule (``moe_reduce.op_name_of``).  Every phase
counts: forward, backward and what ``jax.checkpoint`` recomputes.

On a program without latent attention's scopes (another model, or a
commit from before them) every function here returns None.
"""

from __future__ import annotations

import collections
import re
from typing import Dict, Optional, Sequence

from perfbench import moe_reduce, scope_reduce

MLA_PARTS = ("mla_q", "mla_kv", "mla_rope")
DENSE = "mlp_dense"
ROUTED_PARTS = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
SHARED_PARTS = ("moe_shared",)
MTP = "mtp"
PARTS = MLA_PARTS + (DENSE,) + ROUTED_PARTS + SHARED_PARTS
# The model scopes of an attention half and of a feed-forward half.
ATTENTION_SCOPES = ("attn/qkv", "attn/flash_attention", "attn/out")
PROJECTION_SCOPES = ("attn/qkv", "attn/out")
FLASH_SCOPE = ("attn/flash_attention",)


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
    overlaps the others: the module's layer has parts too."""
    parts: Dict[str, float] = collections.Counter()
    for key, seconds in op_s.items():
        name = key.split(" ", 1)[0].lstrip("%")
        for part in parts_of(moe_reduce.op_name_of(name, hlo)):
            parts[part] += seconds
    return dict(parts)


_MEMO: Dict[int, Optional[Dict[str, float]]] = {}


def for_ctx(ctx) -> Optional[Dict[str, float]]:
    """:func:`attribute` of the run's trace, made once for all readers
    and printed; None where there is no trace, no HLO in it, or no latent
    attention in the HLO."""
    reduced = ctx.get("reduced")
    if not reduced:
        return None
    key = id(reduced)
    if key not in _MEMO:
        path = scope_reduce._trace_file(ctx)
        texts = scope_reduce.trace_hlo(path) if path else []
        parts = (attribute(reduced["op_s"], scope_reduce.parse_hlo(*texts))
                 if texts else {})
        if not set(parts).intersection(MLA_PARTS):
            parts = None
        else:
            ms = 1e3 / ctx["trace_steps"]
            print("latent-attention, dense, expert and prediction parts: "
                  "ms per step on one device, every phase (mtp overlaps "
                  "the others): "
                  + ", ".join(f"{k} {parts.get(k, 0.0) * ms:.3f}"
                              for k in PARTS + (MTP,)), flush=True)
        _MEMO[key] = parts
    return _MEMO[key]


def part_ms(ctx, parts: Sequence[str]) -> Optional[float]:
    """Milliseconds per step in the named parts, every phase; None where
    the program has no latent attention."""
    found = for_ctx(ctx)
    if found is None:
        return None
    return (sum(found.get(p, 0.0) for p in parts) * 1e3
            / ctx["trace_steps"])


def scope_ms(ctx, names: Sequence[str]) -> Optional[float]:
    """``scope_reduce.scope_ms`` of the model scopes ``names``, in a
    program with latent attention; None in any other."""
    if for_ctx(ctx) is None:
        return None
    return scope_reduce.scope_ms(ctx, names)
