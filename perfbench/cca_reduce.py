"""Device time of a ZAYA1-style step by part: compressed convolutional
attention (its projections, its mix and norm, the flash kernels), the MLP
router with its carried state, the one-of-sixteen experts and the skip, the
scaled residual merges, and the head with the loss.

The program opens (``horovod_tpu/telemetry/scopes.py``), as bare path
components: under ``attn/qkv``, ``cca_mix`` (both convolutions, the mean,
the value shift) and ``cca_norm_rope`` (the L2 norm, the temperature, the
half rotation, the head layout); under ``mlp``, PR 26's ``moe_router``
(with ``router_state`` and ``router_mlp`` inside it), ``moe_dispatch``,
``moe_experts``, ``moe_combine`` and beside them ``moe_skip``; under
``attn/out`` and under ``mlp``, ``res_scale``, the scaled merge.
``scope_reduce.scope_of`` knows the model scopes only, which keeps its
``scopes:`` table and identity whole; this file reads one level further,
from the same trace file and the same optimized HLO inside it, by
``scope_reduce.classify``'s rule (``moe_reduce.op_name_of``).  Every phase
counts: forward, backward and what ``jax.checkpoint`` recomputes.

Every executed op lands in exactly one of :data:`PARTS`:

``flash``    the three flash kernels (forward, its recomputation, dQ, dK+dV)
``mix``      under ``cca_mix`` or ``cca_norm_rope``, and what lies under
             ``attn/flash_attention`` outside the kernels (K and V repeated
             for the group, the kernels' layouts)
``proj``     the rest under ``attn/*``: the five projections' matmuls and
             the first norm
``router``   under ``mlp`` and ``moe_router``: the state, the MLP, softmax,
             choice, the share's bookkeeping
``experts``  the rest under ``mlp``: the row moves, the grouped matmuls,
             the weighting, the skip, the second norm
``merge``    the ``res_scale`` components, of both sub-layers
``head``     ``head`` and ``loss``
``other``    everything else: the embedding, the gradient mean, the
             update, and what no rule places

On a program without ``cca_mix`` (another model, or a commit from before
it) every function here returns None.
"""

from __future__ import annotations

import collections
import re
from typing import Dict, Optional, Sequence

from perfbench import moe_reduce, scope_reduce

PARTS = ("flash", "mix", "proj", "router", "experts", "merge", "head",
         "other")
CCA = ("flash", "mix", "proj")
# The seven that share the step's model time out between them; with the
# embedding they are its forward, backward and recomputation.
MODEL = CCA + ("router", "experts", "merge", "head")


def _component(*names):
    return re.compile(r"(?:^|(?<=[/(]))(?:" + "|".join(names)
                      + r")(?=$|[/)])")


_MIX = _component("cca_mix", "cca_norm_rope")
_MERGE = _component("res_scale")
_ROUTER = _component("moe_router")
MARK = "cca_mix"


def part_of(name: str, hlo) -> str:
    """The one of :data:`PARTS` the executed instruction ``name`` lies
    in, by the ``op_name`` it is booked by; under ``attn/*`` the flash
    kernels are told from the rest by the instruction itself."""
    op_name = moe_reduce.op_name_of(name, hlo)
    scope = scope_reduce.scope_of(op_name)
    if scope in ("head", "loss"):
        return "head"
    if _MERGE.search(op_name) and (scope == "mlp"
                                   or scope.startswith("attn/")):
        return "merge"
    if scope == "mlp":
        return "router" if _ROUTER.search(op_name) else "experts"
    if not scope.startswith("attn/"):
        return "other"
    executed = hlo.instructions[name]
    if executed.opcode == "custom-call" and scope_reduce._KERNEL.search(
            executed.op_name):
        return "flash"
    if _MIX.search(op_name) or scope == "attn/flash_attention":
        return "mix"
    return "proj"


def attribute(op_s: Dict[str, float], hlo) -> Dict[str, float]:
    """Seconds by part, from ``trace_reduce``'s ``op_s``; every op in
    exactly one part."""
    parts: Dict[str, float] = collections.Counter()
    for key, seconds in op_s.items():
        name = key.split(" ", 1)[0].lstrip("%")
        parts[part_of(name, hlo) if name in hlo.instructions
              else "other"] += seconds
    return dict(parts)


_MEMO: Dict[int, Optional[Dict[str, float]]] = {}


def for_ctx(ctx) -> Optional[Dict[str, float]]:
    """:func:`attribute` of the run's trace, made once for all readers
    and printed beside ``scope_reduce``'s own model time; None where there
    is no trace, no HLO in it, or no ``cca_mix`` in the HLO."""
    reduced = ctx.get("reduced")
    if not reduced:
        return None
    key = id(reduced)
    if key not in _MEMO:
        path = scope_reduce._trace_file(ctx)
        texts = scope_reduce.trace_hlo(path) if path else []
        parts = None
        if any(MARK in text for text in texts):
            parts = attribute(reduced["op_s"], scope_reduce.parse_hlo(*texts))
            ms = 1e3 / ctx["trace_steps"]
            model = sum(parts.get(k, 0.0) for k in MODEL) * ms
            phases = [scope_reduce.phase_ms(ctx, phase) or 0.0
                      for phase in ("fwd", "bwd", "remat")]
            embed = scope_reduce.scope_ms(ctx, ("embed",)) or 0.0
            print("cca and zaya parts: ms per step on one device, every "
                  "phase: " + ", ".join(f"{k} {parts.get(k, 0.0) * ms:.3f}"
                                        for k in PARTS)
                  + f"; the seven model parts {model:.3f} + embed "
                  f"{embed:.3f} against fwd + bwd + remat "
                  f"{sum(phases):.3f}", flush=True)
        _MEMO[key] = parts
    return _MEMO[key]


def part_ms(ctx, parts: Sequence[str]) -> Optional[float]:
    """Milliseconds per step in the named parts, every phase; None where
    the program runs no compressed convolutional attention."""
    found = for_ctx(ctx)
    if found is None:
        return None
    return (sum(found.get(p, 0.0) for p in parts) * 1e3
            / ctx["trace_steps"])


def moe_part_ms(ctx, sub_scopes: Sequence[str]) -> Optional[float]:
    """``moe_reduce.part_ms`` of the ``mlp`` sub-scopes named, in a program
    that runs compressed convolutional attention; None in any other."""
    if for_ctx(ctx) is None:
        return None
    return moe_reduce.part_ms(ctx, sub_scopes)
