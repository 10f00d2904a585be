"""Device time of a looped step by part: a stack run several times on the
same weights, sandwich-normed, with a readout after every pass.

The program opens (``horovod_tpu/telemetry/scopes.py``): ``loop_<t>``
around pass ``t``, under which every layer's model scopes nest;
``loop_norm``, right under it, the final norm after every pass; as bare
path components ``post_norm`` under ``attn/out`` and under ``mlp`` (the
sandwich's second norm), ``exit_gate`` under ``head`` and ``exit_mix``
under ``loss``.  ``scope_reduce.scope_of`` knows the model scopes only,
which keeps its ``scopes:`` table and identity whole; this file reads one
level further, from the same trace file and the same optimized HLO inside
it, by ``scope_reduce.classify``'s rule (``moe_reduce.op_name_of``).
Every phase counts: forward, backward and what ``jax.checkpoint``
recomputes.

Every executed op lands in exactly one of :data:`PARTS`:

``flash``    the three flash kernels (forward, its recomputation, dQ, dK+dV)
``qk_glue``  under ``attn/qkv`` and ``attn/flash_attention`` outside
             matmuls and kernels: rotary, the head split, layouts
``attn``     the rest under ``attn/*``: the projections' matmuls, the
             first norm, the residual add
``mlp``      under ``mlp`` outside its ``post_norm``
``norm``     the ``post_norm`` components and ``loop_norm``
``exit``     ``exit_gate`` and ``exit_mix``
``head``     the rest of ``head`` and ``loss``: four readouts and their
             cross-entropies
``carry``    under a pass's scope and under no model scope, and the adds
             under no scope at all that sum a shared leaf's gradient
             over the passes
``other``    everything else: the embedding, the gradient mean, the
             update, and what no rule places

On a program without a looped stack (another model, or a commit from
before it) every function here returns None.
"""

from __future__ import annotations

import collections
import re
from typing import Dict, Optional, Sequence

from perfbench import moe_reduce, scope_reduce

PARTS = ("flash", "qk_glue", "attn", "mlp", "norm", "exit", "head", "carry",
         "other")
ATTN = ("flash", "qk_glue", "attn")
HEAD = ("exit", "head")
# The eight that share the step's model time out between them; with the
# embedding they are its forward, backward and recomputation.
MODEL = ATTN + ("mlp", "norm") + HEAD + ("carry",)


def _component(*names):
    return re.compile(r"(?:^|(?<=[/(]))(?:" + "|".join(names)
                      + r")(?=$|[/)])")


_LOOP = _component(r"loop_\d+")
_NORM = _component("post_norm", "loop_norm")
_EXIT = _component("exit_gate", "exit_mix")
# The sum of a shared leaf's partial gradients stands under no scope.
_SUM = re.compile(r"transpose\(.*/add_any$")
_GLUE_SCOPES = ("attn/qkv", "attn/flash_attention")


def part_of_name(op_name: str) -> str:
    """The part an ``op_name`` lies in as far as the name says: ``norm``,
    ``exit``, ``head``, ``attn`` (all of ``attn/*``), ``mlp``, ``carry``
    or ``other``."""
    scope = scope_reduce.scope_of(op_name)
    if _NORM.search(op_name):
        return "norm"
    if _EXIT.search(op_name):
        return "exit"
    if scope in ("head", "loss"):
        return "head"
    if scope.startswith("attn/"):
        return "attn"
    if scope == "mlp":
        return "mlp"
    if scope in ("", "layer") and (_LOOP.search(op_name)
                                   or _SUM.search(op_name)):
        return "carry"
    return "other"


def part_of(name: str, hlo) -> str:
    """The one of :data:`PARTS` the executed instruction ``name`` lies
    in: :func:`part_of_name` of the ``op_name`` it is booked by, and under
    ``attn/*`` the flash kernels and the glue told from the rest by the
    instruction itself."""
    op_name = moe_reduce.op_name_of(name, hlo)
    part = part_of_name(op_name)
    if part != "attn":
        return part
    executed = hlo.instructions[name]
    if executed.opcode == "custom-call":
        return "flash" if scope_reduce._KERNEL.search(
            executed.op_name) else "attn"
    inside = (scope_reduce._fused(executed, hlo)
              if executed.opcode == "fusion" else [executed])
    matmul = any(i.opcode in ("dot", "convolution") for i in inside)
    glue = scope_reduce.scope_of(op_name) in _GLUE_SCOPES and not matmul
    return "qk_glue" if glue else "attn"


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
    is no trace, no HLO in it, or no looped stack in the HLO."""
    reduced = ctx.get("reduced")
    if not reduced:
        return None
    key = id(reduced)
    if key not in _MEMO:
        path = scope_reduce._trace_file(ctx)
        texts = scope_reduce.trace_hlo(path) if path else []
        parts = None
        if any("loop_norm" in text for text in texts):
            parts = attribute(reduced["op_s"], scope_reduce.parse_hlo(*texts))
            ms = 1e3 / ctx["trace_steps"]
            model = sum(parts.get(k, 0.0) for k in MODEL) * ms
            phases = [scope_reduce.phase_ms(ctx, phase) or 0.0
                      for phase in ("fwd", "bwd", "remat")]
            embed = scope_reduce.scope_ms(ctx, ("embed",)) or 0.0
            print("looped parts: ms per step on one device, every phase: "
                  + ", ".join(f"{k} {parts.get(k, 0.0) * ms:.3f}"
                              for k in PARTS)
                  + f"; the eight model parts {model:.3f} + embed "
                  f"{embed:.3f} against fwd + bwd + remat "
                  f"{sum(phases):.3f}", flush=True)
        _MEMO[key] = parts
    return _MEMO[key]


def part_ms(ctx, parts: Sequence[str]) -> Optional[float]:
    """Milliseconds per step in the named parts, every phase; None where
    the program runs no looped stack."""
    found = for_ctx(ctx)
    if found is None:
        return None
    return (sum(found.get(p, 0.0) for p in parts) * 1e3
            / ctx["trace_steps"])
