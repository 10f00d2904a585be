"""Device time of an SDAR-style block-diffusion step by part: the
assembly of the two streams, the attention halves, the flash kernels under
the block-diffusion mask and the glue around them, the softmax-routed
expert layers, and the head with the loss over the noised half.

The program opens (``horovod_tpu/telemetry/scopes.py``), as bare path
components: under ``embed``, ``diffusion_assemble`` (the noised copy beside
the clean sequence, the repeated positions, the loss's weights); under
``attn/qkv``, ``qk_head_norm_rope``; under ``mlp``, PR 26's ``moe_router``,
``moe_dispatch``, ``moe_experts``, ``moe_combine``.  ``scope_reduce``
knows the model scopes only; this file reads the part itself, from the
same trace file and the same optimized HLO inside it, by
``scope_reduce.classify``'s rule (``moe_reduce.op_name_of``).  Every phase
counts: forward, backward and what ``jax.checkpoint`` recomputes.

On a program whose step holds no ``diffusion_assemble`` (another model, or
a commit from before it) every function here returns None.
"""

from __future__ import annotations

import collections
import re
from typing import Dict, Optional, Sequence

from perfbench import moe_reduce, scope_reduce

ASSEMBLE = "diffusion_assemble"
QK_HEAD_NORM_ROPE = "qk_head_norm_rope"
ROUTED_PARTS = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
PARTS = (ASSEMBLE, QK_HEAD_NORM_ROPE) + ROUTED_PARTS
ATTENTION_SCOPES = ("attn/qkv", "attn/flash_attention", "attn/out")
FLASH_SCOPE = ("attn/flash_attention",)
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
    """:func:`attribute` of the run's trace, made once for all readers and
    printed; None where there is no trace, no HLO in it, or no
    ``diffusion_assemble`` anywhere in the HLO (XLA may fuse all of it
    into an op that another scope names: the part then reads 0, which is
    what the metric guards)."""
    reduced = ctx.get("reduced")
    if not reduced:
        return None
    key = id(reduced)
    if key not in _MEMO:
        path = scope_reduce._trace_file(ctx)
        texts = scope_reduce.trace_hlo(path) if path else []
        parts = None
        if any(ASSEMBLE in text for text in texts):
            parts = attribute(reduced["op_s"], scope_reduce.parse_hlo(*texts))
            ms = 1e3 / ctx["trace_steps"]
            print("block-diffusion and expert parts: ms per step on one "
                  "device, every phase: "
                  + ", ".join(f"{k} {parts.get(k, 0.0) * ms:.3f}"
                              for k in PARTS), flush=True)
        _MEMO[key] = parts
    return _MEMO[key]


def part_ms(ctx, parts: Sequence[str]) -> Optional[float]:
    """Milliseconds per step in the named parts, every phase; None where
    the program runs no block diffusion."""
    found = for_ctx(ctx)
    if found is None:
        return None
    return (sum(found.get(p, 0.0) for p in parts) * 1e3
            / ctx["trace_steps"])


def scope_ms(ctx, names: Sequence[str]) -> Optional[float]:
    """``scope_reduce.scope_ms`` of the model scopes ``names``, in a
    program that runs block diffusion; None in any other."""
    if for_ctx(ctx) is None:
        return None
    return scope_reduce.scope_ms(ctx, names)


def flash_kernels_ms(ctx) -> Optional[float]:
    """Milliseconds per step in the three flash kernels (forward, its
    recomputation, dQ, dK+dV), found by the name the program's scopes give
    them whatever number XLA gave the instruction; None where the program
    runs no block diffusion or the trace holds none of them."""
    if for_ctx(ctx) is None:
        return None
    kernels = [scope_reduce.kernel_ms(ctx, k)
               for k in scope_reduce.KERNEL_NAMES]
    return sum(k for k in kernels if k is not None) or None
