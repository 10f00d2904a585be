"""Device time of a Keye-VL-2.0-style step by part: the attention half's
projections, the indexer, the selection, the attention kernels under the
mask, the indexer's loss, and the softmax-routed expert layers.

The program opens (``horovod_tpu/telemetry/scopes.py``), as bare path
components: under ``attn/qkv``, ``dsa_index_proj`` (the indexer's three
projections and its rotation) and ``qk_head_norm_rope`` (the per-head norm
and the rotation of q and k); under ``attn/flash_attention``, the route,
``dsa_index_scores`` (the scores' kernel, and its gradient's),
``dsa_select`` (the bisection kernel and the mask's transpose),
``dsa_flash`` (the three attention kernels under the mask and the moves
around them) and ``dsa_index_loss`` (the head-mean probabilities' kernel
and the KL); under ``mlp``, PR 26's ``moe_router``, ``moe_dispatch``,
``moe_experts``, ``moe_combine``.  ``scope_reduce.scope_of`` knows the
model scopes only, which keeps its ``scopes:`` table and identity whole;
this file reads the part itself, from the same trace file and the same
optimized HLO inside it, by ``scope_reduce.classify``'s rule
(``moe_reduce.op_name_of``).  Every phase counts: forward, backward and
what ``jax.checkpoint`` recomputes.

On a program without sparse attention's scopes (another model, or a
commit from before them) every function here returns None.
"""

from __future__ import annotations

import collections
import re
from typing import Dict, Optional, Sequence

from perfbench import moe_reduce, scope_reduce

INDEX_PROJ = "dsa_index_proj"
QK_HEAD_NORM_ROPE = "qk_head_norm_rope"
INDEX_SCORES = "dsa_index_scores"
SELECT = "dsa_select"
FLASH = "dsa_flash"
INDEX_LOSS = "dsa_index_loss"
DSA_PARTS = (INDEX_PROJ, QK_HEAD_NORM_ROPE, INDEX_SCORES, SELECT, FLASH,
             INDEX_LOSS)
ROUTED_PARTS = ("moe_router", "moe_dispatch", "moe_experts", "moe_combine")
PARTS = DSA_PARTS + ROUTED_PARTS
# The model scopes of an attention half, and those around its route.
ATTENTION_SCOPES = ("attn/qkv", "attn/flash_attention", "attn/out")
PROJECTION_SCOPES = ("attn/qkv", "attn/out")
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
    and printed; None where there is no trace, no HLO in it, or no sparse
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
        if not set(parts).intersection(DSA_PARTS):
            parts = None
        else:
            ms = 1e3 / ctx["trace_steps"]
            print("sparse-attention and expert parts: ms per step on one "
                  "device, every phase: "
                  + ", ".join(f"{k} {parts.get(k, 0.0) * ms:.3f}"
                              for k in PARTS), flush=True)
        _MEMO[key] = parts
    return _MEMO[key]


def part_ms(ctx, parts: Sequence[str]) -> Optional[float]:
    """Milliseconds per step in the named parts, every phase; None where
    the program has no sparse attention."""
    found = for_ctx(ctx)
    if found is None:
        return None
    return (sum(found.get(p, 0.0) for p in parts) * 1e3
            / ctx["trace_steps"])


def scope_ms(ctx, names: Sequence[str]) -> Optional[float]:
    """``scope_reduce.scope_ms`` of the model scopes ``names``, in a
    program with sparse attention; None in any other."""
    if for_ctx(ctx) is None:
        return None
    return scope_reduce.scope_ms(ctx, names)


def kernel_roofline(ctx, kernel: str, label: str) -> Optional[float]:
    """The share of its roofline of the cell's ``kernel`` (an entry of
    ``Cell.kernels``: its cost by ``perfbench.kernel_cost_dsa``, its time
    the trace's events that match it, recomputation included in the time
    and not in the need), in percent; None where there is nothing to
    read."""
    from perfbench import kernel_cost
    from perfbench.peaks import peak

    seconds = (ctx.get("reduced") or {}).get("kernel_s", {}).get(kernel)
    if not seconds or for_ctx(ctx) is None:
        return None
    cost = ctx["cell"].kernels.get(kernel)
    if not cost:
        return None
    ideal, bound = kernel_cost.roofline_seconds(
        cost, peak(ctx["peaks"], "bf16_flops_per_s"),
        peak(ctx["peaks"], "hbm_bytes_per_s"))
    taken_ms = seconds * 1e3 / ctx["trace_steps"]
    print(f"{label}: {bound}-bound, least {ideal * 1e3:.3f} ms per step "
          f"against {taken_ms:.3f} ms taken", flush=True)
    return 100.0 * ideal * 1e3 / taken_ms
