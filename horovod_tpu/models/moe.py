"""Dropless mixture-of-experts feed-forward layer, every expert on this
chip: what :mod:`horovod_tpu.models.transformer` runs in place of its
dense MLP when ``TransformerConfig.n_experts`` is set.

The layer, as OLMoE (arXiv:2409.02060; HF ``modeling_olmoe.py``) states
it: router logits ``r = h @ W_r`` and ``softmax(r)`` in float32 over all
experts, the ``k`` largest probabilities and their experts (not
renormalised unless ``norm_topk_prob``), expert ``e`` is
``W_down,e (silu(W_gate,e h) * (W_up,e h))``, and the output is
``sum_k p_k expert_{i_k}(h)``.  **Every assignment is computed**: there is
no capacity and no dropped token, and an expert with no token is legal.

How: the ``N * k`` (token, expert) assignments are put in expert order by
one stable sort, the tokens' rows gathered in that order, and the three
expert matmuls run as *grouped* matmuls over the ragged groups
(:mod:`horovod_tpu.ops.grouped_matmul`; the candidates that lost are in
PERF.md, PR 26).
Group sizes are taken as they come: nothing is padded to a capacity and
nothing assumes balance.  Both row moves are gathers in both directions
(:func:`_gather_rows`): the backward pass of "gather the sorted rows" is
"gather them back and add", never a scatter.

Not here: experts over a mesh axis (ROADMAP R2).  The one-expert-per-chip,
capacity-dropping ``all_to_all`` demo is :mod:`horovod_tpu.parallel.expert`.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu import telemetry
from horovod_tpu.ops.grouped_matmul import (grouped_matmul,
                                            worst_matmul_rows)
from horovod_tpu.telemetry import scopes


class RouterStats(NamedTuple):
    """What one layer's router hands the auxiliary losses: sums over its
    local tokens, so layers add."""

    prob_sum: jax.Array      # [E] f32: sum over tokens of softmax(r)
    counts: jax.Array        # [E] int32: assignments per expert
    z_sum: jax.Array         # [] f32: sum over tokens of logsumexp(r)^2


def route(h, router_w, k: int, norm_topk_prob: bool):
    """``h`` [N, d], ``router_w`` [d, E] -> ``(top_p [N, k] f32, top_i
    [N, k] int32, RouterStats)``.  Logits, softmax and top-k in float32
    (the matmul at precision ``highest``: it is 0.1% of the layer's FLOPs,
    and a bf16 router flips choices between near-equal experts).  Of equal
    probabilities the lower expert index wins (:func:`jax.lax.top_k`)."""
    logits = jnp.dot(h.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = lax.top_k(probs, k)
    if norm_topk_prob:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    counts = jnp.sum(
        top_i.reshape(-1, 1) == jnp.arange(router_w.shape[1])[None, :],
        axis=0, dtype=jnp.int32)
    z = jax.nn.logsumexp(logits, axis=-1)
    return top_p, top_i, RouterStats(jnp.sum(probs, axis=0), counts,
                                     jnp.sum(z * z))


def router_losses(stats: Sequence[RouterStats], tokens: int,
                  batch_axes: Sequence[str] = ()):
    """``(load_balancing, z)`` over all layers' ``tokens`` (local tokens
    x layers) together.

    Load balancing is the Switch loss as HF computes it
    (``load_balancing_loss_func``): ``E * sum_e f_e P_e`` with ``P_e`` the
    mean router probability of expert ``e`` and ``f_e`` the assignments to
    ``e`` per token (so ``sum_e f_e = k`` and a uniform router reads
    ``k``).  ``z`` is the mean of ``logsumexp(r)^2`` (ST-MoE).

    Inside a ``shard_map`` whose ``batch_axes`` split the batch, ``f_e``
    is averaged over them (it carries no gradient), so the mean of the
    shards' losses *is* the global batch's loss, and the mean of their
    gradients its gradient: ``f_e P_e`` is a product of two means, and
    the mean of local products would be another function."""
    prob = sum(s.prob_sum for s in stats) / tokens
    freq = sum(s.counts for s in stats).astype(jnp.float32) / tokens
    if batch_axes:
        freq = lax.pmean(freq, tuple(batch_axes))
    experts = prob.shape[0]
    return experts * jnp.sum(freq * prob), sum(s.z_sum for s in stats) / tokens


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gather_rows(x, src, back, uses: int):
    """``x[src]`` where every row of ``x`` is read exactly ``uses`` times
    and ``back.reshape(len(x), uses)`` lists, per row of ``x``, the
    output rows that read it.  Its gradient is then a gather too
    (``g[back]``, summed over the uses), where autodiff of ``x[src]``
    would make a scatter-add."""
    del back, uses
    return _take(x, src)


def _take(x, rows):
    # The indices come from a sort of iota: in bounds by construction, so
    # no pass over the result to fill rows that could not be read.
    return x.at[rows].get(mode="promise_in_bounds")


def _gather_rows_fwd(x, src, back, uses):
    return _take(x, src), (src, back)


def _gather_rows_bwd(uses, residuals, g):
    _, back = residuals
    rows = _take(g, back)
    if uses > 1:
        rows = jnp.sum(rows.reshape(-1, uses, g.shape[-1]).astype(
            jnp.float32), axis=1).astype(g.dtype)
    return rows, None, None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


# The three matrices of every expert, [E, d, f], [E, d, f] and [E, f, d].
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _expert_operands(layer):
    """The :data:`EXPERT_LEAVES` as the grouped matmuls are handed them:
    as they are stored.  The kernels round a group's block to the rows'
    dtype in VMEM; an ``.astype`` here is a second copy of every expert in
    HBM, written each step, kept for the backward pass and read twice
    (PERF.md, PR 30), and :func:`record_weight_copies` counts it."""
    return tuple(layer[name] for name in EXPERT_LEAVES)


def experts_ffn(h, top_p, top_i, group_sizes, layer, dtype):
    """``sum_k top_p[:, k] * expert_{top_i[:, k]}(h)``: ``h`` [N, d] ->
    [N, d] in ``dtype``.  ``group_sizes`` [E] int32: assignments per
    expert (``RouterStats.counts``).  ``layer`` holds ``w_gate``, ``w_up``
    [E, d, f] and ``w_down`` [E, f, d] in the dtype they are stored in."""
    n, k = top_i.shape
    with jax.named_scope(scopes.MOE_DISPATCH):
        flat = top_i.reshape(-1)
        # order[j]: the assignment (token * k + slot) at sorted place j;
        # place[a]: where assignment a went.  Stable, so an expert's rows
        # keep token order.  The counts are the router's.
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        place = jnp.zeros_like(order).at[order].set(
            jnp.arange(n * k, dtype=jnp.int32), unique_indices=True)
        rows = _gather_rows(h, order // k, place, k)
    with jax.named_scope(scopes.MOE_EXPERTS):
        w_gate, w_up, w_down = _expert_operands(layer)
        gate = grouped_matmul(rows, w_gate, group_sizes)
        up = grouped_matmul(rows, w_up, group_sizes)
        act = (jax.nn.silu(gate.astype(jnp.float32))
               * up.astype(jnp.float32)).astype(dtype)
        out = grouped_matmul(act, w_down, group_sizes)
    with jax.named_scope(scopes.MOE_COMBINE):
        per_token = _gather_rows(out, place, order, 1).reshape(n, k, -1)
        return jnp.sum(per_token.astype(jnp.float32) * top_p[..., None],
                       axis=1).astype(dtype)


def moe_ffn(h, layer, cfg):
    """The whole layer on ``h`` [..., d]: ``(y [..., d], RouterStats)``.
    ``cfg`` is the model's ``TransformerConfig``."""
    flat = h.reshape(-1, h.shape[-1])
    with jax.named_scope(scopes.MOE_ROUTER):
        top_p, top_i, stats = route(flat, layer["router"],
                                    cfg.experts_per_token,
                                    cfg.norm_topk_prob)
    y = experts_ffn(flat, top_p, top_i, stats.counts, layer, cfg.dtype)
    return y.reshape(h.shape), stats


def record_assignments(layer: int, assignments: int, experts: int) -> None:
    """Trace-time series (like ``hvd_flash_blocks_total``: what was
    compiled into the step, not per-step traffic): the (token, expert)
    assignments layer ``layer`` computes per step on one device — static,
    ``tokens * experts_per_token``, since nothing is dropped — and the
    most rows the grouped matmuls can multiply for one they need, which
    is all that is static of it: how the assignments fall on the
    ``experts`` is data (``grouped_matmul.matmul_rows``)."""
    if not telemetry.enabled():
        return
    telemetry.counter(
        "hvd_moe_assignments_total",
        "(token, expert) assignments the traced MoE layer computes per "
        "step on one device; dropless, so tokens x experts_per_token",
        layer=str(layer)).inc(assignments)
    telemetry.gauge(
        "hvd_moe_gmm_rows_computed_over_needed",
        "Rows the most recently traced MoE layer's grouped matmuls "
        "multiply over the rows they need, at worst: every expert but "
        "the first starts inside a sub-tile (1.0 = no masked work)",
        bound="worst").set(
            worst_matmul_rows(experts, assignments) / assignments)


def record_weight_copies(layer: int, weights) -> None:
    """Trace-time gauge: the bytes of expert weights layer ``layer``
    (``weights``: its parameters) holds a second time because
    :func:`experts_ffn` hands the grouped matmuls a cast and not the
    stored leaf.  0 since the kernels round in VMEM; 2 bytes a parameter
    before."""
    if not telemetry.enabled():
        return
    handed = jax.eval_shape(_expert_operands, weights)
    telemetry.gauge(
        "hvd_moe_expert_weight_copy_bytes",
        "Bytes of expert weights the traced MoE layer materialises in the "
        "compute dtype outside the grouped-matmul kernels (0 = the "
        "kernels read the stored parameters)",
        layer=str(layer)).set(sum(
            h.size * h.dtype.itemsize
            for h, name in zip(handed, EXPERT_LEAVES)
            if h.dtype != weights[name].dtype))
