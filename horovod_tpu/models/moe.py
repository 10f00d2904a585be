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

**One chip's share of a wider layer** (``TransformerConfig.experts_held``
fewer than ``n_experts``): the router still scores all ``n_experts`` and
keeps its ``k``; this chip computes the rows of the contiguous range it
holds and leaves the rest out (:func:`this_chips_share`, for either
router and either expert form): no exchange, and
nothing stands in for the absent chips.  Shapes stay static: a token can
land here at most ``min(k, held)`` times, so a row buffer of ``N *
min(k, held)`` bounds every batch, the grouped matmuls visit only the
tiles that held rows reach, and **nothing held is ever dropped**.

**The latent layer** (:func:`latent_moe_ffn`; DeepSeek-V3's scoring with
Nemotron-3's experts): sigmoid scores with a selection bias that chooses
but does not weigh, the chosen scores renormalised and scaled
(:func:`route_sigmoid`); non-gated ``relu^2`` experts that live in a
latent width between two dense projections; a shared expert on the hidden
state, added for every token.

Not here: the exchange of rows between chips that hold different experts
(ROADMAP R2).  The one-expert-per-chip, capacity-dropping ``all_to_all``
demo is :mod:`horovod_tpu.parallel.expert`.
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


# The matrices of every expert, [E, d, f], [E, d, f] and [E, f, d]; a
# non-gated expert (``relu2``) has no ``w_gate``.
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def _expert_operands(layer):
    """The :data:`EXPERT_LEAVES` the layer has, as the grouped matmuls
    are handed them: as they are stored.  The kernels round a group's
    block to the rows' dtype in VMEM; an ``.astype`` here is a second copy
    of every expert in HBM, written each step, kept for the backward pass
    and read twice (PERF.md, PR 30), and :func:`record_weight_copies`
    counts it."""
    return tuple(layer[name] for name in EXPERT_LEAVES if name in layer)


def experts_ffn(h, slot_w, slot_e, group_sizes, layer, dtype,
                act: str = "swiglu", live=None):
    """``sum_s slot_w[:, s] * expert_{slot_e[:, s]}(h)``: ``h`` [N, d] ->
    [N, d] in ``dtype``.  ``slot_e`` [N, s] int32: a token's experts, as
    indices into the experts ``layer`` holds (or their count, for a slot
    that is no held expert's: :func:`held_slots`); ``group_sizes`` [E]
    int32: rows per held expert.  ``layer`` holds the expert matrices in
    the dtype they are stored in: ``w_gate``, ``w_up`` [E, d, f] and
    ``w_down`` [E, f, d] for ``act="swiglu"`` (``W_down (silu(W_gate h) *
    W_up h)``), ``w_up`` and ``w_down`` for ``act="relu2"`` (``W_down
    relu(W_up h)^2``).

    The ``N * s`` slots are sorted by expert, "not here" last: the held
    rows are the buffer's head, in expert order, and the grouped matmuls
    take the group sizes as they come and visit no tile past the last
    group.  ``live`` [N * s, 1] bool (None where every row is some
    expert's: the chip holds them all) marks that head.  What a grouped
    matmul leaves in the tail of its result, and of its operand's
    gradient, is not defined (any bits, ``nan`` among them): each is
    zeroed on both sides (:func:`matmul`), so nothing undefined meets a
    product, forward or backward."""
    n, s = slot_e.shape
    with jax.named_scope(scopes.MOE_DISPATCH):
        flat = slot_e.reshape(-1)
        # order[j]: the assignment (token * s + slot) at sorted place j;
        # place[a]: where assignment a went.  Stable, so an expert's rows
        # keep token order.  The group sizes are the caller's count of
        # the same keys.
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        place = jnp.zeros_like(order).at[order].set(
            jnp.arange(n * s, dtype=jnp.int32), unique_indices=True)
        rows = _gather_rows(h, order // s, place, s)

    def matmul(x, weights):
        if live is None:
            return grouped_matmul(x, weights, group_sizes)
        # A select, not a product: its gradient is a select too, so the
        # operand's gradient (a grouped matmul's result as well) is
        # zeroed before anything multiplies it.
        x = jnp.where(live, x, 0)
        return jnp.where(live, grouped_matmul(x, weights, group_sizes), 0)

    with jax.named_scope(scopes.MOE_EXPERTS):
        if act == "swiglu":
            w_gate, w_up, w_down = _expert_operands(layer)
            gate = matmul(rows, w_gate)
            up = matmul(rows, w_up)
            hidden = (jax.nn.silu(gate.astype(jnp.float32))
                      * up.astype(jnp.float32)).astype(dtype)
        elif act == "relu2":
            w_up, w_down = _expert_operands(layer)
            up = matmul(rows, w_up)
            hidden = jnp.square(
                jax.nn.relu(up.astype(jnp.float32))).astype(dtype)
        else:
            raise ValueError(f"act={act!r}: expected 'swiglu' or 'relu2'")
        out = matmul(hidden, w_down)
    with jax.named_scope(scopes.MOE_COMBINE):
        per_token = _gather_rows(out, place, order, 1).reshape(n, s, -1)
        return jnp.sum(per_token.astype(jnp.float32) * slot_w[..., None],
                       axis=1).astype(dtype)


def rows_bound(tokens: int, k: int, held: int) -> int:
    """Rows of :func:`experts_ffn`'s buffer: a token's ``k`` experts are
    distinct, so at most ``min(k, held)`` of them are held here."""
    return tokens * min(k, held)


def held_slots(top_w, top_i, first: int, held: int):
    """A token's assignments that land on the experts ``first .. first +
    held`` this chip holds: ``(slot_w [N, s] f32, slot_e [N, s] int32)``
    with ``s = min(k, held)``; ``slot_e`` is the held expert's local index
    or ``held`` for "not here" (weight 0).  A token's held experts are
    among its ``s`` least keys, so none is lost."""
    k = top_i.shape[1]
    local = top_i - first
    key = jnp.where((local >= 0) & (local < held), local, held)
    slots = min(k, held)
    if slots < k:
        least, at = lax.top_k(-key, slots)
        key, top_w = -least, jnp.take_along_axis(top_w, at, axis=-1)
    return jnp.where(key < held, top_w, 0.0), key.astype(jnp.int32)


def this_chips_share(top_w, top_i, cfg, counts=None):
    """A router's choice as :func:`experts_ffn` takes it on this chip:
    ``(slot_w, slot_e, group_sizes, live)``.  Where the chip holds every
    expert that is the choice itself and ``live`` is None (``counts``: the
    router's assignments per expert, where it has them already).  Where
    it holds a share (``cfg.experts_held``): the held slots
    (:func:`held_slots`), the rows each held expert receives and the mask
    of the buffer's rows that are some held expert's."""
    held = cfg.held_experts
    share = held < cfg.n_experts
    if share:
        top_w, top_i = held_slots(top_w, top_i, cfg.experts_held_from, held)
    if share or counts is None:
        counts = jnp.sum(
            top_i.reshape(-1, 1) == jnp.arange(held)[None, :], axis=0,
            dtype=jnp.int32)
    live = ((jnp.arange(top_i.size) < jnp.sum(counts))[:, None] if share
            else None)
    return top_w, top_i, counts, live


def moe_ffn(h, layer, cfg):
    """The whole softmax-routed layer on ``h`` [..., d]: ``(y [..., d],
    RouterStats)``.  ``cfg`` is the model's ``TransformerConfig``."""
    flat = h.reshape(-1, h.shape[-1])
    with jax.named_scope(scopes.MOE_ROUTER):
        top_p, top_i, stats = route(flat, layer["router"],
                                    cfg.experts_per_token,
                                    cfg.norm_topk_prob)
        slot_w, slot_e, rows, live = this_chips_share(
            top_p, top_i, cfg, stats.counts)
    y = experts_ffn(flat, slot_w, slot_e, rows, layer, cfg.dtype,
                    act="swiglu", live=live)
    return y.reshape(h.shape), stats


def route_sigmoid(h, router_w, bias, k: int, scale: float):
    """``h`` [N, d], ``router_w`` [d, E], ``bias`` [E] -> ``(top_w [N, k]
    f32, top_i [N, k] int32)``: scores ``s = sigmoid(h W_r)`` in float32
    over all ``E`` (the matmul at precision ``highest``, as
    :func:`route`'s), the ``k`` experts with the largest ``s + bias``
    (``bias`` chooses and carries no gradient), and weights ``scale *
    s[chosen] / (sum of s[chosen] + 1e-20)``: the sum runs over all ``k``
    whether this chip holds them or not."""
    logits = jnp.dot(h.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    scores = jax.nn.sigmoid(logits)
    _, top_i = lax.top_k(scores + lax.stop_gradient(bias), k)
    chosen = jnp.take_along_axis(scores, top_i, axis=-1)
    return (scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                              + 1e-20), top_i)


def latent_moe_ffn(u, layer, cfg):
    """The latent mixture of experts with a shared expert on ``u`` [...,
    d]: ``(routed + shared [..., d], rows per held expert)``.  ``routed =
    (sum over the chosen experts held here of w_k expert_k(u W_in^lat))
    W_out^lat``, ``shared = W_sd relu(W_su u)^2``.  ``cfg`` is the
    model's ``TransformerConfig``."""
    dt = cfg.dtype
    flat = u.reshape(-1, u.shape[-1])
    with jax.named_scope(scopes.MOE_ROUTER):
        top_w, top_i = route_sigmoid(
            flat, layer["router"], layer["router_bias"],
            cfg.experts_per_token, cfg.routed_scale)
        slot_w, slot_e, rows, live = this_chips_share(top_w, top_i, cfg)
    with jax.named_scope(scopes.MOE_LATENT):
        latent = flat @ layer["w_latent_in"].astype(dt)
    routed = experts_ffn(latent, slot_w, slot_e, rows, layer, dt,
                         act="relu2", live=live)
    with jax.named_scope(scopes.MOE_LATENT):
        routed = routed @ layer["w_latent_out"].astype(dt)
    with jax.named_scope(scopes.MOE_SHARED):
        hidden = jnp.square(jax.nn.relu(
            (flat @ layer["w_shared_up"].astype(dt)).astype(jnp.float32)))
        shared = hidden.astype(dt) @ layer["w_shared_down"].astype(dt)
    return (routed + shared).reshape(u.shape), rows


def record_held(layer: int, tokens: int, cfg) -> None:
    """Trace-time series beside ``hvd_moe_assignments_total``: the routed
    experts layer ``layer`` holds on this chip, and the static bound on
    the rows they can receive from ``tokens`` tokens (what they do
    receive is data)."""
    if not telemetry.enabled():
        return
    telemetry.gauge(
        "hvd_moe_experts_held",
        "Routed experts of the traced MoE layer that this chip holds (of "
        "n_experts the router scores)",
        layer=str(layer)).set(cfg.held_experts)
    telemetry.gauge(
        "hvd_moe_rows_bound",
        "Rows of the traced MoE layer's buffer: tokens x min("
        "experts_per_token, experts held); no batch can need more, so "
        "nothing held is dropped",
        layer=str(layer)).set(rows_bound(tokens, cfg.experts_per_token,
                                         cfg.held_experts))


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
            for h, name in zip(handed, (n for n in EXPERT_LEAVES
                                        if n in weights))
            if h.dtype != weights[name].dtype))
