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
nothing assumes balance.  Where every slot has a row of the buffer, both
row moves are gathers in both directions (:func:`_gather_rows`): the
backward pass of "gather the sorted rows" is "gather them back and add",
never a scatter (decided at 65,536 rows of 2,048: PERF.md, PR 26).

**One chip's share of a wider layer** (``TransformerConfig.experts_held``
fewer than ``n_experts``): the router still scores all ``n_experts`` and
keeps its ``k``; this chip computes the rows of the contiguous range it
holds and leaves the rest out (:func:`this_chips_share`, for either
router and either expert form): no exchange, and
nothing stands in for the absent chips.  Shapes stay static: a token can
land here at most ``min(k, held)`` times, so a row buffer of ``N *
min(k, held)`` bounds every batch (:func:`rows_bound`), and **nothing
held is ever dropped**.  A batch fills a few percent of that bound, at
its head, so the layer works on a *prefix* of the sorted buffer, derived
from the shapes (:func:`rows_prefix`: four times the rows a uniform
router sends, in whole row tiles), and a batch whose held rows overflow
it is computed over the whole buffer: the same function of the same
inputs, chosen on the device by the batch's own row count
(:func:`_prefix_ffn`; under ``remat`` either form runs forward twice a
step, as the layer of before did, and a batch past the prefix costs what
it cost: ``nemotron3s_t8192`` with every expert layer forced past it,
835.8 ms a step against the parent's 839.3; PERF.md, PR 36).  On the
prefix a token's rows cannot be listed in a static shape, so the rows go
out by token and come back by **one sum into the tokens**
(:func:`_head_ffn`): one gather of the prefix, and the kernels of
:mod:`horovod_tpu.ops.moe_rows`, a DMA a live row (PERF.md, PR 48), where
they take the rows; else a scatter-add.
Decided again on the chip at the prefix of ``nemotron3s_t8192``, 11,264
rows of 1,024 into 8,192 tokens (PERF.md, PR 36): the sum takes 0.54 ms
(``moe_combine`` of a traced step 9.4), a gather of all 65,536 slots that
reads a zero row for a dead one 0.50, and the gather of before, from
65,536 rows, 1.52.  Level on the chip, so the sum it is: it costs what
the rows that exist cost, and leaves nothing in the program as long as
the bound but indices.

**The latent layer** (:func:`latent_moe_ffn`; DeepSeek-V3's scoring with
Nemotron-3's experts): sigmoid scores with a selection bias that chooses
but does not weigh, the chosen scores renormalised and scaled
(:func:`route_sigmoid`); non-gated ``relu^2`` experts that live in a
latent width between two dense projections; a shared expert on the hidden
state, added for every token.

**A sigmoid-routed share no wider than the choice** (8 held of a choice
of 22 in ``nemotron3s_t8192``) needs no index of the choice, only whether
each held expert is in it and the sum of the chosen scores: its router is
a membership mask (:mod:`horovod_tpu.ops.router_choice`, ``lax.top_k``'s
set by counting) and products and row sums of it
(:func:`route_sigmoid_held`), with no sort, gather or scatter
(:func:`choice_path`, read from the shapes; PERF.md, PR 52).  Everywhere
else the choice is ``lax.top_k``'s indices.

**SwiGLU experts under the sigmoid router** (:func:`sigmoid_moe_ffn`;
DeepSeek-V3's layer as GLM-4.7-Flash runs it): the same router and the
same shared expert beside SwiGLU experts on the hidden state itself, no
latent width between.

**One expert a token or a skip, under an MLP router**
(:func:`zaya_moe_ffn`; ZAYA1, arXiv:2511.17127): the router is a down
projection, the previous expert layer's router state added a channel, a
norm and a small MLP (:func:`route_mlp`) over the experts **and a skip**,
one choice a token by argmax under a selection bias.  The state is handed
from expert layer to expert layer beside ``x`` (``Part.carries``).  The
skip is a choice after the last expert that no chip holds
(:func:`router_choices`): to :func:`held_slots` it is "not here" like
another chip's expert, and its term ``p_c u``, which multiplies no matrix,
is added by the chip whose token it is.  With one slot a token,
:func:`rows_bound` is the token count and four times a uniform router's
rows pass it, so the layer works on the whole buffer
(:func:`_every_slot_ffn`, masked), half of whose rows are dead where half
of the experts are held.

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
from horovod_tpu.models import parts
from horovod_tpu.models.parts import dense, ones, rmsnorm, whole
from horovod_tpu.ops import grouped_matmul as gmm
from horovod_tpu.ops import moe_rows, router_choice
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


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _rows_of(tokens: int, h, token):
    """``h[token]`` for ``h`` [tokens, d]: the tokens' rows at the head of
    the sorted buffer (``token`` [m] int32, a token as often as it has
    places there).  Its gradient is the sum of the rows' into their
    tokens, a scatter-add of ``m`` rows, in float32 like the sum over a
    token's uses in :func:`_gather_rows`."""
    del tokens
    return _take(h, token)


def _rows_of_fwd(tokens, h, token):
    return _take(h, token), token


def _rows_of_bwd(tokens, token, g):
    return jax.ops.segment_sum(g.astype(jnp.float32), token,
                               num_segments=tokens).astype(g.dtype), None


_rows_of.defvjp(_rows_of_fwd, _rows_of_bwd)


@functools.partial(jax.jit, static_argnums=0)
def _lowered_once(matmul, x, w, group_sizes):
    """``matmul(x, w, group_sizes)`` as a call of one function a shape:
    a share's step holds 96 grouped-matmul kernels (two forms of each
    layer; the forward's two, then the backward's two again and their
    four gradients), and traced in line each is lowered on its own, ~15
    ms apiece before every run (ROADMAP S4)."""
    return matmul(x, w, group_sizes)


def _experts(x, live, group_sizes, layer, act: str, dtype,
             tail_is_read: bool = True):
    """The held experts on the sorted rows ``x`` [m, d] -> [m, d];
    ``live`` [m, 1] bool marks the rows that are some held expert's (None:
    all of them).  ``tail_is_read`` False: nothing reads the result past
    the live rows, forward or backward, so it is left as the last matmul
    leaves it there (undefined), without a pass to zero it."""

    def matmul(x, w, tail_is_read=True):
        if live is None:
            return grouped_matmul(x, w, group_sizes)
        # A select, not a product: its gradient is a select too, so the
        # operand's gradient (a grouped matmul's result as well) is
        # zeroed before anything multiplies it.
        x = jnp.where(live, x, 0)
        out = _lowered_once(grouped_matmul, x, w, group_sizes)
        return jnp.where(live, out, 0) if tail_is_read else out

    if act == "swiglu":
        w_gate, w_up, w_down = _expert_operands(layer)
        gate = matmul(x, w_gate)
        up = matmul(x, w_up)
        hidden = (jax.nn.silu(gate.astype(jnp.float32))
                  * up.astype(jnp.float32)).astype(dtype)
    elif act == "relu2":
        w_up, w_down = _expert_operands(layer)
        up = matmul(x, w_up)
        hidden = jnp.square(
            jax.nn.relu(up.astype(jnp.float32))).astype(dtype)
    else:
        raise ValueError(f"act={act!r}: expected 'swiglu' or 'relu2'")
    return matmul(hidden, w_down, tail_is_read)


def _every_slot_ffn(act: str, dtype, masked: bool, h, slot_w, order,
                    group_sizes, layer):
    """:func:`experts_ffn` with a row of the buffer for every slot, all
    ``N * s``: both row moves are gathers in both directions
    (:func:`_gather_rows`).  ``masked``: rows past ``sum(group_sizes)``
    are no held expert's."""
    n, s = slot_w.shape
    live = ((jnp.arange(n * s) < jnp.sum(group_sizes))[:, None] if masked
            else None)
    with jax.named_scope(scopes.MOE_DISPATCH):
        # place[a]: where assignment a went.
        place = jnp.zeros_like(order).at[order].set(
            jnp.arange(n * s, dtype=jnp.int32), unique_indices=True)
        x = _gather_rows(h, order // s, place, s)
    with jax.named_scope(scopes.MOE_EXPERTS):
        out = _experts(x, live, group_sizes, layer, act, dtype)
    with jax.named_scope(scopes.MOE_COMBINE):
        per_token = _gather_rows(out, place, order, 1).reshape(n, s, -1)
        return jnp.sum(per_token.astype(jnp.float32) * slot_w[..., None],
                       axis=1).astype(dtype)


def _head_ffn(rows: int, act: str, dtype, h, slot_w, order, group_sizes,
              layer):
    """:func:`experts_ffn` over the first ``rows`` sorted places, which
    hold every held row (the caller's promise; ``rows`` static, fewer
    than ``N * s``).  A token's places among them cannot be listed in a
    static shape, so the rows go out by one gather of ``rows`` and come
    back by one sum into the tokens, each the other's gradient: nothing
    here is ``N * s`` long but ``order``.  Where the kernels of
    :mod:`horovod_tpu.ops.moe_rows` take the rows (``moe_rows.takes``:
    read from ``h``, never set) the sum, forward and as the gather's
    gradient, costs what the batch's live rows cost; else it is a
    scatter-add of ``rows`` (:func:`_rows_of`'s gradient too)."""
    n, s = slot_w.shape
    kernels = moe_rows.takes(h, s)
    with jax.named_scope(scopes.MOE_DISPATCH):
        head = order[:rows]
        token = head // s
        places, held = jnp.arange(rows), jnp.sum(group_sizes)
        live = (places < held)[:, None]
        if kernels:
            lists = moe_rows.by_token(head, held, n * s)
            x = moe_rows.rows_by_token(s, h, token, lists, held)
        else:
            x = _rows_of(n, h, token)
    with jax.named_scope(scopes.MOE_EXPERTS):
        # The kernels' sum reads no row past the live count, and its
        # gradient is zero there.
        out = _experts(x, live, group_sizes, layer, act, dtype,
                       tail_is_read=not kernels)
    with jax.named_scope(scopes.MOE_COMBINE):
        if kernels:
            return moe_rows.sum_by_token(out, slot_w, head, lists, held)
        weighted = (out.astype(jnp.float32)
                    * _take(slot_w.reshape(-1), head)[:, None])
        return jax.ops.segment_sum(weighted, token,
                                   num_segments=n).astype(dtype)


def _projected(y, project, dtype):
    """``y @ project`` (None: ``y``), under the latent projections'
    scope."""
    if project is None:
        return y
    with jax.named_scope(scopes.MOE_LATENT):
        return y @ project.astype(dtype)


def _head_or_every_slot(prefix: int, act: str, dtype):
    """The two forms of a share's layer, as functions of ``(h, slot_w,
    order, group_sizes, experts, project)``: over the ``prefix`` rows of
    the buffer's head, for a batch whose held rows fit them, and over
    every slot, for one that overflows them.  One function of the same
    inputs either way, so such a batch is computed whole."""

    def form(ffn):
        return lambda h, slot_w, order, group_sizes, experts, project: (
            _projected(ffn(h, slot_w, order, group_sizes, experts), project,
                       dtype))

    return (form(functools.partial(_head_ffn, prefix, act, dtype)),
            form(functools.partial(_every_slot_ffn, act, dtype, True)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _prefix_ffn(prefix: int, act: str, dtype, h, slot_w, order, group_sizes,
                experts, project):
    """:func:`experts_ffn` on a share: the form the batch's own row count
    chooses on the device (:func:`_head_or_every_slot`).

    Why a gradient of its own: autodiff of the conditional keeps both
    branches' intermediates for the backward pass and has the branch
    taken write zeros for the other's, a pass over the bound's shapes (~1
    GB an expert layer of ``nemotron3s_t8192``) on the path that is there
    to avoid them.  So the backward pass branches for itself and
    differentiates the form the batch needs, from the layer's inputs:
    they are all that is kept between the passes, and the backward
    computes the chosen form once more.

    Why ``project`` is inside: the product that follows the layer keeps
    its operand for its own gradient.  With it inside, nothing outside
    needs this function's result a second time, so where the model
    recomputes the layer for the backward pass (``remat``) the recomputed
    forward is dead code and is dropped: either form then runs forward
    twice a step, as the layer of before did, and not three times."""
    return lax.cond(jnp.sum(group_sizes) <= prefix,
                    *_head_or_every_slot(prefix, act, dtype), h, slot_w,
                    order, group_sizes, experts, project)


def _prefix_ffn_fwd(prefix, act, dtype, h, slot_w, order, group_sizes,
                    experts, project):
    return (_prefix_ffn(prefix, act, dtype, h, slot_w, order, group_sizes,
                        experts, project),
            (h, slot_w, order, group_sizes, experts, project))


def _prefix_ffn_bwd(prefix, act, dtype, residuals, g):
    h, slot_w, order, group_sizes, experts, project = residuals

    def gradients(ffn):
        return lambda h, slot_w, experts, project, g: jax.vjp(
            lambda h, slot_w, experts, project: ffn(
                h, slot_w, order, group_sizes, experts, project),
            h, slot_w, experts, project)[1](g)

    d_h, d_slot_w, d_experts, d_project = lax.cond(
        jnp.sum(group_sizes) <= prefix,
        *map(gradients, _head_or_every_slot(prefix, act, dtype)), h, slot_w,
        experts, project, g)
    return d_h, d_slot_w, None, None, d_experts, d_project


_prefix_ffn.defvjp(_prefix_ffn_fwd, _prefix_ffn_bwd)


def experts_ffn(h, slot_w, slot_e, group_sizes, layer, dtype,
                act: str = "swiglu", prefix=None, project=None):
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
    group.  ``prefix`` (static; :func:`this_chips_share`): None where
    every slot is some held expert's, and the buffer is the ``N * s``
    places; on a share, the rows of the buffer's head that the layer
    works on while a batch's held rows fit them (:func:`rows_prefix`), a
    batch with more taking all ``N * s`` (:func:`_prefix_ffn`).  What a
    grouped matmul leaves in the tail of its result, and of its operand's
    gradient, is not defined (any bits, ``nan`` among them): on a share
    each is zeroed on both sides (:func:`_experts`), so nothing undefined
    meets a product, forward or backward.  ``project`` [d, d_out], where
    given: the result times it, as part of the layer (why:
    :func:`_prefix_ffn`)."""
    n, s = slot_e.shape
    with jax.named_scope(scopes.MOE_DISPATCH):
        # order[j]: the assignment (token * s + slot) at sorted place j.
        # Stable, so an expert's rows keep token order.  The group sizes
        # are the caller's count of the same keys.
        order = jnp.argsort(slot_e.reshape(-1), stable=True).astype(
            jnp.int32)
    if prefix is None or prefix >= n * s:
        return _projected(
            _every_slot_ffn(act, dtype, prefix is not None, h, slot_w, order,
                            group_sizes, layer), project, dtype)
    experts = {name: layer[name] for name in EXPERT_LEAVES if name in layer}
    return _prefix_ffn(prefix, act, dtype, h, slot_w, order, group_sizes,
                       experts, project)


def rows_bound(tokens: int, k: int, held: int) -> int:
    """Rows of :func:`experts_ffn`'s buffer: a token's ``k`` experts are
    distinct, so at most ``min(k, held)`` of them are held here."""
    return tokens * min(k, held)


# Rows of the prefix over the rows a uniform router sends to the held
# experts, ``tokens * k * held / n_experts``.  At initialisation the six
# expert layers of ``nemotron3s_t8192`` draw up to 1.45 times that (4,094
# rows against 2,816; PERF.md, PR 35-36).  What a trained router sends is
# not measured here: 4 keeps one whose held experts are four times as
# popular as the mean on the prefix (11,264 rows there) and still leaves
# 83% of the bound's rows alone; 2 (5,632) would save ~8 ms a step more
# and send a skew of 2 over the bound.  Derived from the shapes, never
# set: a batch past it is computed whole all the same
# (:func:`_prefix_ffn`), and a chip that is sent a deployment's rows
# (a third of the bound in that cell) is past it every step: the rungs
# are to be sized again from such traffic (ROADMAP R15).
PREFIX_SLACK = 4


def rows_prefix(tokens: int, k: int, held: int, n_experts: int) -> int:
    """Rows of the sorted buffer's head that a share of the experts works
    on (:func:`experts_ffn`): :data:`PREFIX_SLACK` times the expected
    rows, rounded up to the grouped matmuls' row tile, and never more
    than :func:`rows_bound`, which it is where every expert is held."""
    bound = rows_bound(tokens, k, held)
    expected = -(-tokens * k * held // n_experts)
    tile = gmm.TILE_M
    return min(bound, -(-PREFIX_SLACK * expected // tile) * tile)


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


def router_choices(cfg) -> int:
    """What a token's router ranks: the ``n_experts`` and, under the MLP
    router (``router_width``), the skip, a choice after the last expert
    that **no** chip holds: to :func:`held_slots` it is "not here" on
    every chip, so the held range, :func:`rows_bound` and
    :func:`rows_prefix` speak of the real experts alone."""
    return cfg.n_experts + (1 if cfg.router_width else 0)


def this_chips_share(top_w, top_i, cfg, counts=None):
    """A router's choice as :func:`experts_ffn` takes it on this chip:
    ``(slot_w, slot_e, group_sizes, prefix)``.  Where the chip holds every
    choice (:func:`router_choices`) that is the choice itself and
    ``prefix`` is None (``counts``: the router's assignments per expert,
    where it has them already).  Where it holds a share
    (``cfg.experts_held``, or any range under a router with a skip): the
    held slots (:func:`held_slots`), the rows each held expert receives
    and the rows of the buffer's head that hold an ordinary batch's
    (:func:`rows_prefix`, static)."""
    held = cfg.held_experts
    share = held < router_choices(cfg)
    if share:
        top_w, top_i = held_slots(top_w, top_i, cfg.experts_held_from, held)
    if share or counts is None:
        counts = jnp.sum(
            top_i.reshape(-1, 1) == jnp.arange(held)[None, :], axis=0,
            dtype=jnp.int32)
    prefix = (rows_prefix(top_i.shape[0], cfg.experts_per_token, held,
                          router_choices(cfg)) if share else None)
    return top_w, top_i, counts, prefix


def moe_ffn(h, layer, cfg):
    """The whole softmax-routed layer on ``h`` [..., d]: ``(y [..., d],
    RouterStats)``.  ``cfg`` is the model's ``TransformerConfig``."""
    flat = h.reshape(-1, h.shape[-1])
    with jax.named_scope(scopes.MOE_ROUTER):
        top_p, top_i, stats = route(flat, layer["router"],
                                    cfg.experts_per_token,
                                    cfg.norm_topk_prob)
        slot_w, slot_e, rows, prefix = this_chips_share(
            top_p, top_i, cfg, stats.counts)
    y = experts_ffn(flat, slot_w, slot_e, rows, layer, cfg.dtype,
                    act="swiglu", prefix=prefix)
    return y.reshape(h.shape), stats


def sigmoid_scores(h, router_w):
    """``sigmoid(h W_r)`` [N, E] in float32, the matmul at precision
    ``highest``, as :func:`route`'s."""
    logits = jnp.dot(h.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    return jax.nn.sigmoid(logits)


def route_sigmoid(h, router_w, bias, k: int, scale: float):
    """``h`` [N, d], ``router_w`` [d, E], ``bias`` [E] -> ``(top_w [N, k]
    f32, top_i [N, k] int32)``: scores ``s = sigmoid(h W_r)`` in float32
    over all ``E`` (:func:`sigmoid_scores`), the ``k`` experts with the
    largest ``s + bias`` (``bias`` chooses and carries no gradient), and
    weights ``scale * s[chosen] / (sum of s[chosen] + 1e-20)``: the sum
    runs over all ``k`` whether this chip holds them or not."""
    scores = sigmoid_scores(h, router_w)
    _, top_i = lax.top_k(scores + lax.stop_gradient(bias), k)
    chosen = jnp.take_along_axis(scores, top_i, axis=-1)
    return (scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True)
                              + 1e-20), top_i)


def route_sigmoid_held(h, router_w, bias, k: int, scale: float, first: int,
                       held: int, kernel: bool):
    """:func:`route_sigmoid` and :func:`held_slots` for a share no wider
    than the choice (``held <= k``), without an index: ``(slot_w [N, held]
    f32, slot_e [N, held] int32, rows [held] int32)``.  Of a token's ``k``
    choices such a share needs whether each held expert is among them and
    the sum of the chosen scores, so the choice is a membership mask ``m``
    [N, E] (:func:`router_choice.chosen`, ``lax.top_k``'s set exactly;
    ``kernel`` False: from ``lax.top_k`` itself), and the rest is slices,
    products and row sums: held expert ``j`` has slot ``j``, with weight
    ``scale * s_j m_j / (sum_e s_e m_e + 1e-20)`` and expert ``j`` if
    chosen, else "not here"; ``rows`` counts the chosen a held expert.
    :func:`experts_ffn` sorts the slots stably by expert and a token has
    at most one slot an expert, so every group holds the rows
    :func:`held_slots`' hold, in the same order.  The gradient with
    respect to ``s`` is elementwise with two row sums; the mask and the
    bias carry none."""
    scores = sigmoid_scores(h, router_w)
    mask = (router_choice.chosen if kernel else router_choice.chosen_xla)(
        scores + lax.stop_gradient(bias), k)
    total = jnp.sum(scores * mask, axis=-1, keepdims=True) + 1e-20
    here = slice(first, first + held)
    mask = mask[:, here]
    slot_e = jnp.where(mask > 0, jnp.arange(held, dtype=jnp.int32), held)
    return (scale * scores[:, here] * mask / total, slot_e,
            jnp.sum(mask, axis=0).astype(jnp.int32))


def choice_path(x, layer, cfg) -> str:
    """How a layer of ``cfg`` with the parameters ``layer`` and the input
    ``x`` [..., d] finds a token's experts: ``"top_k"`` (``lax.top_k``'s
    indices: every softmax-routed layer, every layer that holds all its
    experts, and a share wider than the choice, whose ``k`` compact slots
    :func:`experts_ffn` needs), or, where a sigmoid-routed share is no
    wider than the choice, the mask of :func:`route_sigmoid_held`:
    ``"threshold_kernel"`` where :func:`router_choice.takes` accepts the
    scores, else ``"threshold_xla"``; ``"argmax"`` under the MLP router,
    whose one choice a token is an argmax."""
    if cfg.router_width:
        return "argmax"
    held = cfg.held_experts
    if ("router_bias" not in layer or held == cfg.n_experts
            or held > cfg.experts_per_token):
        return "top_k"
    keys = jnp.broadcast_to(
        x.reshape(-1, x.shape[-1])[:, :1].astype(jnp.float32),
        (x.size // x.shape[-1], cfg.n_experts))
    return ("threshold_kernel" if router_choice.takes(keys)
            else "threshold_xla")


def _sigmoid_share(flat, layer, cfg):
    """The sigmoid router's choice among ``flat`` [N, d] as
    :func:`experts_ffn` takes it on this chip: ``(slot_w, slot_e,
    group_sizes, prefix)``, by :func:`choice_path`."""
    k = cfg.experts_per_token
    path = choice_path(flat, layer, cfg)
    if path == "top_k":
        top_w, top_i = route_sigmoid(flat, layer["router"],
                                     layer["router_bias"], k,
                                     cfg.routed_scale)
        return this_chips_share(top_w, top_i, cfg)
    held = cfg.held_experts
    return (*route_sigmoid_held(
        flat, layer["router"], layer["router_bias"], k, cfg.routed_scale,
        cfg.experts_held_from, held, path == "threshold_kernel"),
        rows_prefix(flat.shape[0], k, held, cfg.n_experts))


def latent_moe_ffn(u, layer, cfg):
    """The latent mixture of experts with a shared expert on ``u`` [...,
    d]: ``(routed + shared [..., d], rows per held expert)``.  ``routed =
    (sum over the chosen experts held here of w_k expert_k(u W_in^lat))
    W_out^lat``, ``shared = W_sd relu(W_su u)^2``.  ``cfg`` is the
    model's ``TransformerConfig``."""
    dt = cfg.dtype
    flat = u.reshape(-1, u.shape[-1])
    with jax.named_scope(scopes.MOE_ROUTER):
        slot_w, slot_e, rows, prefix = _sigmoid_share(flat, layer, cfg)
    with jax.named_scope(scopes.MOE_LATENT):
        latent = flat @ layer["w_latent_in"].astype(dt)
    routed = experts_ffn(latent, slot_w, slot_e, rows, layer, dt,
                         act="relu2", prefix=prefix,
                         project=layer["w_latent_out"])
    with jax.named_scope(scopes.MOE_SHARED):
        hidden = jnp.square(jax.nn.relu(
            (flat @ layer["w_shared_up"].astype(dt)).astype(jnp.float32)))
        shared = hidden.astype(dt) @ layer["w_shared_down"].astype(dt)
    return (routed + shared).reshape(u.shape), rows


def sigmoid_moe_ffn(u, layer, cfg):
    """SwiGLU experts under the sigmoid router, with a shared expert, on
    ``u`` [..., d]: ``(routed + shared [..., d], rows per held expert)``.
    ``routed = sum over the chosen experts held here of w_k expert_k(u)``
    with :func:`route_sigmoid`'s weights, ``shared = W_sd (silu(W_sg u) *
    W_su u)``, added for every token.  ``cfg`` is the model's
    ``TransformerConfig``."""
    dt = cfg.dtype
    flat = u.reshape(-1, u.shape[-1])
    with jax.named_scope(scopes.MOE_ROUTER):
        slot_w, slot_e, rows, prefix = _sigmoid_share(flat, layer, cfg)
    routed = experts_ffn(flat, slot_w, slot_e, rows, layer, dt,
                         act="swiglu", prefix=prefix)
    with jax.named_scope(scopes.MOE_SHARED):
        hidden = (jax.nn.silu(flat @ layer["w_shared_gate"].astype(dt))
                  * (flat @ layer["w_shared_up"].astype(dt)))
        shared = hidden @ layer["w_shared_down"].astype(dt)
    return (routed + shared).reshape(u.shape), rows


def route_mlp(u, state, layer, cfg):
    """The MLP router with a state carried from expert layer to expert
    layer (ZAYA1, arXiv:2511.17127): ``u`` [N, d], ``state`` [N, w] (the
    previous expert layer's, None in the first) -> ``(p_c [N] f32, c [N]
    int32, state' [N, w] f32)``.  ``r = u W_d + b_d + gamma * state`` (the
    last term absent without ``state``) is what the next layer receives;
    ``z = W_3 gelu(W_2 gelu(W_1 RMSNorm(r) + b_1) + b_2)`` over the
    ``n_experts + 1`` choices (the last is the skip), exact ``gelu``; ``p
    = softmax(z)``; ``c = argmax(p + bias)`` (``router_bias`` chooses and
    carries no gradient; of equal sums the lower index wins) and ``p_c``
    its probability.  All in float32, the matmuls at precision ``highest``
    (0.66 M of the layer's 13.2 M active matrix parameters; a bf16 router
    flips choices: :func:`route`)."""
    f32 = jnp.float32
    dot = functools.partial(jnp.dot, precision=lax.Precision.HIGHEST)
    with jax.named_scope(scopes.ROUTER_STATE):
        r = dot(u.astype(f32), layer["router_down"]) + layer[
            "router_down_bias"]
        if state is not None:
            r = r + layer["router_state_scale"] * state
    with jax.named_scope(scopes.ROUTER_MLP):
        h = rmsnorm(r, layer["router_norm_scale"], cfg.norm_eps)
        h = jax.nn.gelu(dot(h, layer["router_w1"]) + layer["router_b1"],
                        approximate=False)
        h = jax.nn.gelu(dot(h, layer["router_w2"]) + layer["router_b2"],
                        approximate=False)
        p = jax.nn.softmax(dot(h, layer["router_w3"]), axis=-1)
        c = jnp.argmax(p + lax.stop_gradient(layer["router_bias"]), axis=-1)
        p_c = jnp.take_along_axis(p, c[:, None], axis=-1)[:, 0]
    return p_c, c.astype(jnp.int32), r


def zaya_moe_ffn(u, state, layer, cfg):
    """One SwiGLU expert a token of ``n_experts`` and a skip, under the
    MLP router, on ``u`` [..., d] with the previous expert layer's router
    ``state`` [..., w] (or None): ``(y [..., d], state')``.  ``y = p_c
    expert_c(u)`` for a real expert ``c`` that this chip holds, nothing
    for one it does not, and ``p_c u`` for the skip, which multiplies no
    matrix and is computed by the chip whose token it is."""
    dt = cfg.dtype
    flat = u.reshape(-1, u.shape[-1])
    with jax.named_scope(scopes.MOE_ROUTER):
        p_c, c, new = route_mlp(
            flat, None if state is None else state.reshape(
                -1, state.shape[-1]), layer, cfg)
        slot_w, slot_e, rows, prefix = this_chips_share(
            p_c[:, None], c[:, None], cfg)
    routed = experts_ffn(flat, slot_w, slot_e, rows, layer, dt,
                         act="swiglu", prefix=prefix)
    with jax.named_scope(scopes.MOE_SKIP):
        skipped = (jnp.where(c == cfg.n_experts, p_c, 0.0)[:, None]
                   * flat.astype(jnp.float32)).astype(dt)
    return ((routed + skipped).reshape(u.shape),
            new.reshape(u.shape[:-1] + new.shape[-1:]))


def record_held(layer: int, tokens: int, cfg) -> None:
    """Trace-time series beside ``hvd_moe_assignments_total``: the routed
    experts layer ``layer`` holds on this chip, the static bound on the
    rows they can receive from ``tokens`` tokens, and the rows of the
    buffer's head that the layer works on while a batch's fit them (what
    they do receive is data)."""
    if not telemetry.enabled():
        return
    k, held = cfg.experts_per_token, cfg.held_experts
    telemetry.gauge(
        "hvd_moe_experts_held",
        "Routed experts of the traced MoE layer that this chip holds (of "
        "n_experts the router scores)",
        layer=str(layer)).set(held)
    telemetry.gauge(
        "hvd_moe_rows_bound",
        "Rows of the traced MoE layer's buffer: tokens x min("
        "experts_per_token, experts held); no batch can need more, so "
        "nothing held is dropped",
        layer=str(layer)).set(rows_bound(tokens, k, held))
    telemetry.gauge(
        "hvd_moe_rows_prefix",
        "Rows of the buffer's head that the traced MoE layer's row work "
        "runs over while a batch's held rows fit them (a batch with more "
        "takes the bound's): the bound where every expert is held",
        layer=str(layer)).set(
            rows_prefix(tokens, k, held, router_choices(cfg)))


def record_router(layer: int, x, weights, cfg) -> None:
    """Trace-time series beside :func:`record_held` (what was compiled
    into the step): how layer ``layer`` (parameters ``weights``, input
    ``x``) finds a token's experts (:func:`choice_path`)."""
    if not telemetry.enabled():
        return
    telemetry.counter(
        "hvd_moe_router_choices_total",
        "Routers of the traced MoE layer by how a token's experts are "
        "found (path: top_k = lax.top_k's indices | threshold_kernel = "
        "the membership mask of the same set from the kernel moe_choose, "
        "on a sigmoid-routed share no wider than the choice | "
        "threshold_xla = that mask from lax.top_k where the kernel does "
        "not take the scores | argmax = the one choice a token of the MLP "
        "router)",
        layer=str(layer), path=choice_path(x, weights, cfg)).inc()


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


# --- the three expert forms as parts (models/parts.py) ----------------------

def _validate(cfg, used):
    if not cfg.n_experts:
        if (cfg.experts_per_token or cfg.d_expert or cfg.norm_topk_prob
                or cfg.router_aux_coef or cfg.router_z_coef
                or cfg.experts_held or cfg.experts_held_from):
            raise ValueError("experts_per_token, d_expert, norm_topk_prob, "
                             "experts_held* and the router loss coefficients "
                             "mean nothing without n_experts")
        return
    if cfg.mlp == "gelu":
        raise ValueError("n_experts > 0: the experts are SwiGLU "
                         "(mlp='swiglu') or latent relu^2 (mlp='relu2')")
    if not 0 < cfg.experts_per_token <= cfg.n_experts:
        raise ValueError(
            f"experts_per_token={cfg.experts_per_token} must lie "
            f"in 1..n_experts={cfg.n_experts}")
    if cfg.d_expert <= 0:
        raise ValueError("n_experts > 0 needs d_expert, one expert's width")
    if not (0 <= cfg.experts_held_from and
            cfg.experts_held_from + cfg.held_experts <= cfg.n_experts):
        raise ValueError(
            f"experts_held={cfg.experts_held} from {cfg.experts_held_from} "
            f"is not a range of the n_experts={cfg.n_experts}")


def _validate_sigmoid(cfg, used):
    if cfg.mlp == "relu2":      # the latent mixture's d_shared
        return
    if cfg.d_shared:
        if cfg.mlp != "swiglu" or not cfg.n_experts:
            raise ValueError(
                "d_shared is the shared expert beside routed experts: it "
                "needs n_experts and mlp='swiglu' (or 'relu2', the latent "
                "mixture)")
        if cfg.router_aux_coef or cfg.router_z_coef or cfg.norm_topk_prob:
            raise NotImplementedError(
                "d_shared with mlp='swiglu' is the sigmoid router: it has "
                "no auxiliary loss (its balance is the selection bias's) "
                "and always renormalises its top-k (norm_topk_prob is the "
                "softmax router's)")
    elif cfg.routed_scale != 1.0:
        raise ValueError("routed_scale scales the sigmoid router's "
                         "weights: it means nothing without d_shared")


def _validate_latent(cfg, used):
    if cfg.mlp != "relu2":
        if cfg.d_latent:
            raise ValueError("d_latent means nothing without mlp='relu2'")
        return
    if not cfg.n_experts or cfg.d_latent <= 0 or cfg.d_shared <= 0:
        raise ValueError("mlp='relu2' is the latent mixture of experts: it "
                         "needs n_experts, d_latent and d_shared")
    if cfg.router_aux_coef or cfg.router_z_coef:
        raise NotImplementedError(
            "mlp='relu2': the sigmoid router has no auxiliary loss (its "
            "balance is the selection bias's)")


def _stacked(key, shape, cfg):
    """[E held, in, out]: each expert a dense matrix of its own fan-in."""
    return dense(key, (cfg.held_experts,) + shape, scale=shape[0] ** -0.5)


def _init(k, cfg):
    d, e = cfg.d_model, cfg.d_expert
    k_up, k_router = parts.ffn_keys(k)
    return dict(ln2_scale=ones(d), router=dense(k_router, (d, cfg.n_experts)),
                w_gate=_stacked(k[4], (d, e), cfg),
                w_up=_stacked(k_up, (d, e), cfg),
                w_down=_stacked(k[5], (e, d), cfg))


def _init_sigmoid(k, cfg):
    d, s = cfg.d_model, cfg.d_shared
    k_shared = jax.random.split(jax.random.fold_in(k[5], 1), 3)
    return dict(
        _init(k, cfg),
        # Chooses and is not trained: its gradient is zero.
        router_bias=jnp.zeros((cfg.n_experts,), jnp.float32),
        w_shared_gate=dense(k_shared[0], (d, s)),
        w_shared_up=dense(k_shared[1], (d, s)),
        w_shared_down=dense(k_shared[2], (s, d)))


def _init_latent(k, cfg):
    d, e, lat, s = cfg.d_model, cfg.d_expert, cfg.d_latent, cfg.d_shared
    k_up, k_router = parts.ffn_keys(k)
    k_lat, k_shared = jax.random.split(jax.random.fold_in(k[5], 1))
    return dict(
        ln2_scale=ones(d), router=dense(k_router, (d, cfg.n_experts)),
        # Chooses and is not trained: its gradient is zero.
        router_bias=jnp.zeros((cfg.n_experts,), jnp.float32),
        w_latent_in=dense(k_lat, (d, lat)),
        w_latent_out=dense(jax.random.fold_in(k_lat, 1), (lat, d)),
        w_up=_stacked(k_up, (lat, e), cfg),
        w_down=_stacked(k[5], (e, lat), cfg),
        w_shared_up=dense(k_shared, (d, s)),
        w_shared_down=dense(jax.random.fold_in(k_shared, 1), (s, d)))


def _validate_zaya(cfg, used):
    if not cfg.router_width:
        return
    if cfg.router_width < 0 or not cfg.n_experts or cfg.mlp != "swiglu":
        raise ValueError(
            f"router_width={cfg.router_width} is the width of the MLP "
            f"router of SwiGLU experts: it needs n_experts and "
            f"mlp='swiglu'")
    if cfg.experts_per_token != 1:
        raise NotImplementedError(
            f"router_width: the MLP router makes one choice a token (an "
            f"expert or the skip), not experts_per_token="
            f"{cfg.experts_per_token}")
    refused = [f"{name}={getattr(cfg, name)!r}" for name in (
        "d_shared", "dense_layers", "norm_topk_prob", "router_aux_coef",
        "router_z_coef", "mtp_layer_types") if getattr(cfg, name)]
    if cfg.loops > 1:
        refused.append(f"loops={cfg.loops}")
    if refused:
        # The state is handed from one expert layer to the next of ONE
        # pass over ONE stack; the router has no auxiliary loss.
        raise NotImplementedError(
            "router_width: the MLP router with its carried state is not "
            "implemented with " + ", ".join(refused))


def _init_zaya(k, cfg):
    d, e, w = cfg.d_model, cfg.d_expert, cfg.router_width
    k_up, k_router = parts.ffn_keys(k)
    kr = jax.random.split(k_router, 9)
    small = lambda key, shape, mean=0.0, std=0.02: (
        mean + std * jax.random.normal(key, shape, jnp.float32))
    return dict(
        ln2_scale=ones(d),
        router_down=dense(kr[0], (d, w)),
        router_down_bias=small(kr[1], (w,)),
        # The previous layer's state a channel; unused (and its gradient
        # zero) in the first expert layer the stack runs.
        router_state_scale=small(kr[2], (w,), 1.0, 0.1),
        router_norm_scale=ones(w),
        router_w1=dense(kr[3], (w, w)), router_b1=small(kr[4], (w,)),
        router_w2=dense(kr[5], (w, w)), router_b2=small(kr[6], (w,)),
        router_w3=dense(kr[7], (w, cfg.n_experts + 1)),
        # Chooses and is not trained: its gradient is zero.
        router_bias=small(kr[8], (cfg.n_experts + 1,), 0.0, 0.01),
        w_gate=_stacked(k[4], (d, e), cfg),
        w_up=_stacked(k_up, (d, e), cfg),
        w_down=_stacked(k[5], (e, d), cfg),
        **parts.merge_init(k[5], "merge2", cfg))


_ZAYA_LEAVES = ("ln2_scale", "router_down", "router_down_bias",
                "router_state_scale", "router_norm_scale", "router_w1",
                "router_b1", "router_w2", "router_b2", "router_w3",
                "router_bias", "w_gate", "w_up", "w_down")


def _zaya_apply(x, layer, cfg, ctx, carried):
    with jax.named_scope(scopes.MLP):
        y, state = zaya_moe_ffn(
            rmsnorm(x, layer["ln2_scale"], cfg.norm_eps), carried[0], layer,
            cfg)
        return parts.merged(x, y, layer, "merge2", cfg), {}, (state,)


def _zaya_record(name, x, layer, cfg, ctx):
    _record(name, x, layer, cfg, ctx)
    if telemetry.enabled():
        telemetry.gauge(
            "hvd_moe_router_state_width",
            "Channels of the router state the traced MoE layer takes from "
            "the previous expert layer and hands to the next",
            layer=str(name)).set(cfg.router_width)


def _applies(ffn, extras):
    """rmsnorm -> ``ffn`` -> residual, under ``mlp``; ``extras(stats)`` is
    what the loss collects of the second thing ``ffn`` returns."""
    def apply(x, layer, cfg, ctx):
        with jax.named_scope(scopes.MLP):
            y, stats = ffn(rmsnorm(x, layer["ln2_scale"], cfg.norm_eps),
                           layer, cfg)
            return x + y, extras(stats)
    return apply


def moves_path(x, cfg):
    """What moves the rows of a layer of ``cfg`` whose input is ``x``
    [..., d], as :func:`_head_ffn` decides it (``moe_rows.takes`` on the
    rows the experts see: the latent ones where there is a latent width):
    ``"kernel"`` or ``"xla"``; None where the layer holds every expert
    (its moves are :func:`_every_slot_ffn`'s gathers)."""
    slots = min(cfg.experts_per_token, cfg.held_experts)
    tokens = x.size // x.shape[-1]
    if rows_prefix(tokens, cfg.experts_per_token, cfg.held_experts,
                   router_choices(cfg)) >= tokens * slots:
        return None
    width = cfg.d_latent if cfg.mlp == "relu2" else cfg.d_model
    rows = jnp.broadcast_to(x.reshape(-1, x.shape[-1])[:, :1],
                            (tokens, width)).astype(cfg.dtype)
    return "kernel" if moe_rows.takes(rows, slots) else "xla"


def _record(name, x, layer, cfg, ctx):
    record_held(name, ctx.tokens, cfg)
    record_router(name, x, layer, cfg)
    record_weight_copies(name, layer)
    path = moves_path(x, cfg) if telemetry.enabled() else None
    if path:
        moe_rows.record_moves(name, path)
    if cfg.held_experts == router_choices(cfg):
        # What lands on a share is data.
        record_assignments(name, ctx.tokens * cfg.experts_per_token,
                           cfg.n_experts)


# What the three share.  Every held expert is whole on every chip of the
# mesh (experts over an axis, and the exchange with the chips that hold the
# others: ROADMAP R2); the grouped-matmul kernels' index maps read arrays
# that vary over the batch axes, which the Pallas interpreter does not type
# under shard_map's checker (ops/grouped_matmul.py).
_EXPERTS = dict(record=_record, unsupported={"model_axis": ("n_experts",)},
                check_vma=False)
_SOFTMAX_LEAVES = ("ln2_scale", "router", "w_gate", "w_up", "w_down")

SOFTMAX_EXPERTS = parts.Part(
    name="softmax_experts",
    fields=("n_experts", "experts_per_token", "d_expert", "norm_topk_prob",
            "experts_held", "experts_held_from", "router_aux_coef",
            "router_z_coef"),
    validate=parts.refuses_post_norm(_validate, "softmax-routed experts"),
    init=_init,
    specs=lambda cfg, model_axis: whole(*_SOFTMAX_LEAVES),
    apply=_applies(moe_ffn, lambda stats: {"router_stats": stats}),
    **_EXPERTS)

# The sigmoid router has no auxiliary loss: nothing for the loss to collect.
SIGMOID_EXPERTS = parts.Part(
    name="sigmoid_experts", fields=("d_shared", "routed_scale"),
    validate=parts.refuses_post_norm(_validate_sigmoid,
                                     "sigmoid-routed experts"),
    init=_init_sigmoid,
    specs=lambda cfg, model_axis: whole(
        *_SOFTMAX_LEAVES, "router_bias", "w_shared_gate", "w_shared_up",
        "w_shared_down"),
    apply=_applies(sigmoid_moe_ffn, lambda rows: {}), **_EXPERTS)

LATENT_EXPERTS = parts.Part(
    name="latent_experts", fields=("d_latent",),
    validate=parts.refuses_post_norm(_validate_latent, "latent experts"),
    init=_init_latent,
    specs=lambda cfg, model_axis: whole(
        "ln2_scale", "router", "router_bias", "w_latent_in", "w_latent_out",
        "w_up", "w_down", "w_shared_up", "w_shared_down"),
    apply=_applies(latent_moe_ffn, lambda rows: {}), **_EXPERTS)

# One SwiGLU expert a token or a skip, under the MLP router whose state is
# handed from expert layer to expert layer.
ZAYA_EXPERTS = parts.Part(
    name="zaya_experts", fields=("router_width",),
    validate=parts.refuses_post_norm(
        _validate_zaya, "experts under the MLP router"),
    init=_init_zaya,
    specs=lambda cfg, model_axis: whole(
        *_ZAYA_LEAVES, *(parts.merge_names("merge2")
                         * cfg.residual_scaling)),
    apply=_zaya_apply, carries=("router_state",), scaled_merge=True,
    **dict(_EXPERTS, record=_zaya_record))
