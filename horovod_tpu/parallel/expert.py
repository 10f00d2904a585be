"""Expert parallelism: a Mixture-of-Experts layer over an 'expert' mesh axis.

Not in the reference — the v0.18 reference does not even have an alltoall
collective (SURVEY §2.5: ``message.h:47-49``).  TPU-native design: experts
shard over the expert axis (one or more per chip), tokens route to their
expert via ``lax.all_to_all`` over ICI, compute locally, and return the
same way — the standard Switch-Transformer dispatch expressed in pure SPMD.

Static shapes throughout (XLA requirement): routing uses fixed expert
capacity with drop-on-overflow, the standard TPU MoE trick.

This is the one-expert-per-chip, capacity-dropping demo of the
``all_to_all`` dispatch, reachable from no step builder.  It is **not**
what ``horovod_tpu.models`` trains with: the model's expert layer is
:mod:`horovod_tpu.models.moe` — many experts per chip, softmax-then-top-k
routing that drops nothing, a grouped matmul, the load-balancing and
router z-losses (``moe.router_losses``; :func:`load_balancing_loss` here
is the top-1 Switch form over the expert axis).  Experts over a mesh axis
for that layer are ROADMAP R2.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax


def _slotify(pos, gate, capacity: int):
    """Queue positions [T, E] (-1 = not routed there) + per-token gate
    -> (dispatch [T, E, C] one-hot, combine = dispatch * gate); tokens
    whose position exceeds capacity are dropped.  Shared by both
    routers so capacity semantics cannot diverge."""
    in_cap = (pos >= 0) & (pos < capacity)
    dispatch = (jax.nn.one_hot(jnp.clip(pos, 0, capacity - 1), capacity) *
                in_cap[..., None]).astype(jnp.float32)        # [T, E, C]
    return dispatch, dispatch * gate[:, None, None]


def top1_routing(logits, capacity: int):
    """Switch-style top-1 routing with fixed capacity.

    logits: [T, E] router scores for T local tokens.
    Returns (dispatch [T, E, C] one-hot, combine [T, E, C] weights).
    Tokens beyond an expert's capacity are dropped (contribute zero).
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)                   # [T]
    gate = jnp.take_along_axis(probs, expert_idx[:, None],
                               axis=-1)[:, 0]                 # [T]
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)   # [T, E]
    # Position of each token within its expert's queue.
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1             # [T, E]
    return _slotify(pos, gate, capacity)


def top2_routing(logits, capacity: int):
    """GShard-style top-2 routing with fixed capacity.

    logits: [T, E] router scores.  Each token goes to its best AND
    second-best expert; the two gates are renormalized to sum to 1
    (GShard eq. 4 — keeps the layer's output scale independent of how
    probability mass splits between the pair).  Capacity is assigned
    first-come-first-served with ALL first choices queued before any
    second choice at the same expert (the standard priority rule:
    dropping a token's backup hurts less than dropping its primary).
    Returns (dispatch [T, E, C], combine [T, E, C]); overflow drops.
    """
    t, e = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    idx1 = jnp.argmax(probs, axis=-1)                          # [T]
    p1 = jnp.take_along_axis(probs, idx1[:, None], axis=-1)[:, 0]
    masked = probs * (1.0 - jax.nn.one_hot(idx1, e))
    idx2 = jnp.argmax(masked, axis=-1)
    p2 = jnp.take_along_axis(masked, idx2[:, None], axis=-1)[:, 0]
    denom = p1 + p2 + 1e-9
    g1, g2 = p1 / denom, p2 / denom

    oh1 = jax.nn.one_hot(idx1, e, dtype=jnp.int32)             # [T, E]
    oh2 = jax.nn.one_hot(idx2, e, dtype=jnp.int32)
    pos1 = jnp.cumsum(oh1, axis=0) * oh1 - 1                   # [T, E]
    # Second choices queue behind every first choice of that expert.
    count1 = oh1.sum(axis=0)                                   # [E]
    pos2 = (jnp.cumsum(oh2, axis=0) + count1[None, :]) * oh2 - 1

    d1, c1 = _slotify(pos1, g1, capacity)
    d2, c2 = _slotify(pos2, g2, capacity)
    # A token's two choices are distinct experts, so the slots never
    # collide and the sums stay one-hot per (token, choice).
    return d1 + d2, c1 + c2


def moe_layer(x, router_w, expert_fn: Callable, expert_params,
              axis_name: str = "expert", capacity_factor: float = 1.25,
              router: str = "top1"):
    """Apply a distributed MoE layer inside shard_map.

    x: [T_local, D] local tokens; router_w: [D, E_total];
    expert_params: this chip's expert parameters (leading dim =
    experts-per-chip, here fixed to 1 for clarity);
    expert_fn(params, tokens[C, D]) -> [C, D].

    Total experts = axis size.  ``router`` selects Switch top-1 or
    GShard top-2 (each token to its two best experts, renormalized
    gates — roughly doubles per-expert traffic at equal capacity
    factor, so top-2 users typically also raise ``capacity_factor``).
    Returns [T_local, D].
    """
    size = lax.axis_size(axis_name)
    t, d = x.shape
    e = size
    capacity = max(int(capacity_factor * t / e), 1)

    logits = x @ router_w                                     # [T, E]
    if router == "top1":
        dispatch, combine = top1_routing(logits, capacity)
    elif router == "top2":
        dispatch, combine = top2_routing(logits, capacity)
    else:
        raise ValueError(f"router={router!r}: expected 'top1' or 'top2'")

    # Gather this shard's tokens per expert: [E, C, D].
    buffers = jnp.einsum("td,tec->ecd", x, dispatch)
    # all_to_all: dim 0 (experts) scatters so each chip receives ITS
    # expert's buffer from every shard: [E_src=size, C, D] after exchange.
    received = lax.all_to_all(buffers, axis_name, split_axis=0,
                              concat_axis=0, tiled=True)
    # Run the local expert over all received tokens.
    flat = received.reshape(size * capacity, d)
    out = expert_fn(expert_params, flat).reshape(size, capacity, d)
    # Return results to their source shards.
    returned = lax.all_to_all(out, axis_name, split_axis=0, concat_axis=0,
                              tiled=True)                     # [E, C, D]
    # Un-dispatch: weight by gate and scatter back to token positions.
    return jnp.einsum("ecd,tec->td", returned, combine)


def load_balancing_loss(logits, axis_name: str = "expert"):
    """Switch-Transformer auxiliary loss: mean fraction routed per expert
    times mean router prob per expert, scaled by E (encourages balance)."""
    probs = jax.nn.softmax(logits, axis=-1)
    e = probs.shape[-1]
    hard = jax.nn.one_hot(jnp.argmax(probs, -1), e)
    frac = lax.pmean(hard.mean(0), axis_name)
    prob = lax.pmean(probs.mean(0), axis_name)
    return e * jnp.sum(frac * prob)


def moe_layer_ragged(x, router_w, expert_fn: Callable, expert_params,
                     axis_name: str = "expert",
                     capacity_factor: float = 1.25,
                     use_primitive=None):
    """Top-1 MoE layer whose dispatch is the RAGGED exchange
    (:func:`horovod_tpu.ops.collective.alltoall_ragged`) instead of the
    dense ``[T, E, C]`` one-hot einsum of :func:`moe_layer`.

    Same routing decision as ``moe_layer(router="top1")`` — argmax
    expert, softmax gate — but tokens travel as exactly the routed rows
    (sorted by destination, per-destination counts), so the dispatch
    memory is O(T·D) instead of the one-hot's O(T·E·C), and the wire
    moves only real tokens on TPU meshes (XLA ragged-all-to-all; an
    exact dense twin runs on CPU/virtual meshes).

    Capacity semantics differ from the dense layer at overflow: the
    expert's buffer (``size · capacity`` rows) is granted to SOURCE
    shards in rank order (lower ranks first), not per-source slices —
    when nothing overflows the two layers agree exactly (gated by
    ``test_moe_ragged_matches_dense``).  Dropped tokens contribute zero,
    like the dense layer.

    x: [T_local, D]; router_w: [D, E_total]; expert_params: this chip's
    expert parameters; expert_fn(params, tokens[N, D]) -> [N, D]
    (position-independent per row — it sees padded zero rows).
    ``use_primitive`` forwards to :func:`alltoall_ragged`.
    Returns [T_local, D].
    """
    from horovod_tpu.ops.collective import alltoall_ragged

    size = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    t, d = x.shape
    capacity = max(int(capacity_factor * t / size), 1)
    buf = size * capacity                   # the expert's static buffer

    logits = x @ router_w                                     # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    dest = jnp.argmax(probs, axis=-1)                         # [T]
    gate = jnp.take_along_axis(probs, dest[:, None], axis=1)[:, 0]

    # Sort my tokens by destination (stable: ties keep token order, the
    # same FCFS the dense router's cumsum slots implement).
    order = jnp.argsort(dest)                                 # [T]
    splits = jnp.bincount(dest, length=size).astype(jnp.int32)
    x_sorted = x[order]

    out_buf, recv = alltoall_ragged(x_sorted, splits, buf,
                                    axis_name=axis_name,
                                    use_primitive=use_primitive)
    expert_out = expert_fn(expert_params, out_buf)            # [buf, D]

    # Return trip: rows go back grouped by source, counts clamped to
    # what actually landed (the capacity grant, in source-rank order).
    off_at_me = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                 jnp.cumsum(recv)[:-1].astype(jnp.int32)])
    landed = jnp.clip(buf - off_at_me, 0, recv)               # [S]
    back, _ = alltoall_ragged(expert_out, landed, t,
                              axis_name=axis_name,
                              use_primitive=use_primitive)    # [T, D]

    # Which of MY sorted rows survived their expert's buffer?  My block
    # at expert j starts at sum_{k<me} M[k, j]; row i of the block
    # survives iff start + i < buf.  Returned rows arrive grouped by
    # expert in j order == my sorted order with dropped rows REMOVED,
    # so scatter them back to the surviving sorted slots.
    m = lax.all_gather(splits, axis_name, axis=0)             # [S, S]
    start = jnp.sum(m * (jnp.arange(size) < me)[:, None], axis=0)  # [S]
    in_off = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(splits)[:-1].astype(jnp.int32)])
    idx = jnp.arange(t)
    row_dest = dest[order]
    pos_in_block = idx - in_off[row_dest]
    survived = start[row_dest] + pos_in_block < buf
    # Position of each surviving sorted row within the returned stream.
    ret_pos = jnp.cumsum(survived.astype(jnp.int32)) - 1
    gathered = jnp.where(survived[:, None],
                         back[jnp.clip(ret_pos, 0, t - 1)], 0.0)
    # Back to token order, weighted by the gate.
    y = jnp.zeros((t, d), x.dtype).at[order].set(gathered)
    return y * gate[:, None].astype(x.dtype)
