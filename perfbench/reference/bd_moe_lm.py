"""Plain reference of the SDAR language stack under the block-diffusion
objective that the ``bd_moe_lm`` cells train.

Straight ``jax.numpy`` in float32 at precision ``highest``: no kernel, no
``shard_map``, no bf16, attention with the scores of a block of query rows
written out against every key, the mask built here from the objective's
four rules, and the held experts one after another.  It shares no code
with ``horovod_tpu/``; it reads the program's parameter tree (``embed``,
``head``, ``ln_f_scale``, ``layers[i]``) because that tree is what a
checkpoint of the system holds.  What is no part of this model's own
(rounding for the controls, RMSNorm, the softmax router with the held
experts' loop, the head a block of rows at a time, the level placement,
leaves by path) is ``reference/dsa_moe_lm.py``'s, the same Qwen3-MoE
backbone.

The layer (``perfbench/configs/sdar-30b-a3b-chat.json``, ``assumed``),
pre-norm, two halves: ``x <- x + attention(RMSNorm(x))``, then ``x <- x +
experts(RMSNorm(x))``; ``q = u W_q`` as ``H`` heads, ``k = u W_k``, ``v = u
W_v`` as ``Hkv`` heads of ``head_dim``, q and k RMS-normed per head with
one learned scale each, then rotary (pairs ``(i, i + D/2)``, angle
``position * theta^(-2i/D)``) at the position **within the sequence**, the
same for a token and its noised copy; head ``h`` reads key-value head ``h
// (H / Hkv)``; softmax of ``q . k / sqrt(head_dim)`` over the keys the
mask shows.

The objective (Arriola et al., arXiv:2503.09573; SDAR, arXiv:2510.06303):
a clean sequence ``x0`` of ``L`` tokens in blocks of ``b``; ``xt`` is
``x0`` with the mask id where ``masked``; one pass over the ``2 L``
positions of both.  With ``beta(i) = i // b``, query ``i`` reads key ``j``
iff

* ``i`` noised, ``j`` noised: ``beta(j) == beta(i)``;
* ``i`` noised, ``j`` clean:  ``beta(j) <  beta(i)``;
* ``i`` clean,  ``j`` clean:  ``beta(j) <= beta(i)``;
* ``i`` clean,  ``j`` noised: never.

``loss = (1 / L) sum over masked i of (1 / t_beta(i)) x (-log softmax(
head(h_i))[x0_i])``, ``h_i`` the noised copy's last hidden state at ``i``,
``t`` the block's rate; the mean over the batch's sequences.  This file
lays the **noised copy first** and the clean sequence after it (the
program's order is its own business, and the other one):
:func:`visible` is written on (half, position), not on where a row sits.

:func:`loss_block_by_block` states the same objective a block at a time,
with no doubled stream: for each block ``k`` one forward pass of [the
clean blocks before ``k``; the noised block ``k``] under the block-causal
mask, block ``k``'s rows to the head.  Tier-1 holds the two together at a
tiny size.

Devices that change no arithmetic: every layer of the differentiated
tail, every block of query rows and every block of the head is under
``jax.checkpoint``; sequences go one at a time (``lax.map``); layers are
walked by ``lax.scan`` over their stacked matrices.  The gradients come
from a backward pass through the lowest layer that holds a requested leaf
and everything above it.

The controls (``perfbench/controls_bd_moe_lm.py``; each computes another
function, which the cell's check has to refuse): ``rule`` one of
:data:`RULES`, ``running_positions`` (positions ``0..2L-1``, the clean
sequence's first), ``weighted=False`` (the ``1 / t`` left out), ``shift``
(the label of ``i`` is ``x0[i + shift]``), ``low_precision`` (every
matmul's operands, and q, k, v, rounded to that dtype).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from perfbench.reference.dsa_moe_lm import (_mm, _moe_part, _nll_rows,
                                            _rmsnorm, _round, _with_leaf,
                                            leaf, level_placement)

QUERY_BLOCK = 256
with_leaf = _with_leaf
# What a query may read.  "block_diffusion" is the objective; the others
# are controls: one causal sequence of 2 L positions, the clean one first;
# a noised query reading the clean copy of its own block too; a noised
# block read causally instead of in both directions.
RULES = ("block_diffusion", "causal", "own_clean_block", "noised_causal")


def visible(q_noised, q_pos, k_noised, k_pos, block: int,
            rule: str = "block_diffusion"):
    """Whether a query (in the noised copy or not, at ``q_pos`` of the
    sequence) reads a key: the four rules, broadcast."""
    if rule not in RULES:
        raise ValueError(f"rule {rule!r}: one of {RULES}")
    if rule == "causal":
        # Laid clean first: every clean key is before every noised query.
        return jnp.where(q_noised == k_noised, k_pos <= q_pos, q_noised)
    q_beta, k_beta = q_pos // block, k_pos // block
    own = k_beta == q_beta
    if rule == "noised_causal":
        own = own & (k_pos <= q_pos)
    finished = (k_beta <= q_beta if rule == "own_clean_block"
                else k_beta < q_beta)
    return jnp.where(q_noised, jnp.where(k_noised, own, finished),
                     ~k_noised & (k_beta <= q_beta))


def _rotary(x, positions, theta):
    """``x`` [T, H, r] at ``positions`` [T], pairs ``(i, i + r/2)``."""
    r = x.shape[-1]
    inv_freq = theta ** (-jnp.arange(r // 2, dtype=jnp.float32) / (r // 2))
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention_part(u, layer, rows, dims, low, rule):
    """The attention half's output [T, d] for the normed input ``u`` [T,
    d] of rows ``rows = (noised [T] bool, pos [T], rotary_pos [T])``, a
    block of query rows at a time."""
    noised, pos, rotary_pos = rows
    t = u.shape[0]
    h, hkv, d = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    eps, theta = dims["eps"], dims["theta"]
    q = _mm(u, layer["wq"], low).reshape(t, h, d)
    k = _mm(u, layer["wk"], low).reshape(t, hkv, d)
    v = _round(_mm(u, layer["wv"], low).reshape(t, hkv, d), low)
    q = _round(_rotary(_rmsnorm(q, layer["q_norm_scale"], eps), rotary_pos,
                       theta), low)
    k = _round(_rotary(_rmsnorm(k, layer["k_norm_scale"], eps), rotary_pos,
                       theta), low)
    block = min(QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} rows not a multiple of {block}")

    @jax.checkpoint
    def one_block(start):
        cut = lambda a: lax.dynamic_slice_in_dim(a, start, block, axis=0)
        shown = visible(cut(noised)[:, None], cut(pos)[:, None],
                        noised[None, :], pos[None, :], dims["block"], rule)
        qg = cut(q).reshape(block, hkv, h // hkv, d)
        s = jnp.einsum("qkgd,skd->kgqs", qg, k) * (d ** -0.5)
        p = jax.nn.softmax(jnp.where(shown[None, None], s, -jnp.inf),
                           axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v).reshape(block, h * d)

    o = lax.map(one_block, jnp.arange(0, t, block)).reshape(t, h * d)
    return _mm(o, layer["wo"], low)


def _layer(x, layer, rows, dims, low, rule):
    """``(the layer's output, assignments per held expert)``."""
    eps = dims["eps"]
    x = x + _attention_part(_rmsnorm(x, layer["ln1_scale"], eps), layer,
                            rows, dims, low, rule)
    y, count = _moe_part(_rmsnorm(x, layer["ln2_scale"], eps), layer, dims,
                         low)
    return x + y, count.astype(jnp.int32)


def _through(x, layers, body, held):
    """``x`` through ``layers`` by ``body``: ``(x, assignments [layers,
    held])``, the layers stacked and walked by ``lax.scan``."""
    if not layers:
        return x, jnp.zeros((0, held), jnp.int32)
    return lax.scan(body, x, jax.tree_util.tree_map(
        lambda *a: jnp.stack(a), *layers))


LEAVES = {
    "ln_f_scale": ("ln_f_scale",),
    "embed": ("embed",),
    "head": ("head",),
    "wo_last": ("layers", "last", "wo"),
    "wq_last": ("layers", "last", "wq"),
    "wk_last": ("layers", "last", "wk"),
    "wv_last": ("layers", "last", "wv"),
    "q_norm_last": ("layers", "last", "q_norm_scale"),
    "k_norm_last": ("layers", "last", "k_norm_scale"),
    "router_last": ("layers", "last", "router"),
    "w_gate_last": ("layers", "last", "w_gate"),
    "w_up_last": ("layers", "last", "w_up"),
    "w_down_last": ("layers", "last", "w_down"),
    "ln1_last": ("layers", "last", "ln1_scale"),
    "ln2_last": ("layers", "last", "ln2_scale"),
    "wo_first": ("layers", "first", "wo"),
    "wk_first": ("layers", "first", "wk"),
}
# The cell's check; the k-norm's scale (128 numbers, whose gradient the
# chip reads to 0.005-0.009) is tier-1's and the controls', not its
# (configs/sdar-30b-a3b-chat.json, check.why).
CHECKED = ("ln_f_scale", "wo_last", "wk_last")


def leaf_paths(n_layers: int) -> dict:
    """``{name: path in the parameter tree}`` of :data:`LEAVES` for a
    stack of ``n_layers``."""
    at = {"last": n_layers - 1, "first": 0}
    return {name: tuple(at.get(key, key) for key in path)
            for name, path in LEAVES.items()}


def stream_ids(tokens, masked, mask_id: int):
    """The ids [2 L] the stack embeds for one sequence ``tokens`` [L], the
    noised copy first (also what ``level_placement`` places the experts
    by: the mask row's share of the rows included)."""
    return jnp.concatenate([jnp.where(masked, mask_id, tokens), tokens])


def layout(length: int, running_positions: bool = False):
    """``(noised [2 L] bool, pos [2 L], rotary_pos [2 L])`` of
    :func:`stream_ids`'s rows: which half, the position in the sequence,
    and the position the rotation takes (the same, but for the control
    that numbers the clean sequence ``0..L-1`` and its copy after it)."""
    noised = jnp.arange(2 * length) < length
    pos = jnp.tile(jnp.arange(length), 2)
    return noised, pos, (jnp.where(noised, length + pos, pos)
                         if running_positions else pos)


def loss_and_tail_grads(params, tokens, masked, rates, *, dims: dict,
                        low_precision=None, rule: str = "block_diffusion",
                        running_positions: bool = False,
                        weighted: bool = True, shift: int = 0,
                        names=CHECKED):
    """``(loss, {name: gradient for name in names}, stats)`` of the batch
    ``tokens`` [B, L], ``masked`` [B, L] bool, ``rates`` [B, L / block]:
    the loss from a full forward pass; the gradients of the ``names``
    among :data:`LEAVES` from a backward pass down to the lowest layer
    that holds one of them (the last, for :data:`CHECKED`; ``embed``: all
    of them); ``stats``: ``"rows"`` [layers, held], the assignments each
    held expert receives, ``"masked_share"``, the share of the noised
    copy that is the mask id.

    ``dims``: ``n_heads``, ``n_kv_heads``, ``head_dim``, ``eps``,
    ``theta``, ``top_k``, ``held_from``, ``block``, ``mask_id``."""
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
    n_layers, length = len(params["layers"]), tokens.shape[1]
    low, eps, block = low_precision, dims["eps"], dims["block"]
    paths = leaf_paths(n_layers)
    pivot = (0 if "embed" in names else
             min([paths[name][1] for name in names
                  if paths[name][0] == "layers"], default=n_layers))
    held = params["layers"][0]["w_up"].shape[0]

    rows = layout(length, running_positions)

    def body(x, layer):
        return _layer(x, layer, rows, dims, low, rule)

    def trunk(seq_ids):
        """One sequence's rows up to layer ``pivot``."""
        return _through(params["embed"][seq_ids], params["layers"][:pivot],
                        body, held)

    def tail(checked, x_mid, ids, labels, weights):
        swapped = params
        for name, value in checked.items():
            swapped = _with_leaf(swapped, paths[name], value)

        def one_sequence(seq):
            x, seq_ids, lb, w = seq
            if pivot == 0:
                x = swapped["embed"][seq_ids]
            x, counts = _through(x, swapped["layers"][pivot:],
                                 jax.checkpoint(body), held)
            # The head reads the noised copy alone: this file's first half.
            nll = _nll_rows(x[:length], swapped["ln_f_scale"],
                            swapped["head"], jnp.roll(lb, -shift), eps, low)
            return jnp.sum(w * nll) / length, counts

        losses, counts = lax.map(one_sequence, (x_mid, ids, labels, weights))
        return losses.mean(), counts

    per_token = jnp.repeat(rates, block, axis=1)
    weights = jnp.where(masked, 1.0 / per_token if weighted else 1.0, 0.0)
    ids = jax.vmap(lambda t, m: stream_ids(t, m, dims["mask_id"]))(
        tokens, masked)
    checked = {name: leaf(params, paths[name]) for name in names}
    with jax.default_matmul_precision("highest"):
        x_mid, rows_below = lax.map(trunk, ids)
        (loss, rows_above), grads = jax.value_and_grad(tail, has_aux=True)(
            checked, x_mid, ids, tokens, weights)
    counts = jnp.concatenate([rows_below, rows_above], axis=1).sum(0)
    return loss, grads, {"rows": counts,
                         "masked_share": jnp.mean(masked.astype(jnp.float32))}


def loss_block_by_block(params, tokens, masked, rates, *, dims: dict):
    """The objective by its definition, for tiny sizes: for each block
    ``k`` of each sequence one forward pass of ``(k + 1) * block`` rows,
    the clean blocks before ``k`` and then block ``k`` noised, every row at
    its own position under the block-causal mask (a block reads itself in
    both directions and every block before it); block ``k``'s rows go to
    the head and give its masked tokens' weighted terms.  The loss alone."""
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
    batch, length = tokens.shape
    block, eps = dims["block"], dims["eps"]
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for b in range(batch):
            for k in range(length // block):
                end = (k + 1) * block
                here = jnp.arange(end) >= k * block
                ids = jnp.where(here & masked[b, :end], dims["mask_id"],
                                tokens[b, :end])
                pos = jnp.arange(end)
                # Nothing is "noised" to the mask: one block-causal
                # sequence (the third rule).
                rows = (jnp.zeros(end, bool), pos, pos)
                x = params["embed"][ids]
                for layer in params["layers"]:
                    x, _ = _layer(x, layer, rows, dims, None,
                                  "block_diffusion")
                logp = jax.nn.log_softmax(
                    _rmsnorm(x[k * block:], params["ln_f_scale"], eps)
                    @ params["head"], axis=-1)
                nll = -jnp.take_along_axis(
                    logp, tokens[b, k * block:end, None], axis=-1)[:, 0]
                total = total + jnp.sum(jnp.where(
                    masked[b, k * block:end], nll / rates[b, k], 0.0))
    return total / (batch * length)


__all__ = ["CHECKED", "LEAVES", "RULES", "leaf", "leaf_paths",
           "level_placement", "loss_and_tail_grads", "loss_block_by_block",
           "layout", "stream_ids", "visible", "with_leaf"]
