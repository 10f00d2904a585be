"""Plain reference of the Keye-VL-2.0 language stack the ``dsa_moe_lm``
cells train.

Straight ``jax.numpy`` in float32 at precision ``highest``: no kernel, no
``shard_map``, no bf16, the selection by ``lax.top_k`` on the reference's
**own** float32 scores, plain attention with the scores of a block of query
rows written out, and **a dense loop over the held experts** (no sort, no
grouped matmul).  It shares no code with ``horovod_tpu/``; it reads the
program's parameter tree (``embed``, ``head``, ``ln_f_scale``,
``layers[i]``) because that tree is what a checkpoint of the system holds.

Every layer is pre-norm, two halves: ``x <- x + attention(RMSNorm(x))``,
then ``x <- x + experts(RMSNorm(x))``, ``u`` the normed input
(``perfbench/configs/keye-vl-2.0-30b-a3b.json``, ``assumed``):

* ``q = u W_q`` as ``H`` heads, ``k = u W_k``, ``v = u W_v`` as ``Hkv``
  heads of ``head_dim``; q and k RMS-normed **per head** with one learned
  scale each, then turned by the rotary embedding (pairs ``(i, i + D/2)``,
  angle ``position * theta^(-2i/D)``);
* the indexer, from ``stop_gradient(u)``: ``qI = u W_qI`` as ``HI`` heads
  of ``DI``, ``kI = u W_kI`` ONE head, ``w = u W_w``; qI and kI rotary over
  all ``DI`` dims; ``I[t, s] = c * sum_j w[t, j] relu(qI[t, j] . kI[s])``,
  ``c = (HI * DI) ** -0.5``;
* ``S_t``: the ``topk`` keys ``s <= t`` with the largest ``I[t, s]``
  (``lax.top_k``: equal scores, the lower index first), every key while
  ``t < topk``; one set a token for all heads;
* head ``h`` reads key-value head ``h // (H / Hkv)``; softmax over ``S_t``
  of ``q . k / sqrt(head_dim)``; ``out = concat(o) W_o``;
* the indexer's loss: ``p[t, .] = stop_gradient(mean over heads of the
  softmax)``, ``KL(p[t, .] || softmax over S_t of I[t, .])``, its mean over
  tokens summed over layers, times ``index_coef``, beside the
  cross-entropy;
* the experts: ``softmax(u W_r)`` over all experts in float32, the ``k``
  largest (equal: the lower index), their weights divided by their sum;
  expert ``e`` is ``W_down,e (silu(W_gate,e u) * W_up,e u)``; ``out = sum
  over the chosen experts **that the tree holds** of w_e expert_e(u)``.

Devices that change no arithmetic: every layer of the differentiated
tail, every block of query rows and every block of the head is under
``jax.checkpoint``; sequences go one at a time (``lax.map``); layers and
held experts are walked by ``lax.scan`` over their stacked matrices, so
that the compiler meets one layer's and one expert's body (the unrolled
form took 140 s of every run's set-up to compile: PERF.md, PR 39).
The gradients come from a backward pass through the lowest layer that
holds a requested leaf and everything above it.

For the experiments that set and test the tolerances (PERF.md, PR 39;
``tests/test_dsa_moe_lm.py``): ``low_precision`` rounds every matmul's
operands, and q, k, v, qI and kI, to that dtype; ``topk`` overrides the
configuration's; ``select=False`` attends to every earlier key (and takes
the indexer's loss over them); ``index_coef=0`` leaves the indexer's loss
out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 256
HEAD_BLOCK = 1024


def _round(x, low_precision):
    """``x`` rounded to ``low_precision``'s mantissa at float32's exponent
    range (what a scaled float8 tensor keeps of it), gradients straight
    through (a float8 cotangent would underflow to zero).  By
    ``lax.reduce_precision``, which no compiler pass removes: a convert
    there and back is what XLA's excess precision takes out on a TPU
    wherever it sees both (outside a loop's body, not inside one:
    PERF.md, PR 39), and with the dtype's own exponent bits the weights of
    standard deviation 0.02 would flush to zero."""
    if low_precision is None:
        return x
    return x + lax.stop_gradient(lax.reduce_precision(
        x, exponent_bits=8, mantissa_bits=jnp.finfo(low_precision).nmant) - x)


def _mm(a, b, low):
    return _round(a, low) @ _round(b, low)


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _rotary(x, theta):
    """``x`` [T, H, r] at positions 0..T-1, pairs ``(i, i + r/2)``."""
    t, _, r = x.shape
    inv_freq = theta ** (-jnp.arange(r // 2, dtype=jnp.float32) / (r // 2))
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = x[..., :r // 2], x[..., r // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def selection(scores, first_row, topk):
    """[rows, T] bool: what rows ``first_row ..`` select from their
    ``scores`` [rows, T]: every key at or before the row while there are no
    more than ``topk``, else the ``topk`` largest of them."""
    rows, t = scores.shape
    qpos = first_row + jnp.arange(rows)
    causal = jnp.arange(t)[None, :] <= qpos[:, None]
    if topk >= t:
        return causal
    _, chosen = lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    hit = jnp.zeros((rows, t), bool).at[
        jnp.arange(rows)[:, None], chosen].set(True)
    return causal & (hit | (qpos[:, None] < topk))


def _index_scores(qi, ki, w, dims):
    """[rows, T] float32: ``c * sum_j w[t, j] relu(qI[t, j] . kI[s])`` of
    the rows' qi [rows, HI, DI] and w [rows, HI] against every kI [T, DI],
    ``c = (HI * DI) ** -0.5``."""
    scale = (dims["index_heads"] * dims["index_head_dim"]) ** -0.5
    index = jnp.einsum("qjd,sd->jqs", qi, ki)
    return scale * jnp.einsum("jqs,qj->qs", jnp.maximum(index, 0.0), w)


def _sparse_attention(q, k, v, qi, ki, w, topk, select, dims):
    """q [T, H, D], k/v [T, Hkv, D], qi [T, HI, DI], ki [T, DI], w [T, HI]
    of one sequence -> ``(o [T, H, D], the rows' KL summed)``, a block of
    query rows at a time."""
    t, h, d = q.shape
    hkv = k.shape[1]
    block = min(QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"sequence length {t} not a multiple of {block}")

    @jax.checkpoint
    def one_block(start):
        rows = lambda a: lax.dynamic_slice_in_dim(a, start, block, axis=0)
        scores = _index_scores(rows(qi), ki, rows(w), dims)
        if select:
            sel = selection(lax.stop_gradient(scores), start, topk)
        else:
            sel = (jnp.arange(t)[None, :]
                   <= (start + jnp.arange(block))[:, None])
        qg = rows(q).reshape(block, hkv, h // hkv, d)
        s = jnp.einsum("qkgd,skd->kgqs", qg, k) * (d ** -0.5)
        p = jax.nn.softmax(jnp.where(sel[None, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", p, v).reshape(block, h, d)
        target = lax.stop_gradient(jnp.mean(p, axis=(0, 1)))
        log_q = jax.nn.log_softmax(jnp.where(sel, scores, -jnp.inf), axis=-1)
        live = sel & (target > 0.0)
        kl = jnp.sum(jnp.where(
            live, target * (jnp.log(jnp.where(live, target, 1.0)) - log_q),
            0.0))
        return o, kl

    o, kl = lax.map(one_block, jnp.arange(0, t, block))
    return o.reshape(q.shape), jnp.sum(kl)


def _indexer(u, layer, dims, low):
    t = u.shape[0]
    u = lax.stop_gradient(u)
    qi = _mm(u, layer["index_wq"], low).reshape(
        t, dims["index_heads"], dims["index_head_dim"])
    ki = _mm(u, layer["index_wk"], low)[:, None, :]
    w = _mm(u, layer["index_ww"], low)
    return (_round(_rotary(qi, dims["theta"]), low),
            _round(_rotary(ki, dims["theta"])[:, 0], low), w)


def _qkv(u, layer, dims, low):
    t = u.shape[0]
    hd, eps, theta = dims["head_dim"], dims["eps"], dims["theta"]
    q = _mm(u, layer["wq"], low).reshape(t, dims["n_heads"], hd)
    k = _mm(u, layer["wk"], low).reshape(t, dims["n_kv_heads"], hd)
    v = _mm(u, layer["wv"], low).reshape(t, dims["n_kv_heads"], hd)
    q = _rotary(_rmsnorm(q, layer["q_norm_scale"], eps), theta)
    k = _rotary(_rmsnorm(k, layer["k_norm_scale"], eps), theta)
    return _round(q, low), _round(k, low), _round(v, low)


def _attention_part(u, layer, dims, low, topk, select):
    """``(the attention half's output [T, d], the rows' KL summed)``."""
    q, k, v = _qkv(u, layer, dims, low)
    qi, ki, w = _indexer(u, layer, dims, low)
    o, kl = _sparse_attention(q, k, v, qi, ki, w, topk, select, dims)
    return _mm(o.reshape(u.shape[0], -1), layer["wo"], low), kl


def selected(x, layer, dims, topk=None):
    """[T, T] bool: the keys each query of one sequence selects in
    ``layer``, given the layer's input ``x`` [T, d]: float32 scores at
    precision ``highest``, :func:`selection`."""
    layer = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), layer)
    with jax.default_matmul_precision("highest"):
        u = _rmsnorm(x.astype(jnp.float32), layer["ln1_scale"], dims["eps"])
        scores = _index_scores(*_indexer(u, layer, dims, None), dims)
    return selection(scores, 0, topk or dims["topk"])


def _swiglu(u, w_gate, w_up, w_down, low):
    return _mm(_silu(_mm(u, w_gate, low)) * _mm(u, w_up, low), w_down, low)


def _expert_weights(u, layer, dims):
    """[T, E] float32: a token's weight for every expert the router
    scores, zero for those it did not choose."""
    probs = jax.nn.softmax(u @ layer["router"], axis=-1)
    ranked = jnp.argsort(-probs, axis=-1, stable=True)
    rank = jnp.argsort(ranked, axis=-1)
    chosen = jnp.where(rank < dims["top_k"], probs, 0.0)
    return chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def _moe_part(u, layer, dims, low):
    """``(out [T, d], assignments per held expert [held])``."""
    weights = _expert_weights(u, layer, dims)
    held = layer["w_up"].shape[0]
    here = lax.dynamic_slice_in_dim(weights, dims["held_from"], held, axis=1)

    def one_expert(y, expert):
        w_gate, w_up, w_down, weight = expert
        return y + weight[:, None] * _swiglu(u, w_gate, w_up, w_down,
                                             low), None

    y, _ = lax.scan(one_expert, jnp.zeros_like(u), (
        layer["w_gate"], layer["w_up"], layer["w_down"], here.T))
    return y, lax.stop_gradient(jnp.sum(here > 0, axis=0))


def _nll_rows(x, ln_f_scale, head, labels, eps, low):
    """Next-token negative log-likelihood of each row of ``x`` [T, d]."""
    n = x.shape[0]
    block = min(HEAD_BLOCK, n)
    if n % block:
        raise ValueError(f"{n} tokens not a multiple of {block}")

    @jax.checkpoint
    def one_block(xl):
        xb, lb = xl
        logp = jax.nn.log_softmax(
            _mm(_rmsnorm(xb, ln_f_scale, eps), head, low), axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=-1)[:, 0]

    return lax.map(one_block, (x.reshape(n // block, block, -1),
                               labels.reshape(n // block, block))).reshape(n)


LEAVES = {
    "ln_f_scale": ("ln_f_scale",),
    "wo_last": ("layers", "last", "wo"),
    "wq_last": ("layers", "last", "wq"),
    "wk_last": ("layers", "last", "wk"),
    "wv_last": ("layers", "last", "wv"),
    "q_norm_last": ("layers", "last", "q_norm_scale"),
    "k_norm_last": ("layers", "last", "k_norm_scale"),
    "index_wq_last": ("layers", "last", "index_wq"),
    "index_wk_last": ("layers", "last", "index_wk"),
    "index_ww_last": ("layers", "last", "index_ww"),
    "router_last": ("layers", "last", "router"),
    "w_gate_last": ("layers", "last", "w_gate"),
    "w_up_last": ("layers", "last", "w_up"),
    "w_down_last": ("layers", "last", "w_down"),
    "ln1_last": ("layers", "last", "ln1_scale"),
    "ln2_last": ("layers", "last", "ln2_scale"),
    "wo_first": ("layers", "first", "wo"),
    "index_wq_first": ("layers", "first", "index_wq"),
}
CHECKED = ("ln_f_scale", "wo_last", "wk_last", "index_wq_last")


def leaf_paths(n_layers: int) -> dict:
    """``{name: path in the parameter tree}`` of :data:`LEAVES` for a
    stack of ``n_layers``."""
    at = {"last": n_layers - 1, "first": 0}
    return {name: tuple(at.get(key, key) for key in path)
            for name, path in LEAVES.items()}


def leaf(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _with_leaf(tree, path, value):
    """``tree`` with the leaf at ``path`` replaced (copies on the way)."""
    if not path:
        return value
    copy = list(tree) if isinstance(tree, (list, tuple)) else dict(tree)
    copy[path[0]] = _with_leaf(tree[path[0]], path[1:], value)
    return copy


def _nearest_the_mean(loads, held: int, held_from: int):
    """A permutation ``perm`` [E] of the experts that puts the ``held``
    whose ``loads`` [E] lie nearest their mean at the places ``held_from
    .. held_from + held``, nearest first, and the others around them in
    that order (``glm-4.7-flash``'s placement, PERF.md PR 37)."""
    experts = loads.shape[0]
    loads = loads.astype(jnp.float32)
    order = jnp.argsort(jnp.abs(loads - jnp.mean(loads)), stable=True)
    at = jnp.arange(experts)
    place = jnp.where(at < held, held_from + at,
                      jnp.where(at - held < held_from, at - held, at))
    return jnp.zeros((experts,), order.dtype).at[place].set(order)


def level_placement(params, tokens, *, dims: dict):
    """One permutation [E] of the router's columns per layer, for the
    sequence ``tokens`` [T] (:func:`_nearest_the_mean`): each layer placed
    by the assignments its router gives the normed **embedded tokens**.
    That is a layer's own input but for what the layers below have added
    to the residual stream, which at the configuration's embedding scale
    and out-projection shrink is a few hundredths of it: the reference
    prints the rows every held expert does receive.  (Running the stack
    for it, as ``glm-4.7-flash``'s placement does, cost 77 s of every
    run's set-up: PERF.md, PR 39.)  The experts' matrices are drawn alike,
    so permuting the router's columns is choosing which of them this chip
    holds: those a balanced router would load alike."""
    x = params["embed"][tokens].astype(jnp.float32)
    perms = []
    for layer in params["layers"]:
        u = _rmsnorm(x, layer["ln2_scale"].astype(jnp.float32), dims["eps"])
        weights = _expert_weights(
            u, {"router": layer["router"].astype(jnp.float32)}, dims)
        perms.append(_nearest_the_mean(jnp.sum(weights > 0, axis=0),
                                       layer["w_up"].shape[0],
                                       dims["held_from"]))
    return perms


def loss_and_tail_grads(params, tokens, labels, *, dims: dict,
                        index_coef: float, low_precision=None, topk=None,
                        select: bool = True, names=CHECKED):
    """``(loss, {name: gradient for name in names}, stats)`` of the batch
    ``tokens`` [B, T]: the loss (both terms) from a full forward pass; the
    gradients of the ``names`` among :data:`LEAVES` from a backward pass
    down to the lowest layer that holds one of them (the last, for
    :data:`CHECKED`); ``stats``: ``"rows"`` [layers, held], the assignments
    each held expert receives, ``"ce"`` and ``"index_kl"``, the two terms
    (the second before its coefficient).

    ``dims``: ``n_heads``, ``n_kv_heads``, ``head_dim``, ``index_heads``,
    ``index_head_dim``, ``topk``, ``eps``, ``theta``, ``top_k``,
    ``held_from``."""
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
    n_layers = len(params["layers"])
    low, eps = low_precision, dims["eps"]
    topk = topk or dims["topk"]
    paths = leaf_paths(n_layers)
    pivot = min([paths[name][1] for name in names
                 if paths[name][0] == "layers"], default=n_layers)

    def block(x, layer):
        """``(the layer's output, its rows' KL summed, assignments per
        held expert)``."""
        out, kl = _attention_part(_rmsnorm(x, layer["ln1_scale"], eps),
                                  layer, dims, low, topk, select)
        x = x + out
        y, count = _moe_part(_rmsnorm(x, layer["ln2_scale"], eps), layer,
                             dims, low)
        return x + y, kl, count

    held = params["layers"][0]["w_up"].shape[0]

    def through(x, layers, body):
        """``x`` through ``layers`` by ``body``: ``(x, their KLs summed,
        assignments [layers, held])``.  The layers go stacked, as one tree
        with a leading axis that ``lax.scan`` walks."""
        if not layers:
            return x, jnp.zeros(()), jnp.zeros((0, held), jnp.int32)

        def one_layer(x, layer):
            x, kl, count = body(x, layer)
            return x, (kl, count.astype(jnp.int32))

        x, (kls, counts) = lax.scan(one_layer, x, jax.tree_util.tree_map(
            lambda *a: jnp.stack(a), *layers))
        return x, jnp.sum(kls), counts

    def trunk(tok):
        """One sequence up to layer ``pivot``."""
        return through(params["embed"][tok], params["layers"][:pivot],
                       block)

    def tail(checked, x_mid, kl_below, lab):
        swapped = params
        for name, value in checked.items():
            swapped = _with_leaf(swapped, paths[name], value)

        def one_sequence(xl):
            x, kls, lb = xl
            x, kl, counts = through(x, swapped["layers"][pivot:],
                                    jax.checkpoint(block))
            t = x.shape[0]
            ce = _nll_rows(x, swapped["ln_f_scale"], params["head"], lb,
                           eps, low).sum() / t
            return ce, (kls + kl) / t, counts

        ce, kl, counts = lax.map(one_sequence, (x_mid, kl_below, lab))
        ce, kl = ce.mean(), kl.mean()
        return ce + index_coef * kl, (counts, ce, kl)

    checked = {name: leaf(params, paths[name]) for name in names}
    with jax.default_matmul_precision("highest"):
        x_mid, kl_below, rows_below = lax.map(trunk, tokens)
        (loss, (rows_above, ce, kl)), grads = jax.value_and_grad(
            tail, has_aux=True)(checked, x_mid, kl_below, labels)
    # [sequences, layers, held] summed over the sequences.
    counts = jnp.concatenate([rows_below, rows_above], axis=1).sum(0)
    return loss, grads, {"rows": counts, "ce": ce, "index_kl": kl}
