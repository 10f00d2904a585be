"""Plain reference of the GLM-4.7-Flash stack the ``mla_moe_lm`` cells
train.

Straight ``jax.numpy`` in float32 at precision ``highest``: no kernel, no
``shard_map``, no bf16, plain attention over keys and values written out
per head, and **a dense loop over the held experts** (no sort, no grouped
matmul).  It shares no code with ``horovod_tpu/``; it reads the program's
parameter tree (``embed``, ``head``, ``ln_f_scale``, ``layers[i]``,
``mtp``) because that tree is what a checkpoint of the system holds.

Every layer is pre-norm, two halves: ``x <- x + attention(RMSNorm(x))``,
then ``x <- x + ffn(RMSNorm(x))``, ``u`` the normed input
(``perfbench/configs/glm-4.7-flash.json``):

* latent attention: ``c_q = RMSNorm(u W_qa)``; ``q = c_q W_qb`` as ``H``
  heads of ``[q_n | q_r]``; ``[c_kv | k_r] = u W_kva``, ``c_kv <-
  RMSNorm(c_kv)``; ``[k_n | v] = c_kv W_kvb`` per head; ``q_r`` and ``k_r``
  turned by the rotary embedding (pairs ``(i, i + r/2)`` of the ``r``
  rotary dims, angle ``position * theta^(-2i/r)``), ``k_r`` **one head,
  the same for all ``H``**; ``k = [k_n | k_r]``; causal softmax at scale
  ``head_dim ** -0.5``; ``out = o W_o``;
* the leading ``dense_layers`` layers' ffn: ``W_down (silu(W_gate u) *
  W_up u)``;
* every other layer's, the experts: ``s = sigmoid(u W_r)`` over all
  experts; the ``k`` with the largest ``s + bias`` are chosen; weights ``w
  = scale * s[chosen] / (sum of s[chosen] + 1e-20)``; expert ``e`` is
  ``W_down,e (silu(W_gate,e u) * W_up,e u)``; ``routed = sum over the
  chosen experts **that the tree holds** of w_e expert_e(u)``; ``shared``
  the same form on ``u`` for every token; ``out = routed + shared``;
* the prediction module: ``[RMSNorm(embed(x_{t+1})); RMSNorm(h_t)] W_eh``
  (``h_t`` the stack's output before the final norm), one such layer with
  experts, the module's own final norm and the model's head, against
  ``x_{t+2}`` over the ``T - 1`` positions that have one.

Memory devices that change no arithmetic: every layer of the
differentiated tail, every block of query rows and every block of the
head is under ``jax.checkpoint``; sequences go one at a time
(``lax.map``).  The gradients come from a backward pass through the
lowest layer that holds a requested leaf and everything above it: they
depend on nothing below.

For the experiments that set and test the tolerances (PERF.md, PR 37;
``tests/test_mla_moe_lm.py``): ``low_precision`` rounds every matmul's
operands, and q, k and v, to that dtype; ``shared_expert=False`` leaves
the shared expert out; ``rotate_shared_key=False`` leaves ``k_r`` as the
projection gives it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 256
HEAD_BLOCK = 1024


def _round(x, low_precision):
    """``x`` rounded to ``low_precision``, gradients straight through (a
    float8 cotangent would underflow to zero)."""
    if low_precision is None:
        return x
    return x + lax.stop_gradient(
        x.astype(low_precision).astype(jnp.float32) - x)


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


def _attention(q, k, v):
    """q, k, v: [T, H, D] of one sequence; causal softmax attention,
    scores materialised a block of query rows at a time."""
    t, _, d = q.shape
    block = min(QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"sequence length {t} not a multiple of {block}")
    kpos = jnp.arange(t)

    @jax.checkpoint
    def one_block(start):
        qb = lax.dynamic_slice_in_dim(q, start, block, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * (d ** -0.5)
        qpos = start + jnp.arange(block)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s,
                      -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    return lax.map(one_block, jnp.arange(0, t, block)).reshape(q.shape)


def _attention_part(u, layer, dims, low, rotate_shared_key):
    t = u.shape[0]
    heads, hd, rope, rank = (dims["n_heads"], dims["head_dim"],
                             dims["rope_dim"], dims["kv_rank"])
    nope, theta = hd - rope, dims["theta"]
    c_q = _rmsnorm(_mm(u, layer["w_qa"], low), layer["q_latent_norm_scale"],
                   dims["eps"])
    q = _mm(c_q, layer["w_qb"], low).reshape(t, heads, hd)
    down = _mm(u, layer["w_kva"], low)
    c_kv = _rmsnorm(down[:, :rank], layer["kv_latent_norm_scale"],
                    dims["eps"])
    up = _mm(c_kv, layer["w_kvb"], low).reshape(t, heads, nope + hd)
    k_n, v = up[..., :nope], up[..., nope:]
    q = jnp.concatenate([q[..., :nope], _rotary(q[..., nope:], theta)],
                        axis=-1)
    k_r = down[:, None, rank:]
    if rotate_shared_key:
        k_r = _rotary(k_r, theta)
    # The one rotary key, at the end of every head's.
    k = jnp.concatenate([k_n, jnp.repeat(k_r, heads, axis=1)], axis=-1)
    o = _attention(_round(q, low), _round(k, low), _round(v, low))
    return _mm(o.reshape(t, heads * hd), layer["wo"], low)


def _swiglu(u, w_gate, w_up, w_down, low):
    return _mm(_silu(_mm(u, w_gate, low)) * _mm(u, w_up, low), w_down, low)


def _expert_weights(u, layer, dims):
    """[T, E] float32: a token's weight for every expert the router
    scores, zero for those it did not choose."""
    scores = jax.nn.sigmoid(u @ layer["router"])
    ranked = jnp.argsort(-(scores + layer["router_bias"]), axis=-1,
                         stable=True)
    rank = jnp.argsort(ranked, axis=-1)
    chosen = jnp.where(rank < dims["top_k"], scores, 0.0)
    return dims["routed_scale"] * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)


def _moe_part(u, layer, dims, low, shared_expert):
    """``(out [T, d], assignments per held expert [held])``."""
    weights = _expert_weights(u, layer, dims)
    held = layer["w_up"].shape[0]
    here = lax.dynamic_slice_in_dim(weights, dims["held_from"], held, axis=1)
    y = jnp.zeros_like(u)
    # The experts the tree holds, one after another, every token through
    # each: a token that did not choose one has weight zero for it.
    for j in range(held):
        out = _swiglu(u, layer["w_gate"][j], layer["w_up"][j],
                      layer["w_down"][j], low)
        y = y + here[:, j, None] * out
    if shared_expert:
        y = y + _swiglu(u, layer["w_shared_gate"], layer["w_shared_up"],
                        layer["w_shared_down"], low)
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


# The leaves whose gradients the backward pass can return, by where they
# sit in the parameter tree: "last" is the stack's last layer (an expert
# layer), "dense" its first (a dense one).
LEAVES = {
    "ln_f_scale": ("ln_f_scale",),
    "mtp_w_eh": ("mtp", "w_eh"),
    "wo_last": ("layers", "last", "wo"),
    "w_kvb_last": ("layers", "last", "w_kvb"),
    "w_shared_down_last": ("layers", "last", "w_shared_down"),
    "w_qb_last": ("layers", "last", "w_qb"),
    "w_kva_last": ("layers", "last", "w_kva"),
    "q_latent_norm_last": ("layers", "last", "q_latent_norm_scale"),
    "kv_latent_norm_last": ("layers", "last", "kv_latent_norm_scale"),
    "router_last": ("layers", "last", "router"),
    "w_down_last": ("layers", "last", "w_down"),
    "w_down_dense": ("layers", "dense", "w_down"),
}
# What the cell's check compares (the configuration's ``check`` says why
# these): leaves whose reading is set by the arithmetic's precision.
CHECKED = ("ln_f_scale", "mtp_w_eh", "wo_last", "w_kvb_last",
           "w_shared_down_last")


def leaf_paths(n_layers: int) -> dict:
    """``{name: path in the parameter tree}`` of :data:`LEAVES` for a
    stack of ``n_layers``."""
    at = {"last": n_layers - 1, "dense": 0}
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
    that order.  ``router[:, perm]`` then shows this chip experts that
    are loaded as a balanced router loads every expert."""
    experts = loads.shape[0]
    loads = loads.astype(jnp.float32)
    order = jnp.argsort(jnp.abs(loads - jnp.mean(loads)), stable=True)
    at = jnp.arange(experts)
    place = jnp.where(at < held, held_from + at,
                      jnp.where(at - held < held_from, at - held, at))
    return jnp.zeros((experts,), order.dtype).at[place].set(order)


def level_placement(params, tokens, labels, *, dims: dict,
                    dense_layers: int):
    """``(one permutation [E] of the router's columns per expert layer of
    the stack, one per layer of the prediction module)`` for the sequence
    ``tokens`` [T] (:func:`_nearest_the_mean`): the stack runs once from
    the embedding up, at the default matmul precision (this is set-up, not
    the check), each expert layer placed by the assignments its own input
    gives before its output goes on.  No score and no bias changes: the
    experts' matrices are drawn alike, so permuting the router's columns
    is choosing which of them this chip holds.  Why: a trained router is
    balanced and sends every expert about the mean; a seeded one under
    Zipf tokens is lumpy (the most frequent token is a tenth of the batch
    and all of it goes to the same four experts), eight experts taken as
    drawn receive 190-1,600 rows each, and the step's time follows how
    many of the eight groups are too short to hide the next group's
    weights (PERF.md, PR 37)."""
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
    eps, mtp = dims["eps"], params["mtp"]

    def attended(x, layer):
        return x + _attention_part(_rmsnorm(x, layer["ln1_scale"], eps),
                                   layer, dims, None, True)

    def placed(x, layer):
        x = attended(x, layer)
        u = _rmsnorm(x, layer["ln2_scale"], eps)
        loads = jnp.sum(_expert_weights(u, layer, dims) > 0, axis=0)
        perm = _nearest_the_mean(loads, layer["w_up"].shape[0],
                                 dims["held_from"])
        y, _ = _moe_part(u, dict(layer, router=layer["router"][:, perm],
                                 router_bias=layer["router_bias"][perm]),
                         dims, None, True)
        return perm, x + y

    x, stack, module = params["embed"][tokens], [], []
    for i, layer in enumerate(params["layers"]):
        if i < dense_layers:
            x = attended(x, layer)
            x = x + _swiglu(_rmsnorm(x, layer["ln2_scale"], eps),
                            layer["w_gate"], layer["w_up"], layer["w_down"],
                            None)
        else:
            perm, x = placed(x, layer)
            stack.append(perm)
    h = jnp.concatenate([
        _rmsnorm(params["embed"][labels], mtp["embed_norm_scale"], eps),
        _rmsnorm(x, mtp["hidden_norm_scale"], eps)], axis=-1) @ mtp["w_eh"]
    for layer in mtp["layers"]:
        perm, h = placed(h, layer)
        module.append(perm)
    return stack, module


def loss_and_tail_grads(params, tokens, labels, *, dims: dict,
                        dense_layers: int, mtp_coef: float,
                        low_precision=None, shared_expert: bool = True,
                        rotate_shared_key: bool = True, names=CHECKED):
    """``(loss, {name: gradient for name in names}, stats)`` of the
    batch ``tokens`` [B, T]: the loss (both terms) from a full forward
    pass; the gradients of the ``names`` among :data:`LEAVES` from a
    backward pass down to the lowest layer that holds one of them (the
    last, for :data:`CHECKED`); ``stats``: ``"rows"`` [expert layers (the
    module's last), held], the assignments each held expert receives.

    ``dims``: ``n_heads``, ``head_dim``, ``rope_dim``, ``kv_rank``,
    ``eps``, ``theta``, ``top_k``, ``routed_scale``, ``held_from``."""
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
    n_layers = len(params["layers"])
    low, eps = low_precision, dims["eps"]
    paths = leaf_paths(n_layers)
    pivot = min([paths[name][1] for name in names
                 if paths[name][0] == "layers"], default=n_layers)

    def block(x, layer, dense):
        """``(the layer's output, an expert layer's assignments per held
        expert: () from a dense one)``."""
        x = x + _attention_part(_rmsnorm(x, layer["ln1_scale"], eps), layer,
                                dims, low, rotate_shared_key)
        u = _rmsnorm(x, layer["ln2_scale"], eps)
        if dense:
            return x + _swiglu(u, layer["w_gate"], layer["w_up"],
                               layer["w_down"], low), ()
        y, count = _moe_part(u, layer, dims, low, shared_expert)
        return x + y, (count,)

    def trunk(tok):
        """One sequence up to layer ``pivot``, the rows of each expert
        layer on the way."""
        x = params["embed"][tok]
        counts = ()
        for i in range(pivot):
            x, count = block(x, params["layers"][i], i < dense_layers)
            counts += count
        return x, counts

    def tail(checked, x_mid, lab):
        swapped = params
        for name, value in checked.items():
            swapped = _with_leaf(swapped, paths[name], value)
        layers, mtp = swapped["layers"], swapped["mtp"]

        def one_sequence(xl):
            x, lb = xl
            counts = ()
            for i in range(pivot, n_layers):
                x, count = jax.checkpoint(block, static_argnums=2)(
                    x, layers[i], i < dense_layers)
                counts += count
            t = x.shape[0]
            main = _nll_rows(x, swapped["ln_f_scale"], params["head"], lb,
                             eps, low).sum() / t
            # The prediction module: x_{t+1}'s embedding beside h_t.
            h = _mm(jnp.concatenate([
                _rmsnorm(params["embed"][lb], mtp["embed_norm_scale"], eps),
                _rmsnorm(x, mtp["hidden_norm_scale"], eps)], axis=-1),
                mtp["w_eh"], low)
            for layer in mtp["layers"]:
                h, count = jax.checkpoint(block, static_argnums=2)(
                    h, layer, False)
                counts += count
            second = jnp.concatenate([lb[1:], lb[:1]])
            ahead = _nll_rows(h, mtp["ln_f_scale"], params["head"], second,
                              eps, low)[:-1].sum() / (t - 1)
            return main + mtp_coef * ahead, counts

        losses, counts = lax.map(one_sequence, (x_mid, lab))
        return losses.mean(), counts

    checked = {name: leaf(params, paths[name]) for name in names}
    with jax.default_matmul_precision("highest"):
        x_mid, below = lax.map(trunk, tokens)
        (loss, above), grads = jax.value_and_grad(tail, has_aux=True)(
            checked, x_mid, labels)
    counts = jnp.stack([c.sum(0) for c in list(below) + list(above)])
    return loss, grads, {"rows": counts}
