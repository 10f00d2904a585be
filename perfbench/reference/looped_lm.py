"""Plain reference of the looped decoder the ``looped_lm`` cells train.

Straight ``jax.numpy`` in float32 at precision ``highest``: no kernel, no
``shard_map``, no bf16, a Python loop over the passes and over the layers,
one sequence at a time, attention a block of query rows against the whole
causal context, the head a block of rows at a time.  It shares no code
with ``horovod_tpu/``; it reads the program's parameter tree (``embed``,
``head``, ``ln_f_scale``, ``exit_gate_w``, ``exit_gate_b``, ``layers[i]``)
because that tree is what a checkpoint of the system holds.

The model (``perfbench/configs/ouro-2.6b.json``; arXiv:2510.25741):

* ``h^(0) = E[x]``, no position table;
* one layer, two sandwich-normed branches, every RMSNorm (eps) with a
  scale of its own: ``x <- x + RMSNorm_2(Attn(RMSNorm_1(x)))``, ``x <- x +
  RMSNorm_4(MLP(RMSNorm_3(x)))`` (``ln1_scale``, ``ln1_post_scale``,
  ``ln2_scale``, ``ln2_post_scale``);
* ``Attn``: ``q, k, v = u Wq, u Wk, u Wv`` as ``H`` heads each, no bias, no
  QK-norm, rotary over the whole head (rotate-half pairing, theta),
  causal softmax at scale ``head_dim ** -0.5``, ``Wo``;
* ``MLP``: ``W_down (silu(u W_gate) * (u W_up))``;
* the loop: for ``t = 1..L``: ``h^(t) = RMSNorm_f(Layers(h^(t-1)))``, the
  SAME layers and the SAME final norm every pass, the normed state carried;
* after every pass the logits ``z^(t) = h^(t) W_head``, the cross-entropy
  a token ``l_t`` and the gate ``lambda_t = sigmoid(h^(t) w_g + b_g)``;
* the exit distribution a token: ``S_0 = 1``, ``S_t = prod_{j<=t} (1 -
  lambda_j)``, ``p_t = lambda_t S_{t-1}`` for ``t < L`` and ``p_L =
  S_{L-1}``, as plain products;
* the loss: the mean over tokens of ``sum_t p_t l_t - beta H(p)``, ``H(p)
  = -sum_t p_t log p_t``.

With ``untied`` the parameter tree holds ``L x N`` layers and pass ``t``
runs its own ``N`` of them: the same model written as a stack of ``L N``
layers with the final norm between the quarters, which is what ties the
loop to its definition when the quarters are copies of one another
(``tests/test_looped_lm.py``).

Memory devices that change no arithmetic: every layer-pass, every block
of query rows and every block of rows of the head is under
``jax.checkpoint``; sequences go one at a time (``lax.map``); the
gradients are taken with respect to the requested leaves alone, so no
other leaf's is ever held.

For the experiments that set and test the tolerances (PERF.md, PR 51;
``perfbench/controls_looped_lm.py``), each another function:
``low_precision`` rounds every matmul's operands to that dtype, those of
attention's two (q, k; the probabilities, v) among them;
``loops`` runs another number of passes than the model's; ``cut_passes``
stops the gradient where a pass hands its state to the next (each pass's
loss reaches its own pass alone); ``norm_carried=False`` norms at the
readouts only and carries the un-normed state; ``post_norms=False`` leaves
the second norm of every branch out; ``uniform_exit`` puts 1 / L in the
gate's place; ``entropy=False`` leaves ``beta H(p)`` out;
``last_takes_rest=False`` gives the last pass ``lambda_L S_{L-1}`` like
the others (a distribution that does not sum to one); ``last_pass_only``
is the last pass's cross-entropy alone.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 256
HEAD_BLOCK = 1024

# The leaves a cell's check reads (``paths`` of :func:`leaf_paths`): the
# gate's weight (it learns through ``p`` and ``H`` alone), the final norm's
# scale (inside the carry, used every pass), the last layer's ``W_down``
# and second MLP norm (the top of every pass) and the first layer's ``W_k``
# (the bottom of every pass: four paths of depth N, 2N, 3N and 4N).
CHECKED = ("exit_gate_w", "ln_f_scale", "w_down_last", "ln2_post_last",
           "wk_first")


def leaf_paths(n_layers: int) -> dict:
    """``{name: path in the parameter tree}`` of :data:`CHECKED`."""
    last = n_layers - 1
    return {"exit_gate_w": ("exit_gate_w",), "ln_f_scale": ("ln_f_scale",),
            "w_down_last": ("layers", last, "w_down"),
            "ln2_post_last": ("layers", last, "ln2_post_scale"),
            "wk_first": ("layers", 0, "wk")}


def every_leaf(params) -> dict:
    """``{name: path}`` of every leaf of ``params``."""
    found = {}
    for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]:
        keys = tuple(getattr(k, "key", getattr(k, "idx", None))
                     for k in path)
        found[".".join(map(str, keys))] = keys
    return found


def leaf(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def with_leaf(tree, path, value):
    """``tree`` with the leaf at ``path`` replaced (copies on the way)."""
    if not path:
        return value
    copy = list(tree) if isinstance(tree, (list, tuple)) else dict(tree)
    copy[path[0]] = with_leaf(tree[path[0]], path[1:], value)
    return copy


def _round(x, low):
    """``x`` rounded to ``low`` (values rounded, gradients straight
    through: a float8 cotangent would underflow); ``x`` without it."""
    if low is None:
        return x
    return x + lax.stop_gradient(x.astype(low).astype(jnp.float32) - x)


def _mm(a, b, low):
    """``a @ b``; with ``low``, of operands rounded to it."""
    return _round(a, low) @ _round(b, low)


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, theta):
    """x: [T, H, D] of one sequence at positions 0..T-1, rotate-half."""
    t, _, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    half = d // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(emb) + turned * jnp.sin(emb)


def _attention(q, k, v, low):
    """q, k, v: [T, H, D] of one sequence; causal softmax attention, the
    operands of its two matmuls (q, k; the probabilities, v) rounded to
    ``low`` where that is given."""
    q, k, v = (_round(x, low) for x in (q, k, v))
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
        return jnp.einsum("hqk,khd->qhd",
                          _round(jax.nn.softmax(s, axis=-1), low), v)

    return lax.map(one_block, jnp.arange(0, t, block)).reshape(q.shape)


def _layer(x, layer, dims, low, post_norms):
    """One sequence ``x`` [T, d] through one layer."""
    t, eps = x.shape[0], dims["eps"]
    u = _rms(x, layer["ln1_scale"], eps)
    split = (t, dims["n_heads"], -1)
    o = _attention(_rope(_mm(u, layer["wq"], low).reshape(split),
                         dims["theta"]),
                   _rope(_mm(u, layer["wk"], low).reshape(split),
                         dims["theta"]),
                   _mm(u, layer["wv"], low).reshape(split), low)
    branch = _mm(o.reshape(t, -1), layer["wo"], low)
    if post_norms:
        branch = _rms(branch, layer["ln1_post_scale"], eps)
    x = x + branch
    u = _rms(x, layer["ln2_scale"], eps)
    gate = _mm(u, layer["w_gate"], low)
    branch = _mm(gate * jax.nn.sigmoid(gate) * _mm(u, layer["w_up"], low),
                 layer["w_down"], low)
    if post_norms:
        branch = _rms(branch, layer["ln2_post_scale"], eps)
    return x + branch


def _readout(h, params, labels, low):
    """``(l [T], g [T])``: the cross-entropy a token of the normed state
    ``h`` [T, d] through the head, a block of rows at a time, and the
    gate's pre-activation."""
    t = h.shape[0]
    block = min(HEAD_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} tokens not a multiple of {block}")

    @jax.checkpoint
    def one_block(hl):
        hb, lb = hl
        logp = jax.nn.log_softmax(_mm(hb, params["head"], low), axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=-1)[:, 0]

    losses = lax.map(one_block, (h.reshape(t // block, block, -1),
                                 labels.reshape(t // block, block)))
    gates = (h @ params["exit_gate_w"])[:, 0] + params["exit_gate_b"][0]
    return losses.reshape(t), gates


def exit_probabilities(gates, last_takes_rest: bool = True):
    """``p`` [L, ...] from the gates' pre-activations [L, ...], as plain
    products of ``lambda`` and ``1 - lambda``."""
    lam = jax.nn.sigmoid(gates)
    stay, p = jnp.ones_like(lam[0]), []
    for t in range(lam.shape[0]):
        last = t == lam.shape[0] - 1
        p.append(stay if last and last_takes_rest else lam[t] * stay)
        stay = stay * (1.0 - lam[t])
    return jnp.stack(p)


def _sequence(params, tokens, labels, dims, low, loops, untied, cut_passes,
              norm_carried, post_norms, uniform_exit, entropy,
              last_takes_rest, last_pass_only):
    """``(summed loss, sum of p a pass [L], sum of l a pass [L])`` of one
    sequence."""
    layers = params["layers"]
    per_pass = len(layers) // loops if untied else len(layers)
    one_layer = jax.checkpoint(
        lambda x, layer: _layer(x, layer, dims, low, post_norms))
    x = params["embed"][tokens]
    losses, gates = [], []
    for t in range(loops):
        first = t * per_pass if untied else 0
        for layer in layers[first:first + per_pass]:
            x = one_layer(x, layer)
        h = _rms(x, params["ln_f_scale"], dims["eps"])
        l_t, g_t = _readout(h, params, labels, low)
        losses.append(l_t)
        gates.append(g_t)
        if norm_carried:
            x = h
        if cut_passes:
            x = lax.stop_gradient(x)
    losses, gates = jnp.stack(losses), jnp.stack(gates)
    p = exit_probabilities(gates, last_takes_rest)
    if uniform_exit:
        p = jnp.full_like(p, 1.0 / loops)
    per_token = jnp.sum(p * losses, axis=0)
    if entropy:
        per_token = per_token + dims["beta"] * jnp.sum(
            jax.scipy.special.xlogy(p, p), axis=0)
    if last_pass_only:
        per_token = losses[-1]
    return per_token.sum(), (p.sum(1), losses.sum(1))


def loss_and_grads(params, tokens, labels, *, dims: dict, names=CHECKED,
                   paths=None, low_precision=None, loops=None,
                   untied: bool = False, cut_passes: bool = False,
                   norm_carried: bool = True, post_norms: bool = True,
                   uniform_exit: bool = False, entropy: bool = True,
                   last_takes_rest: bool = True,
                   last_pass_only: bool = False):
    """``(loss, {name: gradient for name in names}, stats)`` of the batch
    ``tokens`` [B, T]: the loss of the global batch mean, its gradient with
    respect to the leaves ``names`` (``paths``: ``{name: path}``, by
    default :func:`leaf_paths`), and ``stats``: the mean over tokens of
    ``p_t`` and of ``l_t`` a pass (``p_mean``, ``l_mean``, [L] each).
    ``dims``: ``n_heads``, ``eps``, ``theta``, ``loops``, ``beta``."""
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
    loops = loops or dims["loops"]
    paths = paths or leaf_paths(len(params["layers"]))
    n = tokens.size

    def loss_of(chosen):
        tree = params
        for name, value in chosen.items():
            tree = with_leaf(tree, paths[name], value)
        total, (p_sum, l_sum) = lax.map(
            lambda tl: _sequence(
                tree, *tl, dims, low_precision, loops, untied, cut_passes,
                norm_carried, post_norms, uniform_exit, entropy,
                last_takes_rest, last_pass_only), (tokens, labels))
        return total.sum() / n, {"p_mean": p_sum.sum(0) / n,
                                 "l_mean": l_sum.sum(0) / n}

    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.value_and_grad(loss_of, has_aux=True)(
            {name: leaf(params, paths[name]) for name in names})
    return loss, grads, stats
