"""Plain reference of the decoder the ``cca_moe_lm`` cells train: ZAYA1's
layer, compressed convolutional attention (CCA) and one expert a token
under an MLP router with a carried state, joined by scaled residual merges.

Straight ``jax.numpy`` in float32 at precision ``highest``: no kernel, no
``shard_map``, no bf16, a Python loop over the layers, one sequence at a
time, attention a block of query rows against the whole causal context,
the held experts one after another with a mask a token, the head a block
of rows at a time.  It shares no code with ``horovod_tpu/``; it reads the
program's parameter tree (``embed``, ``ln_f_scale``, ``layers[i]``) because
that tree is what a checkpoint of the system holds.

The model (``perfbench/configs/zaya1-8b.json``; arXiv:2510.04476,
arXiv:2511.17127), with ``H`` query heads on ``G`` key-value heads of ``D``
and ``g(h) = h // (H / G)``:

* ``x = E[tokens]``, no position table; ``logits = RMSNorm_f(x) E^T``
  (the head is the embedding's transpose);
* a layer is two sub-layers, each ``x <- a_r * (x + b_r) + a_o *
  (f(RMSNorm(x)) + b_o)`` with four vectors of its own (``merge1_*``,
  ``merge2_*``: ``res_scale``, ``res_bias``, ``out_scale``, ``out_bias``);
* CCA on ``u = RMSNorm(x)``: ``q~ = u W_q`` [T, H, D], ``k~ = u W_k`` [T,
  G, D]; over the packed ``c = [q~ | k~]`` a causal depthwise convolution
  of two taps, ``c1[t] = w0 * c[t-1] + w1 * c[t] + b`` (``cca_dw_w`` [2,
  (H + G) D], ``cca_dw_b``), then a causal convolution of two taps grouped
  by head, ``c2[t] = c1[t-1] C0_j + c1[t] C1_j + b'`` (``cca_gw_w`` [2, H +
  G, D, D], ``cca_gw_b``), rows before the first zero; ``(q', k') = c2``;
  the mean ``m_q[h] = (q~[h] + k~[g(h)]) / 2``, ``m_k[j]`` its mean over
  group ``j``'s query heads, ``q = q' + m_q``, ``k = k' + m_k``; the values'
  first ``G / 2`` heads ``u[t] W_v_now``, the others ``u[t-1] W_v_prev``;
  ``q <- sqrt(D) q / |q|``, ``k <- sqrt(D) exp(tau_j) k / |k|``
  (``k_temp``); rotary (rotate-half pairing, theta) over the first
  ``rotary_dims`` of every head; causal softmax at scale ``D ** -0.5``,
  query head ``h`` on key-value head ``g(h)``; ``W_o``;
* experts on ``u = RMSNorm(x)``: the router state ``r_l = u W_d + b_d +
  gamma_l * r_{l-1}`` (the last term absent in the first layer), handed to
  the next layer as it is; ``z = W_3 gelu(W_2 gelu(W_1 RMSNorm_r(r_l) +
  b_1) + b_2)`` over ``E + 1`` choices, exact ``gelu``; ``p = softmax(z)``;
  ``c = argmax(p + beta)`` (``router_bias``; ties to the lower index); ``y
  = p_c W_down,c (silu(W_gate,c u) * (W_up,c u))`` for an expert ``c < E``
  **that is held** (``layers[i]["w_gate"]`` holds experts ``held_from ..
  held_from + len``; another chip's add nothing), ``y = p_c u`` for ``c =
  E``, the skip;
* the loss: the mean next-token cross-entropy.

Memory devices that change no arithmetic: every layer, each of its two
sub-layers inside it, every block of query rows, every expert and every
block of rows of the head is under ``jax.checkpoint``; sequences go one at a time (``lax.map``); the
gradients are taken with respect to the requested leaves alone.

For the experiments that set and test the tolerances (``PERF.md``, PR 53;
``perfbench/controls_cca_moe_lm.py``), each another function (the keyword
arguments of :func:`loss_and_grads`): ``mix=False`` leaves both
convolutions out; ``mean=False`` adds no mean back; ``value_shift=False``
takes both value halves from the token itself; ``l2_norm=False`` leaves
the norm and the temperature out; ``rotary_whole`` turns the whole head;
``cut_state`` stops the gradient where a layer hands its router state on;
``carry=False`` is ``gamma = 0``; ``skip_term=False`` has the skip compute
nothing; ``skip_choice=False`` is sixteen choices and no skip;
``bias_weighs`` weighs by ``(p + beta)_c``; ``weighted=False`` leaves the
chosen probability out; ``scaled_merge=False`` is ``x + f(RMSNorm(x))``;
``low_precision`` rounds every matmul's operands to that dtype, those of
attention's two among them (the router stays in float32, as the
configuration states it); ``router_low_precision`` rounds the operands of
the router's four matmuls, and of nothing else, to that dtype.
"""

from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

QUERY_BLOCK = 256
HEAD_BLOCK = 1024

# The leaves a cell's check reads (:func:`leaf_paths`): layer 0's
# depthwise taps and its shifted value projection (the bottom of the stack,
# the mix and the shift), layer 0's router down projection (its gradient
# arrives through every later router by the carried state), the last
# layer's ``gamma`` and ``W_3`` (the last router's own leaves), its ``a_o``
# of the expert sub-layer, its held experts' ``W_down`` and its key
# temperature.
CHECKED = ("dw_taps_first", "wv_prev_first", "router_down_first",
           "gamma_last", "router_w3_last", "merge2_out_scale_last",
           "w_down_last", "k_temp_last")
# Defaults of the controls: the model.
MODEL = dict(mix=True, mean=True, value_shift=True, l2_norm=True,
             rotary_whole=False, cut_state=False, carry=True,
             skip_term=True, skip_choice=True, bias_weighs=False,
             weighted=True, scaled_merge=True, low_precision=None,
             router_low_precision=None)


def leaf_paths(n_layers: int) -> dict:
    """``{name: path in the parameter tree}`` of :data:`CHECKED`;
    ``w_down_last`` is every held expert's, stacked (one expert's alone
    follows the few rows a random router may send it)."""
    last = n_layers - 1
    return {"dw_taps_first": ("layers", 0, "cca_dw_w"),
            "wv_prev_first": ("layers", 0, "wv_prev"),
            "router_down_first": ("layers", 0, "router_down"),
            "gamma_last": ("layers", last, "router_state_scale"),
            "router_w3_last": ("layers", last, "router_w3"),
            "merge2_out_scale_last": ("layers", last, "merge2_out_scale"),
            "w_down_last": ("layers", last, "w_down"),
            "k_temp_last": ("layers", last, "k_temp")}


def trained_leaves(params) -> dict:
    """``{name: path}`` of every leaf of ``params`` that the loss moves:
    all but the selection biases, which choose and carry no gradient, and
    the first layer's ``gamma``, which multiplies no state."""
    found = {}
    for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]:
        keys = tuple(getattr(k, "key", getattr(k, "idx", None))
                     for k in path)
        if keys[-1] == "router_bias" or keys == (
                "layers", 0, "router_state_scale"):
            continue
        found[".".join(map(str, keys))] = keys
    return found


def leaf(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def with_leaf(tree, path, value):
    """``tree`` with the leaf at ``path`` replaced (copies on the way; an
    index into an array sets that slice)."""
    if not path:
        return value
    if isinstance(tree, jax.Array):
        return tree.at[path[0]].set(with_leaf(tree[path[0]], path[1:],
                                              value))
    copy = list(tree) if isinstance(tree, (list, tuple)) else dict(tree)
    copy[path[0]] = with_leaf(tree[path[0]], path[1:], value)
    return copy


def _round(x, low):
    """``x`` rounded to ``low`` (values rounded, gradients straight
    through: a float8 cotangent would underflow); ``x`` without it."""
    if low is None:
        return x
    if jnp.dtype(low) == jnp.bfloat16:
        # A convert there and back is what XLA may remove (excess
        # precision is allowed); this it keeps.
        rounded = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    else:
        rounded = x.astype(low).astype(jnp.float32)
    return x + lax.stop_gradient(rounded - x)


def _mm(a, b, low):
    return _round(a, low) @ _round(b, low)


def _rms(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _previous(a):
    """Row ``t`` holds ``a[t - 1]``, row 0 zeros."""
    return jnp.concatenate([jnp.zeros_like(a[:1]), a[:-1]], axis=0)


def _rope(x, theta):
    """x: [T, H, R] of one sequence at positions 0..T-1: all ``R`` dims
    turned, rotate-half pairing ``(i, i + R / 2)``."""
    t, _, r = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    half = r // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * jnp.cos(emb) + turned * jnp.sin(emb)


def _attention(q, k, v, low):
    """q, k, v: [T, H, D] of one sequence; causal softmax attention at
    scale ``D ** -0.5``, a block of query rows at a time."""
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


def _merge(x, y, layer, prefix, ctl):
    if not ctl["scaled_merge"]:
        return x + y
    return (layer[prefix + "_res_scale"] * (x + layer[prefix + "_res_bias"])
            + layer[prefix + "_out_scale"]
            * (y + layer[prefix + "_out_bias"]))


def cca_heads(u, layer, dims, ctl):
    """``(q [T, H, D], k [T, G, D], v [T, G, D])`` of one sequence's
    normed input ``u`` [T, d], as the module's docstring writes them."""
    low = ctl["low_precision"]
    t = u.shape[0]
    heads, groups, hd = dims["n_heads"], dims["n_kv_heads"], dims["head_dim"]
    per_group = heads // groups
    q0 = _mm(u, layer["wq"], low).reshape(t, heads, hd)
    k0 = _mm(u, layer["wk"], low).reshape(t, groups, hd)
    q, k = q0, k0
    if ctl["mix"]:
        c = jnp.concatenate([q0.reshape(t, -1), k0.reshape(t, -1)], axis=-1)
        taps = layer["cca_dw_w"]
        c1 = (taps[0] * _previous(c) + taps[1] * c
              + layer["cca_dw_b"]).reshape(t, heads + groups, hd)
        mats = _round(layer["cca_gw_w"], low)
        c1r = _round(c1, low)
        c2 = (jnp.einsum("thd,hde->the", _previous(c1r), mats[0])
              + jnp.einsum("thd,hde->the", c1r, mats[1])
              + layer["cca_gw_b"].reshape(heads + groups, hd))
        q, k = c2[:, :heads], c2[:, heads:]
    if ctl["mean"]:
        m_q = (q0 + jnp.repeat(k0, per_group, axis=1)) / 2
        q = q + m_q
        k = k + m_q.reshape(t, groups, per_group, hd).mean(axis=2)
    earlier = _previous(u) if ctl["value_shift"] else u
    v = jnp.concatenate(
        [_mm(u, layer["wv_now"], low).reshape(t, groups // 2, hd),
         _mm(earlier, layer["wv_prev"], low).reshape(t, groups // 2, hd)],
        axis=1)
    if ctl["l2_norm"]:
        q = hd ** 0.5 * q / jnp.linalg.norm(q, axis=-1, keepdims=True)
        k = (hd ** 0.5 * jnp.exp(layer["k_temp"])[None, :, None] * k
             / jnp.linalg.norm(k, axis=-1, keepdims=True))
    turned = hd if ctl["rotary_whole"] else (dims["rotary_dims"] or hd)
    q, k = (jnp.concatenate([_rope(a[..., :turned], dims["theta"]),
                             a[..., turned:]], axis=-1) for a in (q, k))
    return q, k, v


def _cca(x, layer, dims, ctl):
    low = ctl["low_precision"]
    t = x.shape[0]
    q, k, v = cca_heads(_rms(x, layer["ln1_scale"], dims["eps"]), layer,
                        dims, ctl)
    per_group = q.shape[1] // k.shape[1]
    o = _attention(q, jnp.repeat(k, per_group, axis=1),
                   jnp.repeat(v, per_group, axis=1), low)
    return _merge(x, _mm(o.reshape(t, -1), layer["wo"], low), layer,
                  "merge1", ctl)


def expert_branch(u, state, layer, dims, ctl=MODEL):
    """The expert sub-layer's branch on one sequence's normed input ``u``
    [T, d] with the previous layer's router state (or None): ``(routed [T,
    d], skipped [T, d], state' [T, w], choice [T])``; the branch's output
    is ``routed + skipped``.  ``routed`` is the part the experts held here
    give (``layer["w_gate"]`` holds ``dims["held_from"]`` and the ones
    after it), ``skipped`` the skip's term, which every chip computes
    alike."""
    low, n_experts = ctl["low_precision"], dims["n_experts"]
    z, r = _router(u, state if ctl["carry"] else None, layer, dims,
                   ctl["cut_state"], ctl["router_low_precision"])
    bias = layer["router_bias"]
    if not ctl["skip_choice"]:
        z, bias = z[:, :n_experts], bias[:n_experts]
    p = jax.nn.softmax(z, axis=-1)
    biased = p + lax.stop_gradient(bias)
    choice = jnp.argmax(biased, axis=-1)
    weight = jnp.take_along_axis(biased if ctl["bias_weighs"] else p,
                                 choice[:, None], axis=-1)[:, 0]
    if not ctl["weighted"]:
        weight = jnp.ones_like(weight)

    @jax.checkpoint
    def one_expert(args):
        j, w_gate, w_up, w_down = args
        gate = _mm(u, w_gate, low)
        out = _mm(gate * jax.nn.sigmoid(gate) * _mm(u, w_up, low), w_down,
                  low)
        mine = jnp.where(choice == dims["held_from"] + j, weight, 0.0)
        return mine[:, None] * out

    held = layer["w_gate"].shape[0]
    # One after another into one sum: no [held, T, d] stack.
    routed, _ = lax.scan(
        lambda total, args: (total + one_expert(args), None),
        jnp.zeros_like(u), (jnp.arange(held), layer["w_gate"],
                            layer["w_up"], layer["w_down"]))
    skipped = jnp.where(choice == n_experts, weight, 0.0)[:, None] * u
    if not ctl["skip_term"]:
        skipped = jnp.zeros_like(skipped)
    return routed, skipped, r, choice


def _router(u, state, layer, dims, cut_state: bool = False, low=None):
    """``(z [T, E + 1], state')``: the router's logits and the state it
    hands on, as the module's docstring writes them (``low``: its matmuls'
    operands rounded to that dtype)."""
    r = _mm(u, layer["router_down"], low) + layer["router_down_bias"]
    if state is not None:
        r = r + layer["router_state_scale"] * (
            lax.stop_gradient(state) if cut_state else state)
    h = _rms(r, layer["router_norm_scale"], dims["eps"])
    h = jax.nn.gelu(_mm(h, layer["router_w1"], low) + layer["router_b1"],
                    approximate=False)
    h = jax.nn.gelu(_mm(h, layer["router_w2"], low) + layer["router_b2"],
                    approximate=False)
    return _mm(h, layer["router_w3"], low), r


def _subsets(n_experts: int, held: int):
    """Every choice of ``held`` of ``n_experts``, a row of 0 / 1 each."""
    chosen = list(itertools.combinations(range(n_experts), held))
    subsets = np.zeros((len(chosen), n_experts), np.float32)
    subsets[np.arange(len(chosen))[:, None], np.asarray(chosen)] = 1.0
    return subsets


def place_layer(layer, x, state, owed=0.0, *, dims: dict):
    """One layer of :func:`level_placement` on one sequence's stream ``x``
    [T, d] and the router state the layer below hands it (zeros for the
    first layer, which is what an absent state adds): ``(perm [E + 1], x',
    state', load [E + 1], owed')``, the permutation of the layer's choices
    for :func:`place`, the stream and the state under it, the tokens every
    choice receives (before the permutation), and the rows by which the
    held range falls short of its target.  The target is the layer's own
    share plus ``owed``, what the layers below fell short by (a layer one
    of whose experts takes half the tokens has no subset near its share;
    the layers above it make it up, and the chip's rows over the stack
    are a uniform router's).  Float32 at precision ``highest``."""
    n_experts, first = dims["n_experts"], dims["held_from"]
    layer = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), layer)
    held = layer["w_gate"].shape[0]
    subsets = _subsets(n_experts, held)
    target = x.shape[0] * held / (n_experts + 1) + owed
    with jax.default_matmul_precision("highest"):
        x = _cca(x, layer, dims, MODEL)
        z, _ = _router(_rms(x, layer["ln2_scale"], dims["eps"]), state,
                       layer, dims)
        choice = jnp.argmax(jax.nn.softmax(z, axis=-1)
                            + layer["router_bias"], axis=-1)
        load = jnp.sum(choice[:, None] == jnp.arange(n_experts + 1)[None],
                       axis=0)
        sums = subsets @ load[:n_experts].astype(jnp.float32)
        best = jnp.argmin(jnp.abs(sums - target))
        order = jnp.argsort(1.0 - jnp.asarray(subsets)[best], stable=True)
        rest = order[held:]
        perm = jnp.concatenate([rest[:first], order[:held], rest[first:],
                                jnp.array([n_experts])])
        x, state, _, _ = _experts(x, state, place(layer, perm), dims, MODEL)
    return perm, x, state, load, target - sums[best]


def layer_loads(layer, x, state, *, dims: dict):
    """One layer's forward pass on one sequence's stream ``x`` [T, d] and
    the router state handed to it (zeros for the first layer): ``(x',
    state', rows [held], skips)``, the rows every held expert receives and
    the tokens that skip.  Float32 at precision ``highest``; what a layer
    of :func:`loss_and_grads` computes, for a caller that walks the layers
    with one compiled program."""
    layer = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), layer)
    with jax.default_matmul_precision("highest"):
        return _experts(_cca(x, layer, dims, MODEL), state, layer, dims,
                        MODEL)


def level_placement(params, tokens, *, dims: dict):
    """Which experts of every layer this chip holds, for one chip of a
    deployment that shares a layer's experts level: a permutation ``perm``
    [E + 1] of every layer's choices, to be applied by :func:`place` (the
    skip stays last; a permutation of the last matrix's columns moves no
    state).  From the model's own forward pass on ``tokens`` [T], layer by
    layer (:func:`place_layer`), each layer's input what the layers before
    it give under their placement: of the tokens' loads on the ``E``
    experts, the held range ``held_from .. held_from + held`` gets the
    subset of ``held`` experts whose loads add up nearest to ``T held / (E
    + 1)``, a uniform router's rows, plus what the layers below it fell
    short by.  No bias is moved: a router's margins
    between two choices stay what its weights give."""
    x = params["embed"][tokens].astype(jnp.float32)
    state = jnp.zeros((tokens.shape[0],
                       params["layers"][0]["router_down"].shape[1]),
                      jnp.float32)
    perms, owed = [], 0.0
    for layer in params["layers"]:
        perm, x, state, _, owed = place_layer(layer, x, state, owed,
                                              dims=dims)
        perms.append(perm)
    return perms


def place(layer, perm):
    """``layer`` with :func:`level_placement`'s ``perm`` applied."""
    return dict(layer, router_w3=layer["router_w3"][:, perm],
                router_bias=layer["router_bias"][perm])


def _experts(x, state, layer, dims, ctl):
    """``(x', state', rows a held expert [held], tokens that skip)``."""
    routed, skipped, state, choice = expert_branch(
        _rms(x, layer["ln2_scale"], dims["eps"]), state, layer, dims, ctl)
    held = layer["w_gate"].shape[0]
    rows = jnp.sum(choice[:, None] == dims["held_from"]
                   + jnp.arange(held)[None, :], axis=0)
    return (_merge(x, routed + skipped, layer, "merge2", ctl), state, rows,
            jnp.sum(choice == dims["n_experts"]))


def _sequence(params, tokens, labels, dims, ctl):
    """``(summed loss, (rows [L, held], skips [L]))`` of one sequence."""
    low, eps = ctl["low_precision"], dims["eps"]
    x = params["embed"][tokens]
    state, rows, skips = None, [], []
    @jax.checkpoint
    def one_layer(x, state, layer):
        # Both sub-layers recomputed again inside the layer's own
        # recomputation: one saved [T, d] a layer, one more inside it.
        x = jax.checkpoint(lambda x, layer: _cca(x, layer, dims, ctl))(
            x, layer)
        return jax.checkpoint(
            lambda x, state, layer: _experts(x, state, layer, dims, ctl))(
                x, state, layer)

    for layer in params["layers"]:
        x, state, held_rows, skipped = one_layer(x, state, layer)
        rows.append(held_rows)
        skips.append(skipped)
    h = _rms(x, params["ln_f_scale"], eps)
    t = h.shape[0]
    block = min(HEAD_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} tokens not a multiple of {block}")

    @jax.checkpoint
    def one_block(hl):
        hb, lb = hl
        logp = jax.nn.log_softmax(_mm(hb, params["embed"].T, low), axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=-1)[:, 0]

    losses = lax.map(one_block, (h.reshape(t // block, block, -1),
                                 labels.reshape(t // block, block)))
    return losses.sum(), (jnp.stack(rows), jnp.stack(skips))


def loss_and_grads(params, tokens, labels, *, dims: dict, names=CHECKED,
                   paths=None, **controls):
    """``(loss, {name: gradient for name in names}, stats)`` of the batch
    ``tokens`` [B, T]: the loss of the global batch mean, its gradient with
    respect to the leaves ``names`` (``paths``: ``{name: path}``, by
    default :func:`leaf_paths`), and ``stats``: ``rows`` [L, held], the
    rows every held expert of every layer receives over the batch, and
    ``skips`` [L], the tokens of every layer whose choice is the skip.
    ``dims``: ``n_heads``, ``n_kv_heads``, ``head_dim``, ``rotary_dims``,
    ``eps``, ``theta``, ``n_experts`` (the published count, the skip's
    index) and ``held_from``.  ``controls``: see the module's docstring."""
    unknown = set(controls) - set(MODEL)
    if unknown:
        raise TypeError(f"unknown controls {sorted(unknown)}")
    ctl = dict(MODEL, **controls)
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
    paths = paths or leaf_paths(len(params["layers"]))
    n = tokens.size

    def loss_of(chosen):
        tree = params
        for name, value in chosen.items():
            tree = with_leaf(tree, paths[name], value)
        total, (rows, skips) = lax.map(
            lambda tl: _sequence(tree, *tl, dims, ctl), (tokens, labels))
        return total.sum() / n, {"rows": rows.sum(0), "skips": skips.sum(0)}

    with jax.default_matmul_precision("highest"):
        (loss, stats), grads = jax.value_and_grad(loss_of, has_aux=True)(
            {name: leaf(params, paths[name]) for name in names})
    return loss, grads, stats
