"""Plain reference of the Nemotron-3 stack the ``ssm_moe_lm`` cells train.

Straight ``jax.numpy`` in float32 at precision ``highest``: no kernel, no
``shard_map``, no bf16, **the state-space recurrence token by token**
(never in chunks), **a dense loop over the held experts** (no sort, no
grouped matmul) and plain attention with the key-value heads repeated.  It
shares no code with ``horovod_tpu/``; it reads the program's parameter
tree (``embed``, ``head``, ``ln_f_scale``, ``layers[i]``, ``mtp``) because
that tree is what a checkpoint of the system holds.

Every layer is one part: ``x <- x + part(RMSNorm(x))``, ``u`` the normed
input (``perfbench/configs/nemotron-3-super-120b-a12b.json``):

* ``mamba2``: ``[z | xBC | dt] = u W_in``; ``xBC = silu(conv(xBC) + b)``
  with a causal depthwise convolution of ``K`` taps (``K`` shifted adds,
  zeros before the sequence); ``x`` [T, H, P], ``B``, ``C`` [T, G, N]
  split off it, head ``h`` reading group ``h // (H / G)``; ``delta =
  softplus(dt + dt_bias)``, ``a = exp(-delta exp(A_log))``; then for every
  token in turn, from ``S = 0``,

      S <- a_t S + delta_t x_t B_t^T
      y_t = S C_t + D x_t

  ``out = GroupRMSNorm(y * silu(z)) W_out``, the norm over ``G`` groups
  of channels with one learned scale of ``H P``;
* ``attention``: ``q, k, v = u Wq, u Wk, u Wv`` without bias, ``H`` query
  heads over ``H_kv`` key-value heads (each repeated ``H / H_kv`` times),
  **no positional term**, causal softmax at scale ``head_dim ** -0.5``,
  ``out = o Wo``;
* ``mlp`` (the latent mixture of experts): ``s = sigmoid(u W_r)`` over all
  experts; the ``k`` with the largest ``s + bias`` are chosen; weights ``w
  = scale * s[chosen] / (sum of s[chosen] + 1e-20)``; ``l = u W_in^lat``;
  expert ``e`` is ``W_down,e relu(W_up,e l)^2``; ``routed = (sum over the
  chosen experts **that the tree holds** of w_e expert_e(l)) W_out^lat``;
  ``shared = W_sd relu(W_su u)^2``; ``out = routed + shared``;
* final RMSNorm, untied head, float32 logits, mean next-token
  cross-entropy; plus ``mtp_coef`` x the multi-token-prediction module's:
  ``[RMSNorm_e(embed(x_{t+1})); RMSNorm_h(h_t)] W_eh`` (``h_t`` the last
  layer's output before the final norm) through its own layers and final
  norm and the model's head, against ``x_{t+2}`` over the ``T - 1``
  positions that have one.

Memory devices that change no arithmetic: the token scan is nested (an
outer scan over runs of :data:`SCAN_RUN` tokens under ``jax.checkpoint``);
every layer of the differentiated tail, every block of query rows and
every block of the head is under ``jax.checkpoint``; sequences go one at a
time (``lax.map``); an ``optimization_barrier`` stands between a Mamba-2
layer's projections and its token loop (PERF.md, PR 31: left free to fuse
them into the loop, the v5e's compiler returned a mixer output 3.7% off).
The gradients come from a backward pass through the last Mamba-2 layer and
everything above it only: they depend on nothing below.

For the experiments that set and test the tolerances (PERF.md, PR 33;
``tests/test_ssm_moe_lm.py``): ``low_precision`` rounds every matmul's
operands, and the recurrence's ``x``, ``B``, ``C`` and the state where it
is an operand, to that dtype; ``reset_every`` zeroes the recurrence's
state every so many tokens (what a chunked form that forgot to carry it
computes); ``shared_expert=False`` leaves the shared expert out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 256
HEAD_BLOCK = 1024
SCAN_RUN = 128


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


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


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


def _attention_part(u, layer, dims, low):
    t = u.shape[0]
    heads, kv_heads = dims["n_heads"], dims["kv_heads"]
    q = _round(_mm(u, layer["wq"], low), low).reshape(t, heads, -1)
    k = _round(_mm(u, layer["wk"], low), low).reshape(t, kv_heads, -1)
    v = _round(_mm(u, layer["wv"], low), low).reshape(t, kv_heads, -1)
    # Query head h reads key-value head h // (heads / kv_heads).
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    return _mm(_attention(q, k, v).reshape(t, -1), layer["wo"], low)


def _conv(x, w, bias):
    """Causal depthwise convolution of ``x`` [T, C] with ``w`` [K, C]:
    tap ``j`` meets the input ``K - 1 - j`` tokens back."""
    taps = w.shape[0]
    out = jnp.zeros_like(x) + bias
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros((back, x.shape[1]), x.dtype), x[:x.shape[0] - back]])
        out = out + shifted * w[j]
    return out


def _state_space(x, b_in, c_in, delta, decay, low, reset_every):
    """The recurrence, one token at a time.  x: [T, H, P]; b_in, c_in:
    [T, H, N] (each head's group's); delta, decay: [T, H] -> y [T, H, P].
    """
    t, h, p = x.shape
    n = b_in.shape[-1]
    run = min(SCAN_RUN, t)
    if t % run:
        raise ValueError(f"sequence length {t} not a multiple of {run}")

    def token(state, inputs):
        x_t, b_t, c_t, delta_t, decay_t, keep_t = inputs
        state = (keep_t * decay_t)[:, None, None] * state
        state = state + (delta_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return state, jnp.einsum("hpn,hn->hp", _round(state, low), c_t)

    @jax.checkpoint
    def tokens(state, xs):
        return lax.scan(token, state, xs)

    at = jnp.arange(t)
    keep = jnp.ones((t,)) if reset_every is None else (
        (at % reset_every != 0).astype(jnp.float32))
    keep = jnp.broadcast_to(keep[:, None], (t, h))
    xs = jax.tree_util.tree_map(
        lambda v: v.reshape((t // run, run) + v.shape[1:]),
        (_round(x, low), _round(b_in, low), _round(c_in, low), delta, decay,
         keep))
    _, y = lax.scan(tokens, jnp.zeros((h, p, n), jnp.float32), xs)
    return y.reshape(t, h, p)


def _decay(u, layer, dims, low):
    """``(delta, a)``, each [T, H]."""
    inner = dims["ssm_heads"] * dims["ssm_head_dim"]
    conv = inner + 2 * dims["ssm_groups"] * dims["ssm_state"]
    delta = jax.nn.softplus(_mm(u, layer["ssm_w_in"][:, inner + conv:], low)
                            + layer["ssm_dt_bias"])
    return delta, jnp.exp(-delta * jnp.exp(layer["ssm_a_log"]))


def _mamba2_part(u, layer, dims, low, reset_every):
    t = u.shape[0]
    h, p, n, g = (dims["ssm_heads"], dims["ssm_head_dim"],
                  dims["ssm_state"], dims["ssm_groups"])
    inner = h * p
    conv = inner + 2 * g * n
    w_in = layer["ssm_w_in"]
    z = _mm(u, w_in[:, :inner], low)
    xbc = _silu(_conv(_mm(u, w_in[:, inner:inner + conv], low),
                      layer["ssm_conv"], layer["ssm_conv_bias"]))
    delta, decay = _decay(u, layer, dims, low)
    x = xbc[:, :inner].reshape(t, h, p)
    # Each head's group's B and C.
    b_in = jnp.repeat(xbc[:, inner:inner + g * n].reshape(t, g, n), h // g,
                      axis=1)
    c_in = jnp.repeat(xbc[:, inner + g * n:].reshape(t, g, n), h // g,
                      axis=1)
    # No arithmetic: the loop's inputs exist as arrays before it reads
    # them (the module's docstring).
    x, b_in, c_in, delta, decay = lax.optimization_barrier(
        (x, b_in, c_in, delta, decay))
    y = _state_space(x, b_in, c_in, delta, decay, low, reset_every)
    y = y + layer["ssm_d"][:, None] * x
    y = (y.reshape(t, inner) * _silu(z)).reshape(t, g, inner // g)
    y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + dims["eps"])
    return _mm(y.reshape(t, inner) * layer["ssm_norm_scale"],
               layer["ssm_w_out"], low)


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
    weights = _expert_weights(u, layer, dims)
    latent = _mm(u, layer["w_latent_in"], low)
    routed = jnp.zeros_like(latent)
    # The experts the tree holds, one after another, every token through
    # each: a token that did not choose one has weight zero for it.
    for j in range(layer["w_up"].shape[0]):
        out = _mm(_relu2(_mm(latent, layer["w_up"][j], low)),
                  layer["w_down"][j], low)
        routed = routed + weights[:, dims["held_from"] + j, None] * out
    y = _mm(routed, layer["w_latent_out"], low)
    if shared_expert:
        y = y + _mm(_relu2(_mm(u, layer["w_shared_up"], low)),
                    layer["w_shared_down"], low)
    return y


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


# The leaves whose gradients the tail's backward pass can return, by where
# they sit in the parameter tree: "pivot" is the last Mamba-2 layer, "last"
# the last layer (an expert layer above it).
LEAVES = {
    "ln_f_scale": ("ln_f_scale",),
    "mtp_w_eh": ("mtp", "w_eh"),
    "ssm_w_out_last": ("layers", "pivot", "ssm_w_out"),
    "ssm_a_log_last": ("layers", "pivot", "ssm_a_log"),
    "ssm_dt_bias_last": ("layers", "pivot", "ssm_dt_bias"),
    "w_shared_down_last": ("layers", "last", "w_shared_down"),
    "w_latent_out_last": ("layers", "last", "w_latent_out"),
    "w_latent_in_last": ("layers", "last", "w_latent_in"),
    "w_down_last": ("layers", "last", "w_down"),
}
# What the cell's check compares (the configuration's ``check`` says why
# these): leaves whose reading is set by the arithmetic's precision.
CHECKED = ("ln_f_scale", "mtp_w_eh", "ssm_w_out_last", "ssm_a_log_last",
           "w_shared_down_last")


def leaf_paths(layer_types) -> dict:
    """``{name: path in the parameter tree}`` of :data:`LEAVES` for a
    model of these layer types."""
    mamba = [i for i, kind in enumerate(layer_types) if kind == "mamba2"]
    last = len(layer_types) - 1
    if not mamba or layer_types[last] != "mlp":
        raise ValueError("the checked leaves are the last layer's experts "
                         "and a Mamba-2 layer below it")
    at = {"pivot": mamba[-1], "last": last}
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


def loss_and_tail_grads(params, tokens, labels, *, dims: dict,
                        layer_types, mtp_layer_types, mtp_coef: float,
                        low_precision=None, reset_every=None,
                        shared_expert: bool = True, names=CHECKED):
    """``(loss, {name: gradient for name in names}, stats)`` of the
    batch ``tokens`` [B, T]: the loss (both terms) from a full forward
    pass; the gradients of the ``names`` among :data:`LEAVES` (the final
    norm's scale, the prediction module's ``w_eh``, leaves of the last
    Mamba-2 layer, ``ssm_a_log`` and ``ssm_dt_bias`` through the decay,
    and of the last expert layer) from a backward pass down to that
    Mamba-2 layer; ``stats``: ``"decay"`` [Mamba-2 layers, 3], the
    1st, 50th and 99th percentile of ``a_t`` over tokens and heads, and
    ``"rows"`` [expert layers (the module's last), held], the
    assignments each held expert receives.

    ``dims``: ``n_heads``, ``kv_heads``, ``ssm_heads``, ``ssm_head_dim``,
    ``ssm_state``, ``ssm_groups``, ``eps``, ``top_k``, ``routed_scale``,
    ``held_from``."""
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
    layers = params["layers"]
    low, eps = low_precision, dims["eps"]
    paths = leaf_paths(layer_types)
    pivot = paths["ssm_w_out_last"][1]

    def part(x, layer, kind):
        if kind == "mamba2":
            return _mamba2_part(_rmsnorm(x, layer["ln1_scale"], eps), layer,
                                dims, low, reset_every)
        if kind == "attention":
            return _attention_part(_rmsnorm(x, layer["ln1_scale"], eps),
                                   layer, dims, low)
        if kind == "mlp":
            return _moe_part(_rmsnorm(x, layer["ln2_scale"], eps), layer,
                             dims, low, shared_expert)
        raise ValueError(f"layer type {kind!r}")

    def block(x, layer, kind):
        return x + part(x, layer, kind)

    def decay_summary(x, layer):
        _, decay = _decay(_rmsnorm(x, layer["ln1_scale"], eps), layer, dims,
                          low)
        return jnp.percentile(decay, jnp.asarray([1.0, 50.0, 99.0]))

    def rows(x, layer):
        weights = _expert_weights(_rmsnorm(x, layer["ln2_scale"], eps),
                                  layer, dims)
        held = layer["w_up"].shape[0]
        here = lax.dynamic_slice_in_dim(weights, dims["held_from"], held,
                                        axis=1)
        return jnp.sum(here > 0, axis=0)

    def trunk(tok):
        """One sequence up to the last Mamba-2 layer, the decay of each
        Mamba-2 layer and the rows of each expert layer on the way."""
        x = params["embed"][tok]
        decays, counts = [], []
        for i in range(pivot):
            if layer_types[i] == "mamba2":
                decays.append(decay_summary(x, layers[i]))
            if layer_types[i] == "mlp":
                counts.append(rows(x, layers[i]))
            x = block(x, layers[i], layer_types[i])
        decays.append(decay_summary(x, layers[pivot]))
        return x, jnp.stack(decays), counts

    def tail(checked, x_mid, lab):
        swapped = params
        for name, value in checked.items():
            swapped = _with_leaf(swapped, paths[name], value)
        layers, mtp = swapped["layers"], swapped["mtp"]

        def one_sequence(xl):
            x, lb = xl
            counts = []
            for i in range(pivot, len(layers)):
                layer = layers[i]
                if layer_types[i] == "mlp":
                    counts.append(lax.stop_gradient(rows(x, layer)))
                x = jax.checkpoint(block, static_argnums=2)(
                    x, layer, layer_types[i])
            t = x.shape[0]
            main = _nll_rows(x, swapped["ln_f_scale"], params["head"], lb,
                             eps, low).sum() / t
            # The prediction module: x_{t+1}'s embedding beside h_t.
            h = _mm(jnp.concatenate([
                _rmsnorm(params["embed"][lb], mtp["embed_norm_scale"], eps),
                _rmsnorm(x, mtp["hidden_norm_scale"], eps)], axis=-1),
                mtp["w_eh"], low)
            for layer, kind in zip(mtp["layers"], mtp_layer_types):
                if kind == "mlp":
                    counts.append(lax.stop_gradient(rows(h, layer)))
                h = jax.checkpoint(block, static_argnums=2)(h, layer, kind)
            second = jnp.concatenate([lb[1:], lb[:1]])
            ahead = _nll_rows(h, mtp["ln_f_scale"], params["head"], second,
                              eps, low)[:-1].sum() / (t - 1)
            return main + mtp_coef * ahead, counts

        losses, counts = lax.map(one_sequence, (x_mid, lab))
        return losses.mean(), counts

    checked = {name: leaf(params, paths[name]) for name in names}
    with jax.default_matmul_precision("highest"):
        x_mid, decays, below = lax.map(trunk, tokens)
        (loss, above), grads = jax.value_and_grad(tail, has_aux=True)(
            checked, x_mid, labels)
    counts = jnp.stack([c.sum(0) for c in list(below) + list(above)])
    return loss, grads, {"decay": decays.mean(0), "rows": counts}
