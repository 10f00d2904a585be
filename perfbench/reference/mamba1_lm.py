"""Plain reference of the Jamba stack the ``mamba1_lm`` cells train.

Straight ``jax.numpy`` in float32 at precision ``highest``: no kernel, no
``shard_map``, no bf16, **the selective scan token by token** and plain
attention with the one key-value head repeated, the scores a block of
query rows at a time.  It shares no code with ``horovod_tpu/``; it reads
the program's parameter tree (``embed``, ``ln_f_scale``, ``layers[i]``)
because that tree is what a checkpoint of the system holds.

Every layer is a sequence mixer and the dense SwiGLU MLP: ``x <- x +
mixer(RMSNorm(x))``, ``x <- x + W_down (silu(W_gate h) * (W_up h))`` with
``h = RMSNorm(x)`` (``perfbench/configs/ai21-jamba2-3b.json``); ``u`` the
normed input:

* ``mamba`` (Mamba-1, arXiv:2312.00752, with Jamba's three inner norms):
  ``[xs | z] = u W_in``; ``xs = silu(conv(xs) + b)`` with a causal
  depthwise convolution of ``K`` taps (``K`` shifted adds, zeros before
  the sequence); ``[r | B | Cm] = xs W_x``, each RMS-normed with its own
  scale; ``delta = softplus(r W_dt + b_dt)`` [T, C]; ``A = -exp(A_log)``
  [C, N]; then for every token in turn, from ``h = 0``,

      h <- exp(delta_t[:, None] A) * h + (delta_t xs_t)[:, None] B_t[None]
      y_t = h Cm_t + D xs_t

  ``out = (y * silu(z)) W_out`` (no norm between);
* ``full_attention``: ``q, k, v = u Wq, u Wk, u Wv`` without bias, ``H``
  query heads over ``H_kv`` key-value heads (each repeated ``H / H_kv``
  times: Jamba2-3B has one), **no positional term**, causal softmax at
  scale ``head_dim ** -0.5``, ``out = o Wo``;
* final RMSNorm, the tied head (``logits = h E^T``), float32 logits, mean
  next-token cross-entropy.

Departures from the published model: none in the arithmetic above; the
model itself computes in bfloat16 with a fused scan kernel, this file in
float32 throughout.

Memory devices that change no arithmetic: the token scan is nested (an
outer scan over runs of :data:`SCAN_RUN` tokens under ``jax.checkpoint``);
every part of the differentiated tail, inside a Mamba part what stands
before its token loop and what stands after it, every block of query rows
and every block of rows of an MLP and of the head is under
``jax.checkpoint``; sequences go one at a time (``lax.map``); an
``optimization_barrier`` stands between a Mamba layer's projections and
its token loop (as ``ssm_moe_lm.py``'s).  The gradients come from a
backward pass through the lowest layer that holds a requested leaf and
everything above it only: they depend on nothing below.  At the cell's
sizes that pass is seven layers deep (``wk_attn``) and compiles to 5.4 GiB
of temporaries beside the 8.2 GiB of training state it shares the chip
with (PERF.md, PR 43).

For the experiments that set and test the tolerances (PERF.md, PR 43;
``tests/test_mamba1_lm.py``): ``low_precision`` rounds every matmul's
operands, and the scan's ``xs``, ``B``, ``Cm`` and the state where it is
read, to that dtype; ``reset_every`` zeroes the scan's state every so many
tokens (what a tiled form that forgot to carry it computes);
``one_decay`` gives a channel the mean of its ``A`` over the state index
(Mamba-2's form under Mamba-1's name); ``inner_norms=False`` leaves the
dt/B/C norms out; ``skip=False`` leaves ``D xs`` out;
``independent_kv=True`` gives every query head a key-value head of its own
(the one head's features rolled by the query head's index).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 256
HEAD_BLOCK = 1024
MLP_BLOCK = 2048
SCAN_RUN = 128

MAMBA, ATTENTION = "mamba", "full_attention"


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


def _attention_part(u, layer, dims, low, independent_kv):
    t = u.shape[0]
    heads, kv_heads = dims["n_heads"], dims["kv_heads"]
    q = _round(_mm(u, layer["wq"], low), low).reshape(t, heads, -1)
    k = _round(_mm(u, layer["wk"], low), low).reshape(t, kv_heads, -1)
    v = _round(_mm(u, layer["wv"], low), low).reshape(t, kv_heads, -1)
    # Query head h reads key-value head h // (heads / kv_heads).
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    if independent_kv:
        # The control: every query head a key-value head of its own.
        roll = lambda x: jnp.stack(
            [jnp.roll(x[:, h], h, axis=-1) for h in range(heads)], axis=1)
        k, v = roll(k), roll(v)
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


def _selective_scan(xs, b_in, c_in, delta, a, low, reset_every):
    """The recurrence, one token at a time.  xs, delta: [T, C]; b_in,
    c_in: [T, N]; a: [C, N] -> y [T, C] (without the skip)."""
    t, c = xs.shape
    n = a.shape[1]
    run = min(SCAN_RUN, t)
    if t % run:
        raise ValueError(f"sequence length {t} not a multiple of {run}")

    def token(state, inputs):
        x_t, b_t, c_t, delta_t, keep_t = inputs
        state = keep_t * jnp.exp(delta_t[:, None] * a) * state
        state = state + (delta_t * x_t)[:, None] * b_t[None, :]
        return state, _round(state, low) @ c_t

    @jax.checkpoint
    def tokens(state, rows):
        return lax.scan(token, state, rows)

    at = jnp.arange(t)
    keep = jnp.ones((t,)) if reset_every is None else (
        (at % reset_every != 0).astype(jnp.float32))
    rows = jax.tree_util.tree_map(
        lambda v: v.reshape((t // run, run) + v.shape[1:]),
        (_round(xs, low), _round(b_in, low), _round(c_in, low), delta, keep))
    _, y = lax.scan(tokens, jnp.zeros((c, n), jnp.float32), rows)
    return y.reshape(t, c)


def _scan_inputs(u, layer, dims, low, inner_norms, one_decay):
    """What a Mamba layer's token loop reads, from the normed ``u``:
    ``(xs [T, C], z [T, C], delta [T, C], A [C, N], B [T, N], Cm [T,
    N])``."""
    n, r, eps = dims["state"], dims["dt_rank"], dims["eps"]
    c = layer["mamba_d"].shape[0]
    w_in = layer["mamba_w_in"]
    z = _mm(u, w_in[:, c:], low)
    xs = _silu(_conv(_mm(u, w_in[:, :c], low), layer["mamba_conv"],
                     layer["mamba_conv_bias"]))
    rbc = _mm(xs, layer["mamba_w_x"], low)
    low_rank, b_in, c_in = rbc[:, :r], rbc[:, r:r + n], rbc[:, r + n:]
    if inner_norms:
        low_rank = _rmsnorm(low_rank, layer["mamba_dt_norm_scale"], eps)
        b_in = _rmsnorm(b_in, layer["mamba_b_norm_scale"], eps)
        c_in = _rmsnorm(c_in, layer["mamba_c_norm_scale"], eps)
    delta = jax.nn.softplus(_mm(low_rank, layer["mamba_w_dt"], low)
                            + layer["mamba_dt_bias"])
    a = -jnp.exp(layer["mamba_a_log"])
    if one_decay:
        a = jnp.broadcast_to(a.mean(axis=1, keepdims=True), a.shape)
    return xs, z, delta, a, b_in, c_in


def _mamba_part(u, layer, dims, low, reset_every, inner_norms, one_decay,
                skip):
    xs, z, delta, a, b_in, c_in = jax.checkpoint(
        lambda u, layer: _scan_inputs(u, layer, dims, low, inner_norms,
                                      one_decay))(u, layer)
    # No arithmetic: the loop's inputs exist as arrays before it reads
    # them (the module's docstring).
    xs, b_in, c_in, delta = lax.optimization_barrier((xs, b_in, c_in, delta))
    y = _selective_scan(xs, b_in, c_in, delta, a, low, reset_every)

    @jax.checkpoint
    def gated(y, xs, z, layer):
        if skip:
            y = y + layer["mamba_d"] * xs
        return _mm(y * _silu(z), layer["mamba_w_out"], low)

    return gated(y, xs, z, layer)


def _mlp_part(h, layer, low):
    """SwiGLU of ``h`` [T, d], a block of rows at a time."""
    t = h.shape[0]
    block = min(MLP_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} tokens not a multiple of {block}")

    @jax.checkpoint
    def one_block(hb):
        return _mm(_silu(_mm(hb, layer["w_gate"], low))
                   * _mm(hb, layer["w_up"], low), layer["w_down"], low)

    return lax.map(one_block, h.reshape(t // block, block, -1)).reshape(
        h.shape)


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


_MAMBA_LEAVES = ("w_in", "conv", "conv_bias", "w_x", "dt_norm_scale",
                 "b_norm_scale", "c_norm_scale", "w_dt", "dt_bias", "a_log",
                 "d", "w_out")
# The leaves whose gradients the tail's backward pass can return, by where
# they sit in the parameter tree: "pivot" is the last Mamba layer, "last"
# the last layer, "attn" the last attention layer.
LEAVES = {
    "ln_f_scale": ("ln_f_scale",),
    "w_down_last": ("layers", "last", "w_down"),
    "w_gate_last": ("layers", "last", "w_gate"),
    **{f"mamba_{name}_last": ("layers", "pivot", f"mamba_{name}")
       for name in _MAMBA_LEAVES},
    **{f"{name}_attn": ("layers", "attn", name)
       for name in ("wq", "wk", "wv", "wo")},
}
# What the cell's check compares (the configuration's ``check`` says why
# these).  Of the last Mamba layer: ``W_out`` above the scan, the scan's
# own ``A_log`` and ``D`` (the backward kernel's two sums), and dt_proj's
# ``W_dt`` below it, which only the kernel's ``d delta`` reaches.  Of the
# attention layer: ``W_k``, which only the sum of dK over the query heads
# that share the one key-value head reaches; the backward pass goes down
# to that layer.
CHECKED = ("ln_f_scale", "w_down_last", "mamba_w_out_last",
           "mamba_a_log_last", "mamba_d_last", "mamba_w_dt_last", "wk_attn")


def leaf_paths(layer_types) -> dict:
    """``{name: path in the parameter tree}`` of those of :data:`LEAVES`
    that a model of these layer types holds."""
    at = {"last": len(layer_types) - 1}
    for key, kind in (("pivot", MAMBA), ("attn", ATTENTION)):
        where = [i for i, k in enumerate(layer_types) if k == kind]
        if where:
            at[key] = where[-1]
    return {name: tuple(at.get(key, key) for key in path)
            for name, path in LEAVES.items()
            if len(path) == 1 or path[1] in at}


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


def loss_and_tail_grads(params, tokens, labels, *, dims: dict, layer_types,
                        low_precision=None, reset_every=None,
                        one_decay: bool = False, inner_norms: bool = True,
                        skip: bool = True, independent_kv: bool = False,
                        names=CHECKED, stats: bool = False):
    """``(loss, {name: gradient for name in names}, stats)`` of the batch
    ``tokens`` [B, T]: the loss from a full forward pass; the gradients
    of the ``names`` among :data:`LEAVES` from a backward pass down to
    the lowest layer that holds one of them.  The third is empty unless
    ``stats`` asks for it (every Mamba layer's projections a second time
    and a sort: the tests' business, not a timed run's): ``"decay"``
    [Mamba layers, 3], the 1st, 50th and 99th percentile of ``exp(delta
    A)`` over tokens, channels and state indices, and ``"delta"`` [Mamba
    layers, 3], the same of ``delta``.

    ``dims``: ``n_heads``, ``kv_heads``, ``state``, ``dt_rank``,
    ``eps``."""
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
    layers = params["layers"]
    low, eps = low_precision, dims["eps"]
    paths = leaf_paths(layer_types)
    # The lowest layer the backward pass has to reach.
    pivot = min([path[1] for name in names
                 if len(path := paths[name]) > 1] + [len(layers)])
    at = jnp.asarray([1.0, 50.0, 99.0])

    def mixer(x, layer, kind):
        u = _rmsnorm(x, layer["ln1_scale"], eps)
        if kind == MAMBA:
            return x + _mamba_part(u, layer, dims, low, reset_every,
                                   inner_norms, one_decay, skip)
        if kind == ATTENTION:
            return x + _attention_part(u, layer, dims, low, independent_kv)
        raise ValueError(f"layer type {kind!r}")

    def mlp(x, layer):
        return x + _mlp_part(_rmsnorm(x, layer["ln2_scale"], eps), layer,
                             low)

    def summary(x, layer):
        _, _, delta, a, _, _ = _scan_inputs(
            _rmsnorm(x, layer["ln1_scale"], eps), layer, dims, low,
            inner_norms, one_decay)
        # A sample of the tokens: [T, C, N] whole does not fit.
        some = delta[::max(1, delta.shape[0] // 64)]
        return (jnp.percentile(jnp.exp(some[:, :, None] * a), at),
                jnp.percentile(delta, at))

    def trunk(tok):
        """One sequence up to layer ``pivot``, with each Mamba layer's
        decay and step on the way (the tail's too: its input is here)."""
        x = params["embed"][tok]
        seen = []
        for i in range(pivot):
            if stats and layer_types[i] == MAMBA:
                seen.append(summary(x, layers[i]))
            x = mlp(mixer(x, layers[i], layer_types[i]), layers[i])
        return x, seen

    def tail(checked, x_mid, lab):
        swapped = params
        for name, value in checked.items():
            swapped = with_leaf(swapped, paths[name], value)

        def one_sequence(xl):
            x, lb = xl
            seen = []
            for i in range(pivot, len(layers)):
                layer = swapped["layers"][i]
                if stats and layer_types[i] == MAMBA:
                    seen.append(lax.stop_gradient(summary(x, layer)))
                x = jax.checkpoint(mixer, static_argnums=2)(
                    x, layer, layer_types[i])
                x = jax.checkpoint(mlp)(x, layer)
            nll = _nll_rows(x, swapped["ln_f_scale"], params["embed"].T, lb,
                            eps, low)
            return nll.sum() / x.shape[0], seen

        losses, seen = lax.map(one_sequence, (x_mid, lab))
        return losses.mean(), seen

    checked = {name: leaf(params, paths[name]) for name in names}
    with jax.default_matmul_precision("highest"):
        x_mid, below = lax.map(trunk, tokens)
        (loss, above), grads = jax.value_and_grad(tail, has_aux=True)(
            checked, x_mid, labels)
    seen = list(below) + list(above)
    return loss, grads, {
        "decay": jnp.stack([s[0].mean(0) for s in seen]),
        "delta": jnp.stack([s[1].mean(0) for s in seen])} if stats else {}
