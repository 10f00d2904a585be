"""Plain reference of the Olmo-Hybrid block the ``hybrid_lm`` cells train.

Straight ``jax.numpy`` in float32 at precision ``highest``: no kernel, no
``shard_map``, no bf16, and **the recurrence token by token**, never in
blocks.  It shares no code with ``horovod_tpu/``; it reads the program's
parameter tree (``embed``, ``head``, ``ln_f_scale``, ``layers[i]`` with
``ln1_scale``, ``ln2_scale``, ``w_gate``, ``w_up``, ``w_down`` and either
``wq``, ``wk``, ``wv``, ``wo``, ``q_norm_scale``, ``k_norm_scale`` or the
eleven ``lin_*`` leaves) because that tree is what a checkpoint of the
system holds.

The blocks, pre-norm (``perfbench/configs/olmo-hybrid-7b.json``,
``assumed``), ``h = RMSNorm(x)``:

* ``full_attention``: ``q, k, v = h Wq, h Wk, h Wv`` without bias; RMSNorm
  of the whole q and the whole k, each with its own scale; heads of
  ``d / n_heads``; **no rotary embedding**; causal softmax attention at
  scale ``head_dim ** -0.5``; ``x = x + o Wo``;
* ``linear_attention`` (Gated DeltaNet, arXiv:2412.06464, as HF's
  ``linear_attention`` layer states it): ``[q; k; v] = silu(conv([h Wq; h
  Wk; h Wv]))`` with a causal depthwise convolution of ``K`` taps (here
  ``K`` shifted adds, zeros before the sequence); per head ``q = l2norm(q)
  * d_k ** -0.5``, ``k = l2norm(k)``; ``beta = 2 sigmoid(h Wb)`` (the 2
  only with ``allow_neg_eigval``); ``g = -exp(A_log) softplus(h Wa +
  dt_bias)``, ``alpha = exp(g)``; then for every token in turn

      S <- alpha_t S
      S <- S + beta_t k_t (v_t - S^T k_t)^T
      o_t = S^T q_t

  from ``S = 0``; ``x = x + (RMSNorm_{d_v}(o_t) * silu(h Wz)) Wo`` with the
  norm per head and one learned scale of ``d_v``;
* both: ``x = x + W_down (silu(W_gate h') * (W_up h'))``, ``h' =
  RMSNorm(x)``; final RMSNorm, untied head, float32 logits, mean next-token
  cross-entropy.

Memory devices that change no arithmetic (the per-token states of one
layer at 16384 tokens are 36 GB if kept): the token scan is nested, an
outer scan over runs of :data:`SCAN_RUN` tokens under ``jax.checkpoint``;
every layer of the differentiated tail, every block of query rows and
every block of the head is under ``jax.checkpoint``; sequences go one at a
time (``lax.map``); an ``optimization_barrier`` stands between a linear
layer's projections and its token loop (see :func:`_linear_mixer`).  The
gradients come from a backward pass through the
last linear layer and everything above it only: they depend on nothing
below.

``low_precision`` is for the experiments that set and test the tolerances
(PERF.md, PR 31; ``tests/test_hybrid_lm.py``): what the same reference
reads when every matmul's operands, and the recurrence's q, k, v and the
state where it is an operand, are rounded to that dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 256
HEAD_BLOCK = 1024
SCAN_RUN = 128
L2_EPS = 1e-6


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


def _full_mixer(h, layer, n_heads, eps, low):
    t = h.shape[0]
    q = _rmsnorm(_mm(h, layer["wq"], low), layer["q_norm_scale"], eps)
    k = _rmsnorm(_mm(h, layer["wk"], low), layer["k_norm_scale"], eps)
    v = _mm(h, layer["wv"], low)
    split = (t, n_heads, -1)
    o = _attention(_round(q, low).reshape(split),
                   _round(k, low).reshape(split),
                   _round(v, low).reshape(split))
    return _mm(o.reshape(t, -1), layer["wo"], low)


def _conv(x, w):
    """Causal depthwise convolution of ``x`` [T, C] with ``w`` [K, C]:
    tap ``j`` meets the input ``K - 1 - j`` tokens back."""
    taps = w.shape[0]
    out = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros((back, x.shape[1]), x.dtype), x[:x.shape[0] - back]])
        out = out + shifted * w[j]
    return out


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _delta_rule(q, k, v, alpha, beta, low):
    """The recurrence, one token at a time.  q, k: [T, H, d_k]; v: [T, H,
    d_v]; alpha, beta: [T, H] -> o [T, H, d_v]."""
    t, h, dk = q.shape
    dv = v.shape[-1]
    run = min(SCAN_RUN, t)
    if t % run:
        raise ValueError(f"sequence length {t} not a multiple of {run}")

    def token(state, x):
        q_t, k_t, v_t, alpha_t, beta_t = x
        state = alpha_t[:, None, None] * state
        seen = jnp.einsum("hkv,hk->hv", _round(state, low), k_t)
        state = state + beta_t[:, None, None] * (
            k_t[:, :, None] * (v_t - seen)[:, None, :])
        return state, jnp.einsum("hkv,hk->hv", _round(state, low), q_t)

    @jax.checkpoint
    def tokens(state, xs):
        return lax.scan(token, state, xs)

    xs = jax.tree_util.tree_map(
        lambda x: x.reshape((t // run, run) + x.shape[1:]),
        (_round(q, low), _round(k, low), _round(v, low), alpha, beta))
    _, o = lax.scan(tokens, jnp.zeros((h, dk, dv), jnp.float32), xs)
    return o.reshape(t, h, dv)


def _gates(h, layer, neg_eigval, low):
    """``(alpha, beta)``, each [T, H]."""
    beta = jax.nn.sigmoid(_mm(h, layer["lin_wb"], low))
    if neg_eigval:
        beta = 2.0 * beta
    g = -jnp.exp(layer["lin_a_log"]) * jax.nn.softplus(
        _mm(h, layer["lin_wa"], low) + layer["lin_dt_bias"])
    return jnp.exp(g), beta


def _linear_mixer(h, layer, heads, key_dim, eps, neg_eigval, low):
    t = h.shape[0]
    width = heads * key_dim
    conv = layer["lin_conv"]
    q = _silu(_conv(_mm(h, layer["lin_wq"], low), conv[:, :width]))
    k = _silu(_conv(_mm(h, layer["lin_wk"], low),
                    conv[:, width:2 * width]))
    v = _silu(_conv(_mm(h, layer["lin_wv"], low), conv[:, 2 * width:]))
    q = _l2norm(q.reshape(t, heads, key_dim)) * key_dim ** -0.5
    k = _l2norm(k.reshape(t, heads, key_dim))
    alpha, beta = _gates(h, layer, neg_eigval, low)
    # No arithmetic: q, k, v and the gates exist as arrays before the token
    # loop reads them.  Left free to fuse their producers into the loop,
    # the v5e's compiler returned, from 8192 tokens up, a mixer output 3.7%
    # off what the same three stages give when jitted one by one (and off
    # the program's chunked form and a float64 run; PERF.md, PR 31).
    q, k, v, alpha, beta = lax.optimization_barrier(
        (q, k, v.reshape(t, heads, -1), alpha, beta))
    o = _delta_rule(q, k, v, alpha, beta, low)
    o = _rmsnorm(o, layer["lin_norm_scale"], eps).reshape(t, -1)
    return _mm(o * _silu(_mm(h, layer["lin_wz"], low)), layer["lin_wo"], low)


def _mlp(x, layer, eps, low):
    h = _rmsnorm(x, layer["ln2_scale"], eps)
    act = _silu(_mm(h, layer["w_gate"], low)) * _mm(h, layer["w_up"], low)
    return x + _mm(act, layer["w_down"], low)


def _nll_sum(x, ln_f_scale, head, labels, eps, low):
    """Summed next-token negative log-likelihood of ``x`` [T, d]."""
    n = x.shape[0]
    block = min(HEAD_BLOCK, n)
    if n % block:
        raise ValueError(f"{n} tokens not a multiple of {block}")

    @jax.checkpoint
    def one_block(xl):
        xb, lb = xl
        logp = jax.nn.log_softmax(
            _mm(_rmsnorm(xb, ln_f_scale, eps), head, low), axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=-1).sum()

    return lax.map(one_block, (x.reshape(n // block, block, -1),
                               labels.reshape(n // block, block))).sum()


def loss_and_tail_grads(params, tokens, labels, *, n_heads: int,
                        layer_types, linear_heads: int, key_dim: int,
                        eps: float, neg_eigval: bool, low_precision=None):
    """``(loss, {"ln_f_scale", "w_down_last", "lin_wo_last",
    "lin_wa_last"}, gates)`` of the batch ``tokens`` [B, T]: the mean
    next-token cross-entropy from a full forward pass; the gradients of
    the final norm's scale, the last layer's ``w_down`` and the last
    linear layer's ``lin_wo`` and ``lin_wa`` (through the decay) from a
    backward pass down to that layer; and ``gates`` [linear layers, 6]:
    per linear layer the least, the 1st, 50th and 99th percentile and
    the greatest ``alpha`` and the greatest ``beta`` over tokens and
    heads."""
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
    layers = params["layers"]
    low = low_precision
    linear = [i for i, kind in enumerate(layer_types)
              if kind == "linear_attention"]
    pivot = linear[-1]

    def mixer(x, layer, kind):
        h = _rmsnorm(x, layer["ln1_scale"], eps)
        if kind == "linear_attention":
            return _linear_mixer(h, layer, linear_heads, key_dim, eps,
                                 neg_eigval, low)
        return _full_mixer(h, layer, n_heads, eps, low)

    def block(x, layer, kind):
        return _mlp(x + mixer(x, layer, kind), layer, eps, low)

    def gate_summary(x, layer):
        alpha, beta = _gates(_rmsnorm(x, layer["ln1_scale"], eps), layer,
                             neg_eigval, low)
        return jnp.concatenate([
            jnp.percentile(alpha, jnp.asarray([0.0, 1.0, 50.0, 99.0, 100.0])),
            jnp.max(beta)[None]])

    def trunk(tok):
        """One sequence up to the last linear layer, and each linear
        layer's gates on the way."""
        x = params["embed"][tok]
        gates = []
        for i in range(pivot):
            if layer_types[i] == "linear_attention":
                gates.append(gate_summary(x, layers[i]))
            x = block(x, layers[i], layer_types[i])
        gates.append(gate_summary(x, layers[pivot]))
        return x, jnp.stack(gates)

    def tail(ln_f_scale, w_down, lin_wo, lin_wa, x_mid, lab):
        checked = {pivot: dict(layers[pivot], lin_wo=lin_wo, lin_wa=lin_wa)}
        last = len(layers) - 1
        checked[last] = dict(checked.get(last, layers[last]), w_down=w_down)

        def one_sequence(xl):
            x, lb = xl
            for i in range(pivot, len(layers)):
                x = jax.checkpoint(block, static_argnums=2)(
                    x, checked.get(i, layers[i]), layer_types[i])
            return _nll_sum(x, ln_f_scale, params["head"], lb, eps, low)

        return lax.map(one_sequence, (x_mid, lab)).sum() / lab.size

    with jax.default_matmul_precision("highest"):
        x_mid, gates = lax.map(trunk, tokens)
        loss, grads = jax.value_and_grad(tail, argnums=(0, 1, 2, 3))(
            params["ln_f_scale"], layers[-1]["w_down"],
            layers[pivot]["lin_wo"], layers[pivot]["lin_wa"], x_mid, labels)
    # Least of the leasts, greatest of the greatests, medians averaged.
    gates = jnp.concatenate([
        gates[..., :1].min(0), gates[..., 1:4].mean(0),
        gates[..., 4:].max(0)], axis=-1)
    return loss, {"ln_f_scale": grads[0], "w_down_last": grads[1],
                  "lin_wo_last": grads[2], "lin_wa_last": grads[3]}, gates
