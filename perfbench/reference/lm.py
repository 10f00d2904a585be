"""Plain reference of the GPT-2-style block the LM cells train.

Straight ``jax.numpy`` in float32: no kernel, no ``shard_map``, no bf16,
one sequence at a time, attention in blocks of queries against the whole
causal context, the head in blocks of positions.  It shares no code with
``horovod_tpu/models/``; it reads the program's parameter tree
(``embed``, ``pos``, ``ln_f_scale``, ``layers[i]`` with ``ln1_scale``,
``ln2_scale``, ``wq``, ``wk``, ``wv``, ``wo``, ``w1``, ``w2``) because
that tree is what a checkpoint of the system holds.

The block, as the configuration's ``departures`` list it: pre-norm
RMSNorm without bias (epsilon 1e-6), full multi-head causal attention,
tanh-approximate GELU MLP, learned positions, no linear biases, head tied
to the embedding, mean next-token cross-entropy.

On a TPU a float32 matmul runs in bf16 passes unless the precision is
raised, so :func:`loss_and_tail_grads` sets it to ``"highest"``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 256
HEAD_BLOCK = 1024


def _rmsnorm(x, scale):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + 1e-6) \
        * scale


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _attention(q, k, v):
    """q, k, v: [T, H, D] of one sequence; causal softmax attention."""
    t, _, d = q.shape
    block = min(QUERY_BLOCK, t)
    if t % block:
        raise ValueError(f"sequence length {t} not a multiple of {block}")
    kpos = jnp.arange(t)

    def one_block(start):
        qb = lax.dynamic_slice_in_dim(q, start, block, axis=0)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * (d ** -0.5)
        qpos = start + jnp.arange(block)
        s = jnp.where(kpos[None, None, :] <= qpos[None, :, None], s,
                      -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)

    out = lax.map(one_block, jnp.arange(0, t, block))
    return out.reshape(q.shape)


def _mlp(x, ln2_scale, w1, w2):
    return x + _gelu_tanh(_rmsnorm(x, ln2_scale) @ w1) @ w2


def _trunk(params, tokens, n_heads):
    """Residual stream of one sequence just before the last MLP block."""
    t = tokens.shape[0]
    x = params["embed"][tokens] + params["pos"][:t]
    last = len(params["layers"]) - 1
    for i, layer in enumerate(params["layers"]):
        h = _rmsnorm(x, layer["ln1_scale"])
        split = (t, n_heads, -1)
        o = _attention((h @ layer["wq"]).reshape(split),
                       (h @ layer["wk"]).reshape(split),
                       (h @ layer["wv"]).reshape(split))
        x = x + o.reshape(t, -1) @ layer["wo"]
        if i < last:
            x = _mlp(x, layer["ln2_scale"], layer["w1"], layer["w2"])
    return x


def _nll_sum(x, ln_f_scale, embed, labels):
    """Summed next-token negative log-likelihood of one sequence."""
    t = x.shape[0]
    block = min(HEAD_BLOCK, t)
    if t % block:
        raise ValueError(f"sequence length {t} not a multiple of {block}")

    @jax.checkpoint
    def one_block(xl):
        xb, lb = xl
        logits = _rmsnorm(xb, ln_f_scale) @ embed.T
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, lb[:, None], axis=-1).sum()

    return lax.map(one_block, (x.reshape(t // block, block, -1),
                               labels.reshape(t // block, block))).sum()


def loss_and_tail_grads(params, tokens, labels, n_heads: int):
    """``(loss, {"ln_f_scale": g, "w2_last": g})`` of the mean over every
    token of ``tokens`` [B, T]: the loss from a full forward pass, the two
    gradients from a backward pass through the head and the last MLP only
    (they depend on nothing below it)."""
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
    last = params["layers"][-1]

    def tail(ln_f_scale, w2, x_mid, lab):
        x = _mlp(x_mid, last["ln2_scale"], last["w1"], w2)
        return _nll_sum(x, ln_f_scale, params["embed"], lab)

    def one_sequence(acc, tok_lab):
        tok, lab = tok_lab
        x_mid = _trunk(params, tok, n_heads)
        nll, (g_ln, g_w2) = jax.value_and_grad(tail, argnums=(0, 1))(
            params["ln_f_scale"], last["w2"], x_mid, lab)
        return (acc[0] + nll, acc[1] + g_ln, acc[2] + g_w2), None

    with jax.default_matmul_precision("highest"):
        zero = (jnp.zeros((), jnp.float32),
                jnp.zeros_like(params["ln_f_scale"]),
                jnp.zeros_like(last["w2"]))
        (nll, g_ln, g_w2), _ = lax.scan(one_sequence, zero,
                                        (tokens, labels))
    n = tokens.size
    return nll / n, {"ln_f_scale": g_ln / n, "w2_last": g_w2 / n}
