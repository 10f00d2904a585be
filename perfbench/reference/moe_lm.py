"""Plain reference of the OLMoE block the ``moe_lm`` cells train.

Straight ``jax.numpy`` in float32 at precision ``highest``: no kernel, no
``shard_map``, no bf16, no sort and no grouped matmul.  It shares no code
with ``horovod_tpu/``; it reads the program's parameter tree (``embed``,
``head``, ``ln_f_scale``, ``layers[i]`` with ``ln1_scale``, ``ln2_scale``,
``wq``, ``wk``, ``wv``, ``wo``, ``q_norm_scale``, ``k_norm_scale``,
``router``, ``w_gate``, ``w_up``, ``w_down``) because that tree is what a
checkpoint of the system holds.

The block, as HF ``modeling_olmoe.py`` states it (arXiv:2409.02060):

* ``h = RMSNorm(x)``; ``q, k, v = h Wq, h Wk, h Wv`` without bias; RMSNorm
  of the whole 2048-wide q and k, each with its own scale; heads of 128;
  rotary embedding, rotate-half convention, positions from 0; causal
  softmax attention at scale ``head_dim ** -0.5``; ``x = x + o Wo``;
* ``h = RMSNorm(x)``; router logits ``r = h Wr``; ``softmax(r)`` over all
  experts, the ``k`` largest kept as they are (``norm_topk_prob`` false:
  not renormalised; of equal probabilities the lower index wins); expert
  ``e`` is ``W_down,e (silu(W_gate,e h) * (W_up,e h))``;
  ``x = x + sum_k p_k expert_{i_k}(h)``.  Here **every token goes through
  every expert, in a loop over the experts, and the result is masked by
  the top-k weights**: no token is dropped because none is ever moved;
* final RMSNorm, untied head, float32 logits; the loss is the mean
  next-token cross-entropy + ``aux_coef`` x the load-balancing loss +
  ``z_coef`` x the router z-loss.  Load balancing as HF's
  ``load_balancing_loss_func``: ``E * sum_e f_e P_e`` over all layers'
  tokens together, ``P_e`` the mean router probability and ``f_e`` the
  assignments to expert ``e`` per token (``sum_e f_e = k``).  z-loss: the
  mean of ``logsumexp(r) ** 2``.

Attention and every layer but the last run one sequence at a time; the
last expert layer, the head and the loss run over all tokens at once, so
that the gradient of the last ``w_down`` (half a GiB in float32) exists
once.  ``low_precision`` is for the experiments that set and test the
tolerances (PERF.md, PR 26; ``tests/test_moe_lm.py``): what the same
reference reads when every matmul's operands, and the router's logits,
are rounded to that dtype (float8 on the chip: the precision below the
bfloat16 the configuration states; bfloat16 in the float32 unit tests).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

QUERY_BLOCK = 256
HEAD_BLOCK = 1024


def _mm(a, b, low_precision):
    """``a @ b``; with ``low_precision``, of operands rounded to it."""
    if low_precision is not None:
        # Values rounded, gradients passed straight through: a float8
        # cotangent would underflow to zero.
        a, b = (x + lax.stop_gradient(
            x.astype(low_precision).astype(jnp.float32) - x) for x in (a, b))
    return a @ b


def _rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)


def _rope(x, theta):
    """x: [T, H, D] of one sequence, positions 0..T-1."""
    t, _, d = x.shape
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]
    return x * jnp.cos(emb) + _rotate_half(x) * jnp.sin(emb)


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

    return lax.map(one_block, jnp.arange(0, t, block)).reshape(q.shape)


def _attention_block(x, layer, n_heads, eps, theta, low):
    t = x.shape[0]
    h = _rmsnorm(x, layer["ln1_scale"], eps)
    q = _rmsnorm(_mm(h, layer["wq"], low), layer["q_norm_scale"], eps)
    k = _rmsnorm(_mm(h, layer["wk"], low), layer["k_norm_scale"], eps)
    v = _mm(h, layer["wv"], low)
    split = (t, n_heads, -1)
    o = _attention(_rope(q.reshape(split), theta),
                   _rope(k.reshape(split), theta), v.reshape(split))
    return x + _mm(o.reshape(t, -1), layer["wo"], low)


def _route(h, router, top_k, low):
    """``(weights, probabilities, chosen, logits)``, each [N, E]: weights
    are the softmax probability where the expert is among the token's
    ``top_k``, else 0."""
    logits = _mm(h, router, low)
    if low is not None:
        logits = logits + lax.stop_gradient(
            logits.astype(low).astype(jnp.float32) - logits)
    probs = jax.nn.softmax(logits, axis=-1)
    experts = probs.shape[-1]
    before = jnp.arange(experts)[None, :] < jnp.arange(experts)[:, None]
    # rank[n, e]: experts that beat e for token n (greater, or equal with
    # a lower index).
    beats = (probs[:, None, :] > probs[:, :, None]) | (
        (probs[:, None, :] == probs[:, :, None]) & before[None])
    chosen = jnp.sum(beats, axis=-1) < top_k
    return probs * chosen, probs, chosen, logits


def _experts(h, weights, w_gate, w_up, w_down, low):
    """``sum_e weights[:, e] * expert_e(h)``, one expert at a time; the
    backward pass recomputes an expert's activations (kept, they would be
    ``tokens x experts x width`` floats)."""

    @jax.checkpoint
    def one_expert(h, weight, gate_w, up_w, down_w):
        gate = _mm(h, gate_w, low)
        act = gate * jax.nn.sigmoid(gate) * _mm(h, up_w, low)
        return weight[:, None] * _mm(act, down_w, low)

    def add(acc, expert):
        return acc + one_expert(h, *expert), None

    out, _ = lax.scan(add, jnp.zeros_like(h),
                      (weights.T, w_gate, w_up, w_down))
    return out


def _moe_block(x, ln2_scale, router, w_gate, w_up, w_down, eps, top_k,
               low):
    """``(x + moe(x), stats)``; stats are sums over the tokens: router
    probability per expert, assignments per expert, logsumexp squared."""
    h = _rmsnorm(x, ln2_scale, eps)
    weights, probs, chosen, logits = _route(h, router, top_k, low)
    z = jax.nn.logsumexp(logits, axis=-1)
    stats = (probs.sum(0), chosen.sum(0).astype(jnp.float32), (z * z).sum())
    return x + _experts(h, weights, w_gate, w_up, w_down, low), stats


def _nll_sum(x, ln_f_scale, head, labels, eps, low):
    """Summed next-token negative log-likelihood of ``x`` [N, d]."""
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


def loss_and_tail_grads(params, tokens, labels, *, n_heads: int, top_k: int,
                        eps: float, theta: float, aux_coef: float,
                        z_coef: float, low_precision=None):
    """``(loss, {"ln_f_scale": g, "w_down_last": g, "router_last": g},
    assignments [layers, E])`` of the batch ``tokens`` [B, T]: the total
    loss from a full forward pass; the three gradients from a backward
    pass through the head and the last expert layer only (they depend on
    nothing below it; the other layers enter the load-balancing loss as
    constants); and the assignments per layer and expert."""
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
    layers = params["layers"]
    last = layers[-1]
    n = tokens.size
    n_experts = last["router"].shape[1]

    def moe(x, layer, router, w_down):
        return _moe_block(x, layer["ln2_scale"], router, layer["w_gate"],
                          layer["w_up"], w_down, eps, top_k, low_precision)

    def trunk(tok):
        """One sequence up to the last expert layer, and the router sums
        of the layers before it."""
        x = params["embed"][tok]
        stats = []
        for i, layer in enumerate(layers):
            x = _attention_block(x, layer, n_heads, eps, theta,
                                 low_precision)
            if i < len(layers) - 1:
                x, s = moe(x, layer, layer["router"], layer["w_down"])
                stats.append(s)
        return x, stats

    def tail(ln_f_scale, w_down, router, x_mid, lab, below):
        x, (prob, own_count, z) = moe(x_mid, last, router, w_down)
        nll = _nll_sum(x, ln_f_scale, params["head"], lab, eps,
                       low_precision)
        rows = n * len(layers)
        prob = (prob + sum(s[0] for s in below)) / rows
        count = (own_count + sum(s[1] for s in below)) / rows
        z = (z + sum(s[2] for s in below)) / rows
        loss = (nll / n + aux_coef * n_experts * jnp.sum(count * prob)
                + z_coef * z)
        return loss, own_count

    with jax.default_matmul_precision("highest"):
        x_mid, per_sequence = lax.map(trunk, tokens)
        below = [tuple(part.sum(0) for part in s) for s in per_sequence]
        (loss, last_count), grads = jax.value_and_grad(
            tail, argnums=(0, 1, 2), has_aux=True)(
            params["ln_f_scale"], last["w_down"], last["router"],
            x_mid.reshape(n, -1), labels.reshape(n), below)
    assignments = jnp.stack([s[1] for s in below] + [last_count])
    return loss, {"ln_f_scale": grads[0], "w_down_last": grads[1],
                  "router_last": grads[2]}, assignments
