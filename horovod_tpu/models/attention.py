"""Softmax attention, the sequence mixer of a ``"full_attention"`` or
``"attention"`` layer, in its three forms (:mod:`horovod_tpu.models.parts`):

* :data:`ATTENTION` — plain: q, k, v projections over grouped heads
  (``n_kv_heads``) of ``head_width``, QK-norm over the whole projection
  (``qk_norm``, OLMoE) or a head at a time (``qk_norm_per_head``, Qwen3),
  rotary or learned positions, and the route ``forward`` was asked for:
  ``local | flash | ring | ring_flash | ulysses | auto``;
* :data:`LATENT_ATTENTION` — DeepSeek-V2's MLA in the up-projected form
  training runs (:func:`latent_qkv`), through the same routes;
* :data:`SPARSE_ATTENTION` — plain attention's projections beside an
  indexer that chooses the ``index_topk`` keys each query reads and learns
  from its own loss (:mod:`horovod_tpu.ops.sparse_attention`), a route of
  its own;
* :data:`CCA_ATTENTION` — compressed convolutional attention
  (arXiv:2510.04476): q and k projected into a latent narrower than the
  hidden size and mixed along the sequence by two short causal
  convolutions, the mean of the unmixed q and k added back, half of the
  value heads from the previous token, an L2 norm a head with a learned
  temperature on the keys, rotary over a head's first ``rotary_dims``
  (:func:`cca_qkv`), through the same routes.

:func:`qkv_proj` and :func:`attn_out` are also what ``decode_step`` and
the pipelined stage run, so the three cannot drift.
"""

from __future__ import annotations

import contextlib
import logging

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu import telemetry
from horovod_tpu.models import parts
from horovod_tpu.models.parts import dense, ones, rmsnorm, whole
from horovod_tpu.ops import mla_assemble, qk_assemble, sparse_attention
from horovod_tpu.ops.flash_attention import (flash_attention,
                                             flash_attention_folded)
from horovod_tpu.parallel import sequence as seq_mod
from horovod_tpu.parallel import tensor as tp
from horovod_tpu.telemetry import scopes


def rotary(x, positions, theta: float):
    """Rotary embedding of ``x`` [..., T, H, head_dim] at ``positions``
    [T], rotate-half convention (HF ``apply_rotary_pos_emb``): the pair
    (x_i, x_{i + head_dim/2}) turns by ``position * theta^(-2i/head_dim)``.
    Angles and the rotation in float32."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.cos(angles)[:, None, :]
    sin = jnp.sin(angles)[:, None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def qk_path(h, cfg, ctx, sparse: bool = False) -> str:
    """What norms a head at a time, rotates and lays out the heads of a
    plain (``sparse``: a sparse) attention layer of ``cfg`` from the
    projections of ``h`` [B, T, d] under ``ctx``: ``"kernel"``, the Pallas
    kernels of :mod:`horovod_tpu.ops.qk_assemble`, which write q, k, v in
    the attention kernels' layout, so only where those consume them (the
    single-device flash route; the sparse route by its kernels), for a
    per-head norm with rotary positions, no model axis and sizes
    ``qk_assemble.takes`` accepts; ``"xla"``, :func:`qkv_proj`'s own
    lines, everywhere else."""
    consumed = (sparse_attention.path(h) == "kernel" if sparse
                else _flash_route(ctx, h.shape[1]))
    return "kernel" if (
        cfg.qk_norm_per_head and cfg.positions == "rope"
        and ctx.model_axis is None and consumed and qk_assemble.takes(
            h, cfg.n_heads, cfg.kv_heads, cfg.head_dim)) else "xla"


def qkv_proj(x, layer, cfg, model_axis, positions=None, normed=None,
             path: str = "xla", share_kv: bool = False):
    """rmsnorm -> q/k/v projections -> (QK-norm) -> head split ->
    (rotary at ``positions`` [T]) (shared by forward, decode_step and
    forward_pipelined so the projection math cannot drift).  Returns q,
    k, v with a trailing [heads, head_dim] split.  ``normed``: the normed
    ``x`` where the caller has it already (it hands it to an indexer too).
    With ``path`` ``"kernel"`` (:func:`qk_path`) q ``[B * heads, T,
    head_dim]`` and k, v ``[B * kv_heads, T, head_dim]``, the attention
    kernels' layout (``share_kv``: each key-value head once a query head
    that reads it, ``[B * heads, T, head_dim]``, as :func:`_share_kv_heads`
    makes them for the flash kernels)."""
    dt = cfg.dtype
    h = (rmsnorm(x, layer["ln1_scale"], cfg.norm_eps) if normed is None
         else normed)
    hi = tp.region_input(h, model_axis) if model_axis else h
    q = hi @ layer["wq"].astype(dt)
    k = hi @ layer["wk"].astype(dt)
    v = hi @ layer["wv"].astype(dt)
    if cfg.qk_norm:
        q = rmsnorm(q, layer["q_norm_scale"], cfg.norm_eps)
        k = rmsnorm(k, layer["k_norm_scale"], cfg.norm_eps)
    dh = q.shape[-1]
    if path == "kernel":
        with jax.named_scope(scopes.QK_HEAD_NORM_ROPE):
            q, k, v = qk_assemble.qk_assemble(
                q, k, v, layer["q_norm_scale"], layer["k_norm_scale"],
                positions, cfg.n_heads, cfg.rope_theta, cfg.norm_eps,
                repeat=share_kv)
        return q, k, v, dh

    def heads(a):
        return a.reshape(a.shape[:-1] + (a.shape[-1] // cfg.head_dim,
                                         cfg.head_dim))

    q, k, v = heads(q), heads(k), heads(v)
    # The per-head norm and the rotation after it are a part of their own
    # in a trace; without the norm the rotation is booked as it always was.
    with (jax.named_scope(scopes.QK_HEAD_NORM_ROPE) if cfg.qk_norm_per_head
          else contextlib.nullcontext()):
        if cfg.qk_norm_per_head:
            q = rmsnorm(q, layer["q_norm_scale"], cfg.norm_eps)
            k = rmsnorm(k, layer["k_norm_scale"], cfg.norm_eps)
        if cfg.positions == "rope":
            q = rotary(q, positions, cfg.rope_theta)
            k = rotary(k, positions, cfg.rope_theta)
    return q, k, v, dh


@jax.named_scope(scopes.DSA_INDEX_PROJ)
def indexer_proj(u, layer, cfg, positions):
    """The indexer's operands from the layer's normed input ``u`` [B, T, d],
    whose gradient stops here (the indexer learns from its own loss and
    moves nothing else): queries ``[B, T, index_heads, index_head_dim]`` and
    ONE key head ``[B, T, index_head_dim]``, both rotary at ``positions``
    over all their dims, and a weight a head ``[B, T, index_heads]``."""
    dt = cfg.dtype
    u = lax.stop_gradient(u)
    qi = (u @ layer["index_wq"].astype(dt)).reshape(
        u.shape[:-1] + (cfg.index_heads, cfg.index_head_dim))
    ki = (u @ layer["index_wk"].astype(dt))[..., None, :]
    w = u @ layer["index_ww"].astype(dt)
    qi = rotary(qi, positions, cfg.rope_theta)
    ki = rotary(ki, positions, cfg.rope_theta)[..., 0, :]
    return qi, ki, w


def assemble_xla(q, up, k_r, positions, heads: int, theta: float):
    """Latent attention's heads put together, as ``jax.numpy``: ``q`` [...,
    T, H * hd] as heads of ``[q_n | q_r]``, ``up`` [..., T, H * (nope +
    hd)] as heads of ``[k_n | v]`` and the one rotary key ``k_r`` [..., T,
    rope]; ``q_r`` and ``k_r`` rotary at ``positions``, ``k = [k_n |
    k_r]``.  Returns q, k, v [..., T, heads, hd]."""
    rope = k_r.shape[-1]
    hd = q.shape[-1] // heads
    nope = hd - rope
    q = q.reshape(q.shape[:-1] + (heads, hd))
    up = up.reshape(up.shape[:-1] + (heads, nope + hd))
    k_n, v = up[..., :nope], up[..., nope:]
    q = jnp.concatenate(
        [q[..., :nope], rotary(q[..., nope:], positions, theta)], axis=-1)
    k_r = rotary(k_r[..., None, :], positions, theta)
    k = jnp.concatenate(
        [k_n, jnp.broadcast_to(k_r, k_n.shape[:-1] + (rope,))], axis=-1)
    return q, k, v


def assemble_path(h, cfg, ctx) -> str:
    """What puts the heads of a latent-attention layer of ``cfg`` together
    from the projections of ``h`` [B, T, d] under ``ctx``: ``"kernel"``,
    the Pallas kernels of :mod:`horovod_tpu.ops.mla_assemble`, which write
    q, k, v in the flash kernels' layout, so only on that route and for
    sizes ``mla_assemble.takes`` accepts (compiled where the mesh that
    executes ``h`` is TPU, interpreted elsewhere); ``"xla"``,
    :func:`assemble_xla`, everywhere else."""
    return "kernel" if mla_assemble.takes(
        h, cfg.n_heads, cfg.head_dim, cfg.rope_dim) and _flash_route(
            ctx, h.shape[1]) else "xla"


def latent_qkv(h, layer, cfg, positions, path: str = "xla"):
    """Latent attention's q, k, v from the normed input ``h`` [..., T, d],
    in the up-projected form (K and V materialised per head; the absorbed
    form and a cache of latents are decode's: ROADMAP R13): ``c_q =
    RMSNorm(h W_qa)``, ``q = c_q W_qb`` as heads of ``[q_n | q_r]``;
    ``[c_kv | k_r] = h W_kva``, ``c_kv <- RMSNorm(c_kv)``, ``[k_n | v] =
    c_kv W_kvb`` per head; ``q_r`` and ``k_r`` rotary at ``positions``,
    ``k_r`` **one head that every head's key ends in** (so its gradient
    sums over the heads); ``k = [k_n | k_r]``.  Returns q, k, v [..., T,
    heads, head_width] and ``heads * head_width``; with ``path``
    ``"kernel"`` (:func:`assemble_path`) q, k, v ``[B * heads, T,
    head_width]``, the flash kernels' layout."""
    dt, heads, rank = cfg.dtype, cfg.n_heads, cfg.kv_latent_rank
    with jax.named_scope(scopes.MLA_Q):
        c_q = rmsnorm(h @ layer["w_qa"].astype(dt),
                      layer["q_latent_norm_scale"], cfg.norm_eps)
        q = c_q @ layer["w_qb"].astype(dt)
    with jax.named_scope(scopes.MLA_KV):
        down = h @ layer["w_kva"].astype(dt)
        c_kv = rmsnorm(down[..., :rank], layer["kv_latent_norm_scale"],
                       cfg.norm_eps)
        up = c_kv @ layer["w_kvb"].astype(dt)
    with jax.named_scope(scopes.MLA_ROPE):
        assemble = (mla_assemble.mla_assemble if path == "kernel"
                    else assemble_xla)
        q, k, v = assemble(q, up, down[..., rank:], positions, heads,
                           cfg.rope_theta)
    return q, k, v, heads * cfg.head_dim


def _share_kv_heads(k, v, n_heads: int):
    """Grouped-query attention's K and V as the attention routes take
    them, one head a query head: each key-value head repeated for the
    ``n_heads / kv_heads`` query heads that read it (so dK and dV sum over
    the group); as they are where the counts are equal.  A copy in HBM:
    a kernel that reads head ``h // group`` instead is ROADMAP R3."""
    group = n_heads // k.shape[-2]
    if group == 1:
        return k, v
    return jnp.repeat(k, group, axis=-2), jnp.repeat(v, group, axis=-2)


def attn_out(o_flat, x, layer, cfg, model_axis):
    """Output projection (row-parallel psum under TP), the sandwich's
    second norm (``post_norm``) + residual."""
    o = o_flat @ layer["wo"].astype(cfg.dtype)
    if model_axis:
        o = lax.psum(o, model_axis)
    return x + parts.post_normed(o, layer, "ln1_post_scale", cfg)


_flash_declined_shapes: set = set()


def _flash_profitable(t: int) -> bool:
    """``attention="auto"``'s flash-vs-lax decision, made at TRACE time
    from the (static) sequence length.  With the kernel's auto block
    sizes (r3 sweep, docs/kernels.md table): measured fwd-only PARITY at
    T=1024 and measured wins from T=2048 up (fwd-only and fwd+bwd), so
    1024 is the safe default threshold — at worst a tie; override with
    HOROVOD_FLASH_AUTO_MIN_T.  Auto also refuses lengths the compiled
    kernel cannot tile (indivisible by the 128-lane block) and falls
    back to the lax path — ``auto`` NEVER raises on shape; only an
    explicit ``attention="flash"`` may (the user asked for the kernel).
    """
    import os
    min_t = int(os.environ.get("HOROVOD_FLASH_AUTO_MIN_T", "1024"))
    if t >= min_t and t % 128 != 0:
        if t not in _flash_declined_shapes:   # one-time per length
            _flash_declined_shapes.add(t)
            logging.getLogger("horovod_tpu").debug(
                "attention='auto': T=%d is not divisible by 128; using "
                "the lax attention path (pad the sequence to enable the "
                "flash kernel)", t)
        return False
    return t >= min_t


def _flash_route(ctx, t: int) -> bool:
    """Whether ``ctx`` sends ``t`` tokens through the single-device flash
    kernels."""
    return ctx.seq_axis is None and (
        ctx.attention in ("flash", "ring_flash")
        or (ctx.attention == "auto" and _flash_profitable(t)))


def _routed(q, k, v, dh, x, layer, cfg, ctx, out=attn_out):
    """``q, k, v`` through the route ``ctx.attention`` names (each opens
    its own ``attn/<route>``), the out projection and the residual
    (``out``: :func:`attn_out`, or a part's own with its arguments)."""
    seq_axis, attention, segment_ids = (ctx.seq_axis, ctx.attention,
                                        ctx.segment_ids)
    # ``True``, or the step's own mask (block diffusion's: no sequence
    # axis and no packing with it, transformer._refuse_beyond_the_data_axis).
    mask = True if ctx.mask is None else ctx.mask
    b, t = q.shape[:2]
    flash = _flash_route(ctx, t)
    with jax.named_scope(scopes.ATTN_FLASH if flash else scopes.ATTN_QKV):
        k, v = _share_kv_heads(k, v, q.shape[-2])
    if seq_axis is not None:
        if attention == "ring_flash" or (attention == "auto" and
                                         _flash_profitable(t)):
            # Ring attention with the flash kernel as the per-step
            # block math: auto upgrades when the LOCAL chunk length
            # clears the kernel's measured crossover.
            o = seq_mod.ring_flash_attention(
                q, k, v, seq_axis, True, None, None, segment_ids)
        elif attention in ("ring", "auto"):
            o = seq_mod.ring_attention(q, k, v, seq_axis, causal=True,
                                       segment_ids=segment_ids)
        elif attention == "ulysses":
            o = seq_mod.ulysses_attention(q, k, v, seq_axis, causal=True,
                                          segment_ids=segment_ids)
        else:
            # The single-device flash kernel route makes no sense
            # under a sequence axis; K/V blocks arrive over ICI and
            # the blockwise math lives in ring[_flash]_attention.
            # Never silently substitute a different algorithm.
            raise ValueError(
                f"attention={attention!r} is not available with a "
                f"sequence axis; choose 'ring', 'ring_flash' or "
                f"'ulysses'")
    elif flash:
        # Pallas flash kernel (ops/flash_attention.py): same exact
        # math blockwise in VMEM; requires T divisible by its blocks.
        # 'ring_flash' without a seq axis degenerates to exactly
        # this kernel (a 1-ring's only step is the diagonal one) —
        # the user still measures the algorithm they selected.
        o = flash_attention(q, k, v, mask, segment_ids=segment_ids)
    else:
        o = seq_mod.local_attention(q, k, v, causal=mask,
                                    segment_ids=segment_ids)
    with jax.named_scope(scopes.ATTN_OUT):
        return out(o.reshape(b, t, dh), x, layer, cfg, ctx.model_axis)


def _folded_out(o, x, layer, cfg):
    """The out projection and the residual for ``o`` as the attention
    kernels leave it, ``[B * heads, T, head_dim]`` (or any shape of the
    same bytes that ends ``[T, head_dim]``): it contracts over (head,
    width)."""
    heads, hd = cfg.n_heads, cfg.head_dim
    with jax.named_scope(scopes.ATTN_OUT):
        o = jnp.einsum(
            "bhtd,hdm->btm", o.reshape((-1, heads) + o.shape[-2:]),
            layer["wo"].astype(cfg.dtype).reshape(heads, hd, -1))
        return x + parts.post_normed(o, layer, "ln1_post_scale", cfg)


def _folded_flash(q, k, v, x, layer, cfg, ctx):
    """The flash route for heads born in the flash kernels' layout, q, k,
    v ``[B * heads, T, head_dim]``: the kernels under the step's mask and
    :func:`_folded_out`."""
    mask = True if ctx.mask is None else ctx.mask
    o = flash_attention_folded(q, k, v, cfg.n_heads, mask,
                               segment_ids=ctx.segment_ids)
    return _folded_out(o, x, layer, cfg)


# --- plain attention --------------------------------------------------------

def _validate(cfg, used):
    if cfg.n_kv_heads and cfg.n_heads % cfg.n_kv_heads:
        raise ValueError(f"n_kv_heads={cfg.n_kv_heads} does not "
                         f"divide n_heads={cfg.n_heads}")
    if cfg.qk_norm and cfg.qk_norm_per_head:
        raise ValueError("qk_norm norms the whole projection, "
                         "qk_norm_per_head each head: one of them")


def _init(k, cfg):
    d, wide, d_kv = cfg.d_model, cfg.attn_width, cfg.kv_heads * cfg.head_dim
    layer = dict(ln1_scale=ones(d), wq=dense(k[0], (d, wide)),
                 wk=dense(k[1], (d, d_kv)), wv=dense(k[2], (d, d_kv)),
                 wo=dense(k[3], (wide, d)))
    if cfg.qk_norm:
        layer.update(q_norm_scale=ones(d), k_norm_scale=ones(d_kv))
    if cfg.qk_norm_per_head:
        layer.update(q_norm_scale=ones(cfg.head_dim),
                     k_norm_scale=ones(cfg.head_dim))
    if cfg.post_norm:
        layer.update(ln1_post_scale=ones(d))
    return layer


def _specs(cfg, model_axis):
    col, row = P(None, model_axis), P(model_axis, None)
    specs = dict(whole("ln1_scale"), wq=col, wk=col, wv=col, wo=row)
    if cfg.qk_norm or cfg.qk_norm_per_head:
        specs.update(whole("q_norm_scale", "k_norm_scale"))
    if cfg.post_norm:
        specs.update(whole("ln1_post_scale"))
    return specs


def _apply(x, layer, cfg, ctx):
    path = qk_path(x, cfg, ctx)
    with jax.named_scope(scopes.ATTN_QKV):
        q, k, v, dh = qkv_proj(x, layer, cfg, ctx.model_axis, ctx.positions,
                               path=path, share_kv=True)
    if path != "kernel":
        return _routed(q, k, v, dh, x, layer, cfg, ctx), {}
    return _folded_flash(q, k, v, x, layer, cfg, ctx), {}


def _record(name, x, layer, cfg, ctx, sparse: bool = False):
    if cfg.qk_norm_per_head:
        batch, t = x.shape[:2]
        qk_assemble.record_rows(name, batch * t,
                                qk_path(x, cfg, ctx, sparse))


# QK-norm's statistics span the whole projection, which the model axis
# splits; the per-head norm's scale and a head's own width are not split
# with the heads, nor are grouped key-value heads.
_NOT_SPLIT = ("qk_norm", "n_kv_heads", "head_width", "qk_norm_per_head")

ATTENTION = parts.Part(
    name="attention",
    fields=("n_kv_heads", "qk_norm", "qk_norm_per_head", "head_width"),
    validate=_validate, init=_init, specs=_specs, apply=_apply,
    record=_record,
    unsupported={"model_axis": _NOT_SPLIT,
                 "seq_axis": ("head_width", "qk_norm_per_head")})


# --- latent attention -------------------------------------------------------

_LATENT = ("q_latent_rank", "kv_latent_rank", "rope_dim")


def _latent_validate(cfg, used):
    # A head width alone is plain attention with heads of that width.
    latent = tuple(getattr(cfg, name) for name in _LATENT)
    if not any(latent):
        return
    if (cfg.q_latent_rank or cfg.kv_latent_rank) and not cfg.head_width:
        raise ValueError(
            f"q_latent_rank={cfg.q_latent_rank}, kv_latent_rank="
            f"{cfg.kv_latent_rank}: latent attention needs head_width, the "
            f"width its up-projections give a head")
    if min(latent + (cfg.head_width,)) <= 0:
        raise ValueError(
            "q_latent_rank, kv_latent_rank and rope_dim come together, with "
            "head_width: they are latent attention")
    if cfg.rope_dim > cfg.head_width:
        raise ValueError(
            f"rope_dim={cfg.rope_dim} is wider than head_width="
            f"{cfg.head_width}: the rotary part is the tail of a head")
    if cfg.positions != "rope" or cfg.rope_dim % 2:
        raise ValueError(
            f"latent attention carries position in its rotary part: it "
            f"needs positions='rope' and an even rope_dim, got "
            f"{cfg.positions!r} and {cfg.rope_dim}")
    if cfg.qk_norm or cfg.n_kv_heads or cfg.qk_norm_per_head:
        raise NotImplementedError(
            "latent attention norms its latents and gives every head its "
            "own key: qk_norm, qk_norm_per_head and n_kv_heads are not "
            "implemented with it")


def _latent_init(k, cfg):
    d, wide = cfg.d_model, cfg.n_heads * cfg.head_dim
    r_q, r_kv = cfg.q_latent_rank, cfg.kv_latent_rank
    return dict(
        ln1_scale=ones(d),
        w_qa=dense(k[0], (d, r_q)), q_latent_norm_scale=ones(r_q),
        w_qb=dense(k[1], (r_q, wide)),
        # To [c_kv | k_r], the latent and the one rotary key.
        w_kva=dense(k[2], (d, r_kv + cfg.rope_dim)),
        kv_latent_norm_scale=ones(r_kv),
        # To [k_n | v] of each head in turn.
        w_kvb=dense(jax.random.fold_in(k[2], 1),
                    (r_kv, 2 * wide - cfg.n_heads * cfg.rope_dim)),
        wo=dense(k[3], (wide, d)))


def _latent_specs(cfg, model_axis):
    # Whole on every chip (heads over the model axis: ROADMAP R16).
    return whole("ln1_scale", "w_qa", "q_latent_norm_scale", "w_qb", "w_kva",
                 "kv_latent_norm_scale", "w_kvb", "wo")


def _latent_apply(x, layer, cfg, ctx):
    path = assemble_path(x, cfg, ctx)
    with jax.named_scope(scopes.ATTN_QKV):
        q, k, v, dh = latent_qkv(
            rmsnorm(x, layer["ln1_scale"], cfg.norm_eps), layer, cfg,
            ctx.positions, path)
    if path != "kernel":
        return _routed(q, k, v, dh, x, layer, cfg, ctx), {}
    return _folded_flash(q, k, v, x, layer, cfg, ctx), {}


def _latent_record(name, x, layer, cfg, ctx):
    batch, t = x.shape[:2]
    mla_assemble.record_rows(name, batch * t, assemble_path(x, cfg, ctx))


# Not written: heads of a latent's up-projection over chips, and the
# shared rotary key under the ring and Ulysses routes.
LATENT_ATTENTION = parts.Part(
    name="latent_attention", fields=_LATENT,
    validate=parts.refuses_post_norm(_latent_validate, "latent attention"),
    init=_latent_init, specs=_latent_specs, apply=_latent_apply,
    record=_latent_record,
    unsupported={"model_axis": ("head_width",) + _LATENT,
                 "seq_axis": ("head_width",) + _LATENT})


# --- learned sparse attention -----------------------------------------------

_SPARSE = ("index_heads", "index_head_dim", "index_topk",
           "indexer_loss_coef")


def _sparse_validate(cfg, used):
    sparse = tuple(getattr(cfg, name) for name in _SPARSE)
    if not any(sparse):
        return
    if min(sparse) <= 0:
        raise ValueError(
            "index_heads, index_head_dim, index_topk and indexer_loss_coef "
            "come together: they are learned sparse attention")
    if cfg.positions != "rope" or cfg.index_head_dim % 2:
        raise ValueError(
            f"the indexer's queries and key are rotary: it needs "
            f"positions='rope' and an even index_head_dim, got "
            f"{cfg.positions!r} and {cfg.index_head_dim}")
    if cfg.latent_attention:
        raise NotImplementedError(
            "an indexer beside latent attention (index_heads with "
            "kv_latent_rank) is not implemented")


def _sparse_init(k, cfg):
    d = cfg.d_model
    k_index = jax.random.split(jax.random.fold_in(k[0], 1), 3)
    return dict(
        _init(k, cfg),
        index_wq=dense(k_index[0], (d, cfg.index_heads * cfg.index_head_dim)),
        index_wk=dense(k_index[1], (d, cfg.index_head_dim)),
        index_ww=dense(k_index[2], (d, cfg.index_heads)))


def _sparse_specs(cfg, model_axis):
    return dict(_specs(cfg, model_axis),
                **whole("index_wq", "index_wk", "index_ww"))


def _sparse_apply(x, layer, cfg, ctx):
    # The route of its own: the indexer chooses each query's keys
    # (ops/sparse_attention.py), whatever ``ctx.attention`` says.
    path = qk_path(x, cfg, ctx, sparse=True)
    folded = path == "kernel"
    with jax.named_scope(scopes.ATTN_QKV):
        u = rmsnorm(x, layer["ln1_scale"], cfg.norm_eps)
        q, k, v, dh = qkv_proj(x, layer, cfg, ctx.model_axis, ctx.positions,
                               normed=u, path=path)
        qi, ki, w = indexer_proj(u, layer, cfg, ctx.positions)
    with jax.named_scope(scopes.ATTN_FLASH):
        o, kl = sparse_attention.dsa_attention(
            q, k, v, qi, ki, w, topk=cfg.index_topk,
            index_scale=(cfg.index_heads * cfg.index_head_dim) ** -0.5,
            folded=folded)
    if folded:
        return _folded_out(o, x, layer, cfg), {"index_kl": jnp.sum(kl)}
    with jax.named_scope(scopes.ATTN_OUT):
        return (attn_out(o.reshape(o.shape[:2] + (dh,)), x, layer, cfg,
                         ctx.model_axis), {"index_kl": jnp.sum(kl)})


def _sparse_record(name, x, layer, cfg, ctx):
    sparse_attention.record_path(sparse_attention.path(x))
    _record(name, x, layer, cfg, ctx, sparse=True)


# Not written: a query's selected keys lie on other chips under a sequence
# axis, a selection that stays inside a document, and the indexer's
# weights over a model axis.
SPARSE_ATTENTION = parts.Part(
    name="sparse_attention", fields=_SPARSE,
    validate=parts.refuses_post_norm(_sparse_validate,
                                     "learned sparse attention"),
    init=_sparse_init, specs=_sparse_specs, apply=_sparse_apply,
    record=_sparse_record,
    unsupported={"model_axis": _NOT_SPLIT + _SPARSE,
                 "seq_axis": ("head_width", "qk_norm_per_head") + _SPARSE,
                 "segment_ids": ("index_topk",)})


# --- compressed convolutional attention -------------------------------------

def cca_convolutions(c, layer, head_dim: int, dtype):
    """Both causal convolutions of CCA over the packed ``c`` [B, T, C]
    (``C`` = heads x ``head_dim``, query and key-value heads together) ->
    [B, T, C / head_dim, head_dim] float32: the depthwise one, ``c1[t] =
    w0 * c[t-1] + w1 * c[t] + b`` a channel in float32 (``cca_dw_w`` [2,
    C], ``cca_dw_b``), then the one grouped by head, ``c2[t] = c1[t-1]
    C0_j + c1[t] C1_j + b'`` (``cca_gw_w`` [2, heads, head_dim, head_dim],
    ``cca_gw_b``), both taps as ONE matmul a head of ``[c1[t-1] | c1[t]]``
    and ``[C0_j; C1_j]`` in ``dtype``.  Rows before the first are zero
    (:func:`parts.shifted`)."""
    f32 = jnp.float32
    c, taps = c.astype(f32), layer["cca_dw_w"]
    c1 = (taps[0] * parts.shifted(c) + taps[1] * c
          + layer["cca_dw_b"]).astype(dtype)
    c1 = c1.reshape(c1.shape[:-1] + (-1, head_dim))
    mats = jnp.concatenate(list(layer["cca_gw_w"].astype(dtype)), axis=-2)
    both = jnp.concatenate([parts.shifted(c1), c1], axis=-1)
    return (jnp.einsum("bthd,hde->bthe", both, mats).astype(f32)
            + layer["cca_gw_b"].reshape(c1.shape[-2:]))


def cca_qkv(u, layer, cfg, positions):
    """CCA's q, k, v from the normed input ``u`` [B, T, d], with ``H``
    query heads on ``G`` key-value heads of ``D``, ``g(h) = h // (H / G)``:

    * ``q~ = u W_q`` [B, T, H, D], ``k~ = u W_k`` [B, T, G, D];
    * the mix, over ``c = [q~ | k~]`` [B, T, (H + G) D]: a causal depthwise
      convolution of two taps, ``c1[t] = w0 * c[t-1] + w1 * c[t] + b`` a
      channel, then a causal convolution of two taps grouped by head,
      ``c2[t] = c1[t-1] C0_j + c1[t] C1_j + b'`` with ``C0_j``, ``C1_j`` [D,
      D] a head ``j`` of the ``H + G``; rows before the first are zero
      (:func:`parts.shifted`); ``(q', k') = c2``;
    * the mean: ``m_q[h] = (q~[h] + k~[g(h)]) / 2``, ``m_k[j]`` the mean of
      ``m_q`` over the query heads of group ``j``; ``q = q' + m_q``, ``k =
      k' + m_k``;
    * the values: the first ``G / 2`` heads are ``u[t] W_v_now``, the
      others ``u[t-1] W_v_prev`` (the shift taken after the product: a
      zero row maps to a zero row);
    * ``q <- sqrt(D) q / |q|``, ``k <- sqrt(D) exp(tau_j) k / |k|`` a head
      (``tau``: ``k_temp`` [G]), then rotary at ``positions`` over the
      first ``cfg.rotary_dims`` of every head (0: all of them).

    bf16 operands with float32 accumulation in the matmuls; the depthwise
    taps, the mean, the norms' statistics, the temperature and the
    rotation in float32.  Returns q [B, T, H, D] and k, v [B, T, G, D]."""
    dt, f32 = cfg.dtype, jnp.float32
    heads, groups, hd = cfg.n_heads, cfg.kv_heads, cfg.head_dim
    per_group = heads // groups

    def split(a):
        return a.reshape(a.shape[:-1] + (a.shape[-1] // hd, hd))

    q0 = u @ layer["wq"].astype(dt)
    k0 = u @ layer["wk"].astype(dt)
    v_now = u @ layer["wv_now"].astype(dt)
    v_prev = u @ layer["wv_prev"].astype(dt)
    with jax.named_scope(scopes.CCA_MIX):
        c2 = cca_convolutions(jnp.concatenate([q0, k0], axis=-1), layer,
                              hd, dt)
        q0, k0 = split(q0.astype(f32)), split(k0.astype(f32))
        m_q = 0.5 * (q0 + jnp.repeat(k0, per_group, axis=-2))
        m_k = jnp.mean(m_q.reshape(m_q.shape[:-2] + (groups, per_group, hd)),
                       axis=-2)
        q = c2[..., :heads, :] + m_q
        k = c2[..., heads:, :] + m_k
        v = jnp.concatenate([split(v_now), parts.shifted(split(v_prev))],
                            axis=-2)
    with jax.named_scope(scopes.CCA_NORM_ROPE):
        def unit(a):
            return a * lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) / hd + 1e-30)

        q = unit(q)
        k = unit(k) * jnp.exp(layer["k_temp"])[:, None]
        turned = cfg.rotary_dims or hd
        q, k = (jnp.concatenate(
            [rotary(a[..., :turned], positions, cfg.rope_theta),
             a[..., turned:]], axis=-1).astype(dt) for a in (q, k))
    return q, k, v


def _cca_validate(cfg, used):
    if not cfg.cca_taps:
        if cfg.rotary_dims:
            raise ValueError(
                f"rotary_dims={cfg.rotary_dims} (a head's first dims that "
                f"rotary turns) means nothing without cca_taps")
        return
    if tuple(cfg.cca_taps) != (2, 2):
        raise NotImplementedError(
            f"cca_taps={cfg.cca_taps!r}: the two causal convolutions are "
            f"written with two taps each, (2, 2)")
    if cfg.positions != "rope" or cfg.rotary_dims % 2 or not (
            0 <= cfg.rotary_dims <= cfg.head_dim):
        raise ValueError(
            f"cca_taps: the heads are rotary over their first rotary_dims "
            f"(0: all): it needs positions='rope' and an even rotary_dims "
            f"up to head_dim={cfg.head_dim}, got {cfg.positions!r} and "
            f"{cfg.rotary_dims}")
    if cfg.kv_heads % 2:
        raise ValueError(
            f"cca_taps: half of the key-value heads carry the previous "
            f"token's value: n_kv_heads={cfg.kv_heads} must be even")
    if (cfg.qk_norm or cfg.qk_norm_per_head or cfg.latent_attention
            or cfg.sparse_attention):
        raise NotImplementedError(
            "cca_taps: compressed convolutional attention norms its heads "
            "itself and is no latent or sparse attention: qk_norm, "
            "qk_norm_per_head, kv_latent_rank and index_topk are not "
            "implemented with it")


def _cca_init(k, cfg):
    d, hd = cfg.d_model, cfg.head_dim
    wide, d_kv = cfg.n_heads * hd, cfg.kv_heads * hd
    mixed, both = wide + d_kv, cfg.n_heads + cfg.kv_heads
    k_mix = jax.random.split(jax.random.fold_in(k[1], 1), 5)
    return dict(
        ln1_scale=ones(d), wq=dense(k[0], (d, wide)),
        wk=dense(k[1], (d, d_kv)), wv_now=dense(k[2], (d, d_kv // 2)),
        wv_prev=dense(jax.random.fold_in(k[2], 1), (d, d_kv // 2)),
        wo=dense(k[3], (wide, d)),
        # [tap, channel], tap 0 on the previous row; [tap, head, in, out].
        cca_dw_w=dense(k_mix[0], (2, mixed), scale=0.5 ** 0.5),
        cca_dw_b=dense(k_mix[1], (mixed,), scale=0.02),
        cca_gw_w=dense(k_mix[2], (2, both, hd, hd), scale=(2 * hd) ** -0.5),
        cca_gw_b=dense(k_mix[3], (mixed,), scale=0.02),
        k_temp=dense(k_mix[4], (cfg.kv_heads,), scale=0.1),
        **parts.merge_init(k[3], "merge1", cfg))


_CCA_LEAVES = ("ln1_scale", "wq", "wk", "wv_now", "wv_prev", "wo",
               "cca_dw_w", "cca_dw_b", "cca_gw_w", "cca_gw_b", "k_temp")


def _cca_specs(cfg, model_axis):
    return whole(*_CCA_LEAVES, *(parts.merge_names("merge1")
                                 * cfg.residual_scaling))


def _cca_out(o_flat, x, layer, cfg, model_axis):
    """The out projection and the (scaled) residual merge."""
    return parts.merged(x, o_flat @ layer["wo"].astype(cfg.dtype), layer,
                        "merge1", cfg)


def _cca_apply(x, layer, cfg, ctx):
    with jax.named_scope(scopes.ATTN_QKV):
        q, k, v = cca_qkv(rmsnorm(x, layer["ln1_scale"], cfg.norm_eps),
                          layer, cfg, ctx.positions)
    return _routed(q, k, v, cfg.n_heads * cfg.head_dim, x, layer, cfg, ctx,
                   out=_cca_out), {}


def _cca_record(name, x, layer, cfg, ctx):
    if telemetry.enabled():
        telemetry.counter(
            "hvd_cca_rows_total",
            "Token rows the traced compressed-convolutional-attention "
            "layer mixes along the sequence per step on one device",
            layer=str(name)).inc(x.shape[0] * x.shape[1])


# Not written: the previous shard's last rows for the shift and the
# convolutions under a sequence axis (a halo of one and two rows), a
# document's boundary under packing, and heads over a model axis.
CCA_ATTENTION = parts.Part(
    name="cca_attention", fields=("cca_taps", "rotary_dims"),
    validate=parts.refuses_post_norm(
        _cca_validate, "compressed convolutional attention"),
    init=_cca_init, specs=_cca_specs, apply=_cca_apply, record=_cca_record,
    unsupported=parts.everywhere("cca_taps"), scaled_merge=True)
