"""What a layer of the LM is made of: the seam between the step
(:mod:`horovod_tpu.models.transformer`) and the parts it runs.

A layer holds up to two **parts**, a sequence mixer and a feed-forward
form, each a block of its own from norm to residual add.  A :class:`Part`
states in one place everything the step has to know of one: the
``TransformerConfig`` fields it owns and their rules, its leaves and how
they are split over a model axis, its body, its trace-time series, and
what it cannot run under.  The step keeps one table of them
(``transformer.PARTS``) and one function that chooses
(``transformer.layer_parts``); it branches on nothing else, so a new
mixer or feed-forward form is one module and one row.

The parts: :mod:`~horovod_tpu.models.attention` (plain, latent, learned
sparse and compressed convolutional attention),
:mod:`~horovod_tpu.models.linear_attention`,
:mod:`~horovod_tpu.models.mamba2`, :mod:`~horovod_tpu.models.mamba1`,
:mod:`~horovod_tpu.models.mlp` (the
dense MLP) and :mod:`~horovod_tpu.models.moe` (softmax-routed,
sigmoid-routed and latent experts, and one expert a token or a skip under
an MLP router).

Two things cross the seam beside ``x`` and ``x + y``: what a part hands
from layer to layer (``Part.carries``: the MLP router's state), which the
step threads through its recomputed blocks as an argument and a result;
and the scaled residual merge (:func:`merged`, ``residual_scaling``).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu import telemetry
from horovod_tpu.telemetry import scopes


class Ctx(NamedTuple):
    """What the step hands every part's body beside the layer's input."""

    model_axis: Optional[str]
    seq_axis: Optional[str]
    attention: str           # the route forward() was asked for
    positions: Any           # [T] int32 of this shard's tokens; None: learned
    tokens: int              # tokens of this shard's batch, B * T
    segment_ids: Any = None  # [B, T] int32 (packing), a mixer's alone
    mask: Any = None         # softmax attention's, where it is not causal


@dataclasses.dataclass(frozen=True, eq=False)
class Part:
    """One sequence mixer or feed-forward form.

    ``validate(cfg, used)``: its share of ``TransformerConfig``'s rules,
    what ``fields`` need and that they mean nothing without it (``used``:
    some layer of ``cfg`` holds it).  ``init(keys, cfg)``: its leaves, the
    norm's scale among them, from the layer's six keys.  ``specs(cfg,
    model_axis)``: a ``PartitionSpec`` a leaf.  ``apply(x, layer, cfg,
    ctx)``: norm, body and residual under its own scopes, ``(x, extras)``
    where ``extras`` maps a name to what the loss collects from every
    layer (``router_stats``, ``index_kl``).  ``record(name, x, layer, cfg,
    ctx)``: its trace-time series for layer ``name``.  ``unsupported``:
    for each of ``model_axis``, ``seq_axis`` and ``segment_ids``, its
    fields that it cannot run under that argument with (one that selects
    the part, where it implements none of it).  ``check_vma``: its body
    types under ``shard_map``'s checker of what varies over which axis.
    ``carries``: what it takes from the previous layer that holds the same
    part and hands to the next, beside ``x`` (``("router_state",)``): its
    body is then ``apply(x, layer, cfg, ctx, carried) -> (x, extras,
    carried)`` with ``carried`` a tuple in that order, ``None`` for what
    no earlier layer of the stack has handed on.  ``scaled_merge``: its
    residual is :func:`merged`, so it runs under ``residual_scaling``."""

    name: str
    fields: Tuple[str, ...]
    validate: Callable[[Any, bool], None]
    init: Callable
    specs: Callable
    apply: Callable
    record: Callable = lambda name, x, layer, cfg, ctx: None
    unsupported: Mapping[str, Tuple[str, ...]] = dataclasses.field(
        default_factory=dict)
    check_vma: bool = True
    carries: Tuple[str, ...] = ()
    scaled_merge: bool = False

    def __post_init__(self):
        # Every trace of the body is counted and timed by part name: the
        # start-up's ``trace_part/<name>`` (telemetry/spans.py).  Host
        # clock reads around the Python that traces; nothing traced
        # changes.
        body = self.apply

        @functools.wraps(body)
        def apply(x, layer, cfg, ctx, *carried):
            began = telemetry.clock()
            try:
                return body(x, layer, cfg, ctx, *carried)
            finally:
                telemetry.part_traced(self.name, telemetry.clock() - began)

        object.__setattr__(self, "apply", apply)


def everywhere(*fields):
    """``unsupported`` of a part that runs over the data axis alone."""
    return {what: fields for what in ("model_axis", "seq_axis",
                                      "segment_ids")}


def dense(key, shape, scale=None):
    """A float32 matrix ~ N(0, scale^2); ``shape[0] ** -0.5`` (its fan-in)
    without ``scale``."""
    scale = scale if scale is not None else (shape[0] ** -0.5)
    return jax.random.normal(key, shape, jnp.float32) * scale


def ffn_keys(keys):
    """``(k_up, k_router)``, what a feed-forward form draws beside the
    layer's ``keys[4]`` and ``keys[5]``."""
    return jax.random.split(jax.random.fold_in(keys[4], 1))


def ones(width: int):
    return jnp.ones((width,), jnp.float32)


def whole(*names):
    """Each leaf whole on every chip."""
    return {name: P() for name in names}


def normed_mixer(mixer, proj_scope: str, out_scope: str):
    """``apply`` of a mixer that opens its own scopes (``attn/qkv/*``, its
    scan, ``attn/out/*``): the norm is booked with its projections
    (``proj_scope``) and the residual add with the out projection
    (``out_scope``)."""
    def apply(x, layer, cfg, ctx):
        with jax.named_scope(scopes.ATTN_QKV), jax.named_scope(proj_scope):
            h = rmsnorm(x, layer["ln1_scale"], cfg.norm_eps)
        y = mixer(h, layer, cfg)
        with jax.named_scope(scopes.ATTN_OUT), jax.named_scope(out_scope):
            return x + y, {}
    return apply


def post_normed(y, layer, name: str, cfg):
    """A branch's output ``y`` through the sandwich's second norm (the
    leaf ``name``, ``cfg.post_norm``) before the residual add; ``y``
    itself without it."""
    if not cfg.post_norm:
        return y
    with jax.named_scope(scopes.POST_NORM):
        return rmsnorm(y, layer[name], cfg.norm_eps)


# The four vectors of one scaled residual merge, by what they multiply or
# shift (the stream's scale and bias, the branch's scale and bias), each
# with the mean and the standard deviation it is drawn with: off their
# neutral values, so that a check on seeded weights sees every one.
MERGE_LEAVES = (("res_scale", 1.0, 0.1), ("res_bias", 0.0, 0.02),
                ("out_scale", 1.0, 0.1), ("out_bias", 0.0, 0.02))


def merge_names(prefix: str):
    """The leaves of the merge ``prefix`` (``"merge1"``: the mixer's,
    ``"merge2"``: the feed-forward form's), in :data:`MERGE_LEAVES`'
    order."""
    return tuple(f"{prefix}_{name}" for name, _, _ in MERGE_LEAVES)


def merge_init(key, prefix: str, cfg):
    """The merge's leaves with ``cfg.residual_scaling`` (none without)."""
    if not cfg.residual_scaling:
        return {}
    keys = jax.random.split(jax.random.fold_in(key, 7), len(MERGE_LEAVES))
    return {f"{prefix}_{name}": mean + std * jax.random.normal(
                k, (cfg.d_model,), jnp.float32)
            for (name, mean, std), k in zip(MERGE_LEAVES, keys)}


def merged(x, y, layer, prefix: str, cfg):
    """The residual stream ``x`` and a branch's output ``y`` put together:
    ``x + y``, or with ``cfg.residual_scaling`` ``a_r * (x + b_r) + a_o *
    (y + b_o)`` with the four vectors of the merge ``prefix``
    (:func:`merge_names`), in float32, back in ``x``'s dtype."""
    if not cfg.residual_scaling:
        return x + y
    with jax.named_scope(scopes.RES_SCALE):
        a_r, b_r, a_o, b_o = (layer[name] for name in merge_names(prefix))
        return (a_r * (x.astype(jnp.float32) + b_r)
                + a_o * (y.astype(jnp.float32) + b_o)).astype(x.dtype)


def shifted(a, axis: int = 1):
    """``a`` one place later along ``axis`` (the sequence's), a zero row
    first: row ``t`` holds ``a[t - 1]``.  The one ``t - 1`` of the short
    causal convolutions and of the value shift."""
    pad = [(0, 0)] * a.ndim
    pad[axis] = (1, 0)
    return lax.slice_in_dim(jnp.pad(a, pad), 0, a.shape[axis], axis=axis)


def refuses_post_norm(validate, what: str):
    """``validate`` of a part that does not write the sandwich's second
    norm: it refuses ``post_norm`` by name where it is used."""
    def checked(cfg, used):
        validate(cfg, used)
        if used and cfg.post_norm:
            raise NotImplementedError(
                f"post_norm=True: the second norm of a sandwich-normed "
                f"branch is written for plain attention and the dense "
                f"MLP, not for {what}")
    return checked


def rmsnorm(x, scale, eps):
    # Stats in f32; output in the INPUT dtype.  The scale param is f32,
    # and without the cast it silently promoted every rmsnorm output —
    # and therefore every qkv/mlp matmul INPUT — to f32: measured 63.5%
    # -> 72.2% MFU on the d3584/L6 LM config from this one cast (r4).
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)).astype(x.dtype) *
            scale.astype(x.dtype))
