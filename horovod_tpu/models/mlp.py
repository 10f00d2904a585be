"""The dense MLP, the feed-forward form of a layer without experts
(:mod:`horovod_tpu.models.parts`): ``w2 gelu(w1 h)`` (``mlp="gelu"``, the
GPT-2 block's) or SwiGLU, ``w_down (silu(w_gate h) * (w_up h))``, of width
``d_ff`` (:data:`MLP`); and the SwiGLU MLP that the first ``dense_layers``
layers of a model with experts keep in their place
(:data:`MLP_BESIDE_EXPERTS`)."""

from __future__ import annotations

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import parts
from horovod_tpu.models.parts import dense, ones, rmsnorm, whole
from horovod_tpu.parallel import tensor as tp
from horovod_tpu.telemetry import scopes


def mlp_block(x, layer, cfg, model_axis):
    """rmsnorm -> dense MLP (gelu, or SwiGLU) -> row-parallel psum ->
    (the sandwich's second norm, ``post_norm``) -> residual (shared by the
    training forward and the KV-cache decode so the two cannot drift)."""
    dt = cfg.dtype
    h = rmsnorm(x, layer["ln2_scale"], cfg.norm_eps)
    hi = tp.region_input(h, model_axis) if model_axis else h
    if cfg.mlp == "gelu":
        u = jax.nn.gelu(hi @ layer["w1"].astype(dt))
        dn = u @ layer["w2"].astype(dt)
    else:
        u = (jax.nn.silu(hi @ layer["w_gate"].astype(dt))
             * (hi @ layer["w_up"].astype(dt)))
        dn = u @ layer["w_down"].astype(dt)
    if model_axis:
        dn = lax.psum(dn, model_axis)
    return x + parts.post_normed(dn, layer, "ln2_post_scale", cfg)


def _validate(cfg, used):
    if cfg.mlp not in ("gelu", "swiglu", "relu2"):
        raise ValueError(f"mlp={cfg.mlp!r}: expected 'gelu', "
                         f"'swiglu' or 'relu2'")


def _validate_beside(cfg, used):
    if not 0 <= cfg.dense_layers <= cfg.n_layers:
        raise ValueError(
            f"dense_layers={cfg.dense_layers} must lie in "
            f"0..n_layers={cfg.n_layers}")
    if cfg.dense_layers and not used:
        raise ValueError(
            f"dense_layers={cfg.dense_layers}: no layer keeps a dense MLP "
            f"in the place of experts (it means nothing without n_experts)")
    if used and cfg.mlp != "swiglu":
        raise NotImplementedError(
            f"dense_layers={cfg.dense_layers} with mlp="
            f"{cfg.mlp!r}: the leading dense MLP is SwiGLU")


def _norms(cfg):
    return ("ln2_scale",) + ("ln2_post_scale",) * cfg.post_norm


def _init(k, cfg):
    d, f = cfg.d_model, cfg.d_ff
    norms = {name: ones(d) for name in _norms(cfg)}
    if cfg.mlp == "gelu":
        return dict(norms, w1=dense(k[4], (d, f)), w2=dense(k[5], (f, d)))
    return dict(norms, w_gate=dense(k[4], (d, f)),
                w_up=dense(parts.ffn_keys(k)[0], (d, f)),
                w_down=dense(k[5], (f, d)))


def _specs(cfg, model_axis):
    col, row = P(None, model_axis), P(model_axis, None)
    if cfg.mlp == "gelu":
        return dict(whole(*_norms(cfg)), w1=col, w2=row)
    return dict(whole(*_norms(cfg)), w_gate=col, w_up=col, w_down=row)


def _apply(x, layer, cfg, ctx):
    with jax.named_scope(scopes.MLP):
        return mlp_block(x, layer, cfg, ctx.model_axis), {}


def _apply_beside(x, layer, cfg, ctx):
    # A part of its own in a trace.
    with jax.named_scope(scopes.MLP), jax.named_scope(scopes.MLP_DENSE):
        return mlp_block(x, layer, cfg, None), {}


MLP = parts.Part(name="mlp", fields=("d_ff", "mlp"), validate=_validate,
                 init=_init, specs=_specs, apply=_apply)

# Beside experts it is whole on every chip, like them.
MLP_BESIDE_EXPERTS = parts.Part(
    name="mlp_beside_experts", fields=("dense_layers",),
    validate=parts.refuses_post_norm(
        _validate_beside, "a dense MLP beside experts"), init=_init,
    specs=lambda cfg, model_axis: whole("ln2_scale", "w_gate", "w_up",
                                        "w_down"),
    apply=_apply_beside, unsupported={"model_axis": ("dense_layers",)})
