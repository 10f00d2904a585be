"""Plain reference of ResNet v1.5 with bottleneck blocks, in train mode.

Straight ``jax.numpy`` / ``lax.conv_general_dilated`` in float32, NHWC,
written from He et al. (arXiv:1512.03385, Table 1) with the stride on the
3x3 convolution (v1.5).  It shares no code with ``horovod_tpu/models/``;
it reads the program's variable tree (``conv_init``, ``norm_init``,
``<Block>_<i>`` with ``Conv_<j>``, ``BatchNorm_<j>``, ``conv_proj``,
``norm_proj``, ``head``) because that is what a checkpoint holds.

BatchNorm normalises with the statistics of the batch it is given, so the
whole of one replica's batch goes through at once (a few images at a time
would be another function); replicas are taken one after the other, as
Horovod's data parallelism leaves statistics per replica.  Departures from
the paper are listed in the configuration file: the space-to-depth stem
and flax's ``SAME`` padding on the strided convolutions.

On a TPU a float32 convolution runs in bf16 passes unless the precision is
raised, so :func:`loss_and_head_grad` sets it to ``"highest"``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

EPS = 1e-5


def _conv(x, kernel, stride=1, padding="SAME"):
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))


def _batch_norm(x, p):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) * lax.rsqrt(var + EPS) * p["scale"] + p["bias"]


def _max_pool_3x3_s2(x):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
        ((0, 0), (1, 1), (1, 1), (0, 0)))


def _bottleneck(x, p, stride):
    y = jax.nn.relu(_batch_norm(_conv(x, p["Conv_0"]["kernel"]),
                                p["BatchNorm_0"]))
    y = jax.nn.relu(_batch_norm(_conv(y, p["Conv_1"]["kernel"], stride),
                                p["BatchNorm_1"]))
    y = _batch_norm(_conv(y, p["Conv_2"]["kernel"]), p["BatchNorm_2"])
    if "conv_proj" in p:
        x = _batch_norm(_conv(x, p["conv_proj"]["kernel"], stride),
                        p["norm_proj"])
    return jax.nn.relu(x + y)


def _basic(x, p, stride):
    y = jax.nn.relu(_batch_norm(_conv(x, p["Conv_0"]["kernel"], stride),
                                p["BatchNorm_0"]))
    y = _batch_norm(_conv(y, p["Conv_1"]["kernel"]), p["BatchNorm_1"])
    if "conv_proj" in p:
        x = _batch_norm(_conv(x, p["conv_proj"]["kernel"], stride),
                        p["norm_proj"])
    return jax.nn.relu(x + y)


def _features(params, images, stage_sizes, stem):
    """Pooled features [B, C] of one replica's batch."""
    if stem == "s2d":      # images arrive packed [B, H/2, W/2, 12]
        x = _conv(images, params["conv_init"]["kernel"], 1,
                  ((2, 1), (2, 1)))
    else:
        x = _conv(images, params["conv_init"]["kernel"], 2,
                  ((3, 3), (3, 3)))
    x = _max_pool_3x3_s2(jax.nn.relu(_batch_norm(x, params["norm_init"])))
    kind = ("BottleneckBlock" if "BottleneckBlock_0" in params
            else "BasicBlock")
    block = _bottleneck if kind == "BottleneckBlock" else _basic
    index = 0
    for stage, count in enumerate(stage_sizes):
        for j in range(count):
            stride = 2 if stage > 0 and j == 0 else 1
            x = block(x, params[f"{kind}_{index}"], stride)
            index += 1
    return jnp.mean(x, axis=(1, 2))


def loss_and_head_grad(params, images, labels, stage_sizes, stem: str,
                       replicas: int):
    """``(loss, {"head_kernel": g})``: mean softmax cross-entropy over the
    global batch (``replicas`` equal parts, each normalised by its own
    batch statistics) and its gradient with respect to the final dense
    kernel, which needs a backward pass through the head only."""
    params = jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), params)
    images = images.astype(jnp.float32)

    def head_loss(kernel, feats, lab):
        logits = feats @ kernel + params["head"]["bias"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, lab[:, None], axis=-1))

    loss, grad = 0.0, jnp.zeros_like(params["head"]["kernel"])
    with jax.default_matmul_precision("highest"):
        for img, lab in zip(jnp.split(images, replicas),
                            jnp.split(labels, replicas)):
            feats = _features(params, img, stage_sizes, stem)
            l, g = jax.value_and_grad(head_loss)(
                params["head"]["kernel"], feats, lab)
            loss, grad = loss + l / replicas, grad + g / replicas
    return loss, {"head_kernel": grad}
