"""ResNet v1.5 in flax, TPU-first.

This is the flagship benchmark model (the reference benchmarks ResNet-50 via
``tf.keras.applications.ResNet50`` in
``examples/tensorflow2_synthetic_benchmark.py:30`` and
``torchvision.models.resnet50`` in ``examples/pytorch_synthetic_benchmark.py:33``;
published scaling numbers are ResNet-101, ``docs/benchmarks.rst:26-43``).

TPU design choices
------------------
* **NHWC** layout — what XLA:TPU prefers for convolutions feeding the MXU.
* **bfloat16 compute, float32 parameters/statistics** — MXU-native wire and
  matmul dtype with fp32 accumulation (XLA accumulates bf16 matmuls in fp32
  on TPU by default); no loss-scaling needed, unlike fp16 on GPUs.
* **Static shapes everywhere**; stride-2 convs instead of pooling where v1.5
  specifies, so the whole network is one fusible XLA program.
* BatchNorm keeps **per-replica statistics** (exactly the reference's
  data-parallel semantics: Horovod averages gradients, never BN statistics —
  see reference ``docs/concepts.rst``); pass ``axis_name`` to opt into
  cross-replica (synchronized) BN, which rides a tiny ``psum`` on ICI.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import flax.linen as nn
import jax.numpy as jnp
import numpy as np

ModuleDef = Any


def space_to_depth(x, block: int = 2):
    """Pack ``block x block`` spatial patches into channels (NHWC).

    ``[B, H, W, C] -> [B, H/b, W/b, b*b*C]`` with channel index
    ``(dy*b + dx)*C + c``.  This is the TPU input-pipeline layout for the
    ResNet stem: the 7x7/s2 conv on 224x224x3 reads 3-channel pixels —
    3 of 128 vector lanes — while the packed equivalent reads 12-channel
    super-pixels.  Do this ONCE in the input pipeline (it is a pure
    relayout); `conv7_to_s2d_weights` maps stem weights so the packed
    conv computes bit-identical math.
    """
    b, h, w, c = x.shape
    x = x.reshape(b, h // block, block, w // block, block, c)
    x = x.transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, h // block, w // block, block * block * c)


def conv7_to_s2d_weights(w7):
    """Exact reparameterization of 7x7/s2 stem weights for the s2d stem.

    Returns ``w4[4, 4, 4*C, O]`` such that ``conv(s2d(x), w4, stride 1,
    pad [(2,1),(2,1)]) == conv(x, w7, stride 2, pad 3)``: output pixel i
    reads original rows ``2i-3 .. 2i+3``, i.e. packed rows ``i-2 .. i+1``
    — a 4x4 window over 2x2-packed super-pixels.  15 of the 64 packed
    taps correspond to no original tap and stay zero (they exist — and
    train — in the packed model; the packed family is a strict superset).
    """
    kh, kw, c, o = w7.shape
    assert (kh, kw) == (7, 7), w7.shape
    w4 = np.zeros((4, 4, 4 * c, o), dtype=np.asarray(w7).dtype)
    for ky in range(7):
        for kx in range(7):
            ku, dy = (ky - 3) // 2 + 2, (ky - 3) % 2
            kv, dx = (kx - 3) // 2 + 2, (kx - 3) % 2
            w4[ku, kv, (dy * 2 + dx) * c:(dy * 2 + dx + 1) * c, :] = \
                np.asarray(w7[ky, kx])
    return w4


def _act(fn, y):
    """Activation tagged for remat policies: under ``remat="lean"`` the
    post-BN/relu tensors are NOT saved for backward — they are recomputed
    elementwise from the (saved) conv outputs, which XLA fuses into the
    consuming backward ops, trading negligible VPU work for one full
    activation write+read of HBM traffic per conv (the step is
    bandwidth-bound, see docs/benchmarks.md)."""
    from jax.ad_checkpoint import checkpoint_name
    return checkpoint_name(fn(y), "act")


class BottleneckBlock(nn.Module):
    """1x1 -> 3x3 -> 1x1 bottleneck with expansion 4 (ResNet-50/101/152)."""

    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (1, 1))(x)
        y = self.norm()(y)
        y = _act(self.act, y)
        # v1.5: the stride lives on the 3x3, not the 1x1.
        y = self.conv(self.filters, (3, 3), self.strides)(y)
        y = self.norm()(y)
        y = _act(self.act, y)
        y = self.conv(self.filters * 4, (1, 1))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters * 4, (1, 1),
                                 self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return _act(self.act, residual + y)


class BasicBlock(nn.Module):
    """3x3 -> 3x3 residual block (ResNet-18/34)."""

    filters: int
    conv: ModuleDef
    norm: ModuleDef
    act: Callable
    strides: Tuple[int, int] = (1, 1)

    @nn.compact
    def __call__(self, x):
        residual = x
        y = self.conv(self.filters, (3, 3), self.strides)(x)
        y = self.norm()(y)
        y = _act(self.act, y)
        y = self.conv(self.filters, (3, 3))(y)
        y = self.norm(scale_init=nn.initializers.zeros)(y)
        if residual.shape != y.shape:
            residual = self.conv(self.filters, (1, 1),
                                 self.strides, name="conv_proj")(residual)
            residual = self.norm(name="norm_proj")(residual)
        return _act(self.act, residual + y)


class ResNet(nn.Module):
    """ResNet v1.5 over NHWC inputs."""

    stage_sizes: Sequence[int]
    block_cls: ModuleDef
    num_classes: int = 1000
    num_filters: int = 64
    dtype: Any = jnp.bfloat16
    axis_name: Optional[str] = None   # set to sync BN stats across replicas
    # "conv7": canonical 7x7/s2 stem on [B,224,224,3].  "s2d": equivalent
    # 4x4/s1 stem on space_to_depth-packed [B,112,112,12] input (exact
    # reparameterization, see conv7_to_s2d_weights) — the TPU-friendly
    # form: 12 input channels instead of 3 fill vector lanes 4x denser.
    stem: str = "conv7"
    # None: save whatever AD saves.  "lean": per-block jax.checkpoint that
    # saves everything EXCEPT post-BN/relu activations (recomputed
    # elementwise in backward, fused — trades VPU flops for HBM traffic).
    # "full": save only block inputs (minimum memory, recompute convs).
    remat: Optional[str] = None

    @nn.compact
    def __call__(self, x, train: bool = True):
        conv = functools.partial(nn.Conv, use_bias=False, dtype=self.dtype,
                                 param_dtype=jnp.float32)
        norm = functools.partial(
            nn.BatchNorm, use_running_average=not train, momentum=0.9,
            epsilon=1e-5, dtype=self.dtype, param_dtype=jnp.float32,
            axis_name=self.axis_name if train else None)

        x = x.astype(self.dtype)
        if self.stem not in ("conv7", "s2d"):
            raise ValueError(
                f"stem={self.stem!r}: expected 'conv7' or 's2d'")
        if self.stem == "s2d":
            x = conv(self.num_filters, (4, 4), (1, 1),
                     padding=[(2, 1), (2, 1)], name="conv_init")(x)
        else:
            x = conv(self.num_filters, (7, 7), (2, 2),
                     padding=[(3, 3), (3, 3)], name="conv_init")(x)
        x = norm(name="norm_init")(x)
        x = nn.relu(x)
        x = nn.max_pool(x, (3, 3), strides=(2, 2), padding=((1, 1), (1, 1)))
        block_cls = self.block_cls
        if self.remat is not None:
            if self.remat not in ("lean", "full"):
                raise ValueError(
                    f"remat={self.remat!r}: expected None, 'lean' or 'full'")
            import jax
            # "lean": save anything EXCEPT the tagged post-BN/relu
            # activations (NOT save_any_names_but_these, which saves only
            # named values — i.e. nothing here — and degenerates to full
            # per-block remat).
            policy = (jax.checkpoint_policies
                      .save_anything_except_these_names("act")
                      if self.remat == "lean" else None)
            block_cls = nn.remat(block_cls, policy=policy,
                                 prevent_cse=False)
        for i, block_count in enumerate(self.stage_sizes):
            for j in range(block_count):
                strides = (2, 2) if i > 0 and j == 0 else (1, 1)
                x = block_cls(self.num_filters * 2 ** i,
                              conv=conv, norm=norm, act=nn.relu,
                              strides=strides)(x)
        x = jnp.mean(x, axis=(1, 2))
        x = nn.Dense(self.num_classes, dtype=self.dtype,
                     param_dtype=jnp.float32, name="head")(x)
        return x.astype(jnp.float32)


ResNet18 = functools.partial(ResNet, stage_sizes=[2, 2, 2, 2],
                             block_cls=BasicBlock)
ResNet34 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3],
                             block_cls=BasicBlock)
ResNet50 = functools.partial(ResNet, stage_sizes=[3, 4, 6, 3],
                             block_cls=BottleneckBlock)
ResNet101 = functools.partial(ResNet, stage_sizes=[3, 4, 23, 3],
                              block_cls=BottleneckBlock)
ResNet152 = functools.partial(ResNet, stage_sizes=[3, 8, 36, 3],
                              block_cls=BottleneckBlock)
