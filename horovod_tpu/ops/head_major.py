"""What the kernel pairs that write an attention part's heads head-major
share (:mod:`horovod_tpu.ops.mla_assemble`, latent attention's;
:mod:`horovod_tpu.ops.qk_assemble`, plain attention's): the tokens a grid
step holds, ``rotary``'s tables and its rotation on a float32 tile, and the
compiler's parameters of a grid ``(batch, T / tile)``.

Both pairs read a tile of tokens at the projections' full width and write
``[heads, tile, width]`` blocks: token on sublanes and a head's width on
lanes on both sides, so nothing is transposed inside a kernel; a head is
placed, not moved.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.selective_scan import VMEM_LIMIT

# Tokens a grid step holds at most where a kernel does not say (docs/
# kernels.md, "Latent attention's assembly"), and the least: a 16-bit
# dtype's sublane tile.
TILE = 128
ROWS = 16

_F32 = jnp.float32

COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel"),
    vmem_limit_bytes=VMEM_LIMIT)


def token_tile(t: int, vmem_bytes, most: int = TILE):
    """Tokens a grid step holds for ``t`` tokens: the largest power of two
    from :data:`ROWS` up to ``most`` that divides ``t`` and whose
    ``vmem_bytes(tile)`` :data:`VMEM_LIMIT` holds; None where there is
    none (the length has to be whole sublane tiles of a 16-bit dtype)."""
    if t <= 0 or t % ROWS:
        return None
    tile = most
    while tile >= ROWS:
        if t % tile == 0 and vmem_bytes(tile) <= VMEM_LIMIT:
            return tile
        tile //= 2
    return None


def tables(positions, rope: int, theta: float):
    """``cos`` and ``sin`` [T, rope / 2] of ``rotary``'s angles
    (:func:`horovod_tpu.models.attention.rotary`), float32."""
    half = rope // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=_F32) / half)
    angles = positions.astype(_F32)[:, None] * inv_freq[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def rotate(x, cos, sin):
    """``rotary``'s lines on the float32 ``x`` [tile, rope]; the inverse
    (its transpose) is the same with ``-sin``."""
    half = x.shape[-1] // 2
    x1, x2 = x[:, :half], x[:, half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)
