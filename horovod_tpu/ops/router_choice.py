"""Which ``k`` of a token's ``E`` router scores are its largest, as one
Pallas TPU kernel that counts and does not sort.

A share of an expert layer no wider than the choice (``held_experts <=
experts_per_token``: :func:`horovod_tpu.models.moe.route_sigmoid_held`)
needs of the ``k`` chosen experts only *whether* each held one is among
them and the *sum* of the chosen scores.  Neither needs an index, so the
router needs no ``lax.top_k`` (a sort of ``E`` keys a token), no
``take_along_axis`` (whose gradient is a scatter-add into ``[N, E]``) and
no second sort to find the held ones: a membership mask does, and products
and row sums of it.

**The mask is** ``lax.top_k``'s **set exactly** (:func:`chosen`): the keys
above the ``k``-th largest, and of the keys equal to it the lowest-indexed
as many as are still wanted.  A float32's bit pattern, its low 31 bits
flipped where the sign is set, orders as the number does under signed
integer comparison (``-0.0`` below ``0.0``, where ``lax.top_k`` holds them
equal: a sigmoid's score plus a bias is never the first).  The ``k``-th
largest key of a token is built from its top bit down, a bit staying set
while at least ``k`` keys reach the candidate (32 passes of a compare and
a count), and the cut among the ties by the same bisection over the index
(``log2 E`` passes): :mod:`horovod_tpu.ops.sparse_attention`'s selection
does the same over a row of 16,384 causal scores in chunks; here a token's
keys are one block and there is no mask but the choice.

**Tokens on the lanes.**  A block of tokens' keys is turned in VMEM so
that a token is a lane and its experts lie along the sublanes: a count is
then adds of whole registers and one fold of eight sublanes a pass, where
experts on the lanes need a cross-lane sum a row a pass (measured at
``[8192, 512]``, ``k`` 22: docs/kernels.md, "The router's choice on a
narrow share").  The mask is turned back as it is written.

**Where it runs.**  Compiled by Mosaic where the executing mesh is TPU, in
the Pallas interpreter elsewhere; :func:`takes` says whether the kernel
can run on an operand, and :func:`chosen_xla` is the same set from
``lax.top_k`` itself, what a layer runs where it cannot and what the tests
hold the kernel to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.grouped_matmul import _interpret, _vma
from horovod_tpu.telemetry import scopes

LANES = 128
# Tokens a grid step holds: their keys turned, [E, TOKENS] int32, are
# what every counting pass reads (512 KiB at 512 experts).
TOKENS = 256
INT_MIN = jnp.iinfo(jnp.int32).min


def takes(keys) -> bool:
    """Whether the kernel can choose among ``keys`` [N, E], read for its
    dtype and sizes, the mesh that executes it and the axes it varies
    over: float32, experts in whole lane groups (the block is turned in
    VMEM), tokens in whole lane groups, and not the interpreter inside
    ``shard_map(check_vma=True)`` (``grouped_matmul``'s reason)."""
    n, e = keys.shape
    return (keys.dtype == jnp.float32 and e % LANES == 0 and n % LANES == 0
            and not (_interpret(keys) and _vma(keys)))


def chosen_xla(keys, k: int):
    """:func:`chosen` from ``lax.top_k``'s own indices: ``keys`` [N, E] ->
    [N, E] float32, 1.0 at a token's ``k`` largest keys (of equal keys the
    lower index: ``lax.top_k``'s rule), else 0.0."""
    _, top_i = lax.top_k(keys, k)
    return jnp.sum(top_i[..., None] == jnp.arange(keys.shape[-1]), axis=-2,
                   dtype=jnp.float32)


def _choose_kernel(keys_ref, mask_ref, key_ref, *, k: int):
    bits = lax.bitcast_convert_type(keys_ref[...], jnp.int32)
    # Ordered as the floats are, and turned: [E, tokens].
    key_ref[...] = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits).T
    experts, tokens = key_ref.shape
    index = lax.broadcasted_iota(jnp.int32, (experts, tokens), 0)

    def count(pred):
        return jnp.sum(pred.astype(jnp.int32), axis=0, keepdims=True)

    def reaches(cand):
        return count(key_ref[...] >= cand) >= k

    tau = jnp.where(reaches(jnp.zeros((1, tokens), jnp.int32)), 0,
                    INT_MIN).astype(jnp.int32)

    def value_bit(n, tau):
        cand = tau | jnp.left_shift(jnp.int32(1), 30 - n)
        return jnp.where(reaches(cand), cand, tau)

    # The k-th largest key of each token.
    tau = lax.fori_loop(0, 31, value_bit, tau)
    wanted = k - count(key_ref[...] > tau)               # >= 1

    bits_of_index = max(experts - 1, 1).bit_length()

    def index_bit(n, cut):
        cand = cut | jnp.left_shift(jnp.int32(1), bits_of_index - 1 - n)
        ties_before = count((key_ref[...] == tau) & (index < cand))
        return jnp.where(ties_before < wanted, cand, cut)

    # The largest index with fewer than ``wanted`` ties before it: the
    # last tie admitted sits there.
    cut = lax.fori_loop(0, bits_of_index, index_bit,
                        jnp.zeros((1, tokens), jnp.int32))
    key = key_ref[...]
    mask = (key > tau) | ((key == tau) & (index <= cut))
    mask_ref[...] = mask.astype(jnp.float32).T


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def _choose_call(keys, *, k: int, interpret: bool):
    n, e = keys.shape
    tokens = TOKENS if n % TOKENS == 0 else LANES
    return pl.pallas_call(
        functools.partial(_choose_kernel, k=k),
        out_shape=jax.ShapeDtypeStruct((n, e), jnp.float32, vma=_vma(keys)),
        grid=(n // tokens,),
        in_specs=[pl.BlockSpec((tokens, e), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tokens, e), lambda i: (i, 0)),
        scratch_shapes=[pltpu.VMEM((e, tokens), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret, name=scopes.MOE_CHOOSE,
    )(keys)


def chosen(keys, k: int):
    """``keys`` [N, E] float32 (sizes :func:`takes` accepts), ``0 < k <=
    E`` -> [N, E] float32: 1.0 at each token's ``k`` largest keys, of
    equal keys the lower index first (``lax.top_k``'s set, exactly ``k`` a
    token), else 0.0.  A choice carries no gradient: ``keys`` is read
    under ``stop_gradient``."""
    return _choose_call(lax.stop_gradient(keys), k=k,
                        interpret=_interpret(keys))
