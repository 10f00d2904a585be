"""The gated-delta-rule recurrence of a linear-attention layer as Pallas
TPU kernels: the chunked form of
:mod:`horovod_tpu.models.linear_attention` with a head's state kept in
VMEM over all of its blocks.

Per head, with ``b_i`` the running sum of ``g = log alpha`` from the
start of a block of :data:`BLOCK` tokens and ``S_0`` the state the block
starts from (the mathematics is the linear-attention module's docstring)::

    N_ij = beta_i exp(b_i - b_j) (k_i . k_j)  for j < i
    T    = (I + N)^-1 diag(beta)
    U    = T V - (T diag(exp b) K) S_0
    O    = diag(exp b) Q S_0 + (Q K^T * exp(b_i - b_j), j <= i) U
    S_C  = exp(b_C) S_0 + (diag(exp(b_C - b)) K)^T U

**Grid.**  ``(batch x heads, T / tile)``: the first axis ``parallel``,
the second ``arbitrary`` and walked in order (the backward kernel walks
it in reverse).  A grid step holds a tile of up to :data:`TILE_PACKS`
packs of every operand, head-major (``[B*H, T, d]``; ``d_k`` and ``d_v``
are whole trailing dimensions, so 96 and 192 need no padding of the
caller's), and the gates as ``[B*H, T / ROWS, ROWS]``: a pack's gates are
one row along the lanes.  Inside a grid step a loop walks the tile's
packs.

**A pack** is :data:`PACK` = 2 blocks worked on as one: their ``K K^T``,
decays, ``N``, inverse, ``T`` and masked ``Q K^T`` are [128, 128]
matrices, block-diagonal (a mask, or a factor that is one, keeps what
two blocks have with one another out), and ``T V``, ``T diag(exp b) K``
and the products of the backward are one matmul for both blocks.  The
arithmetic of a block is what it would be alone; only the state walks
the pack's blocks one after the other.  The MXU is 128 wide: a product
of two [64, 64] matrices costs it what one of two [128, 128] does, and a
[64, 64] float32 matrix fills half of each vector register.

**What stays in VMEM.**  The ``[d_k, d_v]`` float32 state (``dS`` in the
backward kernel) lives in a scratch for the whole walk of a head.  Of a
block, the running sums of ``g``, the decays (masked before the ``exp``),
``K K^T``, the inverse, ``T V``, ``T diag(exp b) K``, ``U``, the masked
``Q K^T`` and the state's update exist in VMEM and registers only: q, k,
v, ``g`` and ``beta`` are read once and ``o`` is written once.

**Precision.**  That of the linear-attention module: ``g``, its sums,
the decays, ``N``, the inverse (products at precision ``highest``) and
the carried state are float32; every other product takes operands in the
model dtype (q's) and accumulates in float32.  ``T V``, ``U`` and the
part of ``o`` that comes from ``S_0``, which the ``jax.numpy`` form
stores in the model dtype between its phases, stay float32 here until
they are operands.

**Backward.**  The forward kernel that runs under differentiation also
writes the state at each block's start (float32, ``[B*H, T / BLOCK, d_k,
d_v]``: :func:`horovod_tpu.models.linear_attention.saved_state_bytes`);
the primal call does not.  The backward kernel walks the blocks from the
last to the first with ``dS`` in VMEM, recomputes a pack's intermediates
from its inputs and its blocks' saved states, and writes ``dq``, ``dk``, ``dv``
(model dtype), ``dg`` and ``dbeta`` (float32).  The inverse's gradient is
``-A^-T G A^-T``, strictly lower part, at precision ``highest``.

**Where it runs.**  Compiled by Mosaic where the executing mesh is TPU,
in the Pallas interpreter (the same code) elsewhere:
``topology.exec_on_tpu``.  :func:`takes` says whether the kernels can
run on an operand: its length has to be whole blocks that cut into tiles
(:func:`tiles`; a last pack that is not whole is filled up), and
the interpreter cannot run them inside ``shard_map(check_vma=True)`` (its
loop over a tile's packs carries the scratch, which it makes unvarying,
beside the operands, which vary over the batch axes: the checker refuses
the carry).  The caller runs the ``jax.numpy`` form where they cannot.
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

# Tokens a block of the chunked recurrence holds: one [BLOCK, BLOCK]
# triangular system a head and block.  Chosen on the chip and on the CPU
# (PERF.md, PR 31): one layer's recurrence at Olmo-Hybrid's sizes and
# 16384 tokens, forward + backward, took 68.8 / 65.0 / 49.1 / 84.4 ms at
# 32 / 64 / 128 / 256 as jax.numpy, but a [128, 128] system with beta
# near 2 and alpha near 1 is conditioned a hundred times worse than a
# [64, 64] one (float32 against the token-by-token recurrence: 9e-4 where
# 64 reads 3e-6), and the operands are bf16.  64 is also the family's.
BLOCK = 64
# Blocks the kernels work on at once, as one block-diagonal system: two
# blocks are 128 rows, the MXU's width.  On the chip one layer's forward
# took 17.9 ms a block at a time, 13.5 two, 27.1 four (PERF.md, PR 32).
PACK = 2
ROWS = PACK * BLOCK
# Packs a grid step holds at most: a tile of 1024 tokens (twice or half
# that takes the same time on the chip).  The backward kernel's tiles of
# q, k, v, o's gradient and the three gradients, double-buffered, and its
# sixteen saved states take 8 MB of VMEM at Olmo-Hybrid's widths; the
# compiler's default allowance is 16 MiB.
TILE_PACKS = 8

_HIGHEST = lax.Precision.HIGHEST
_NN = ((1,), (0,))
_NT = ((1,), (1,))
_TN = ((0,), (0,))


def tiles(t: int):
    """Packs a grid step holds for a sequence of ``t`` tokens: the largest
    divisor of ``t / ROWS`` up to :data:`TILE_PACKS` that is a whole
    number of float32 sublane groups (the gates' tile is ``[packs,
    ROWS]``) or the whole sequence; None where there is none, or ``t`` is
    not whole packs."""
    if t % ROWS:
        return None
    n = t // ROWS
    for packs in range(min(n, TILE_PACKS), 0, -1):
        if n % packs == 0 and (packs % 8 == 0 or packs == n):
            return packs
    return None


def _packs(t: int):
    """:func:`tiles` of ``t`` tokens with their last pack filled up; None
    where ``t`` is not whole blocks."""
    return None if t % BLOCK else tiles(-(-t // ROWS) * ROWS)


def _dot(a, b, contract, precision=None):
    return lax.dot_general(a, b, (contract, ((), ())), precision=precision,
                           preferred_element_type=jnp.float32)


def _unit_lower_inverse(n, eye):
    """``(I + n)^-1`` for ``n`` [ROWS, ROWS] float32, block-diagonal with
    strictly lower-triangular blocks of :data:`BLOCK`: the Neumann series
    ``(I - n)(I + n^2)(I + n^4)...``, which ends because ``n^BLOCK = 0``."""
    inverse, power, reach = jnp.where(eye, 1.0, 0.0) - n, n, 2
    while reach < BLOCK:
        power = _dot(power, power, _NN, _HIGHEST)
        inverse = inverse + _dot(inverse, power, _NN, _HIGHEST)
        reach *= 2
    return inverse


def _block(a: int):
    """The rows of a pack's block ``a``."""
    return slice(a * BLOCK, (a + 1) * BLOCK)


class _Pack:
    """What the forward computes of a pack of blocks before it meets the
    state, from the operands ``q``, ``k`` [ROWS, d_k], ``v`` [ROWS, d_v]
    (model dtype) and the gates ``g``, ``beta`` [1, ROWS] (float32).
    Every [ROWS, ROWS] matrix is block-diagonal: a mask or a factor keeps
    what two different blocks have with one another out.  ``_col`` is
    [ROWS, 1], ``_row`` [1, ROWS]."""

    def __init__(self, q, k, v, g_row, beta_row):
        dt = q.dtype
        ii = lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 0)
        jj = lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 1)
        self.same = ii // BLOCK == jj // BLOCK
        self.eye = ii == jj
        self.lower, self.strict = self.same & (ii >= jj), self.same & (ii > jj)
        # Running sums of g down a block's rows; a vector changes between
        # row and column through the diagonal.
        b_col = jnp.sum(jnp.where(self.lower, g_row, 0.0), axis=1,
                        keepdims=True)
        diff = b_col - self.to_row(b_col)
        # exp(b_i - b_j) for j <= i, else 0 (masked before the exp: above
        # the diagonal the difference is positive and may overflow).
        self.decay = jnp.where(
            self.lower, jnp.exp(jnp.where(self.lower, diff, 0.0)), 0.0)
        self.strict_decay = jnp.where(self.strict, self.decay, 0.0)
        b_end = jnp.sum(jnp.where(self.same, g_row, 0.0), axis=1,
                        keepdims=True)               # b_C of the row's block
        self.from_start = jnp.exp(b_col)             # exp(b_i)
        self.to_end = jnp.exp(b_end - b_col)         # exp(b_C - b_i)
        self.carry = jnp.exp(b_end)                  # exp(b_C)
        self.beta_row, self.beta_col = beta_row, self.to_col(beta_row)
        self.kk = _dot(k, k, _NT)
        self.inverse = _unit_lower_inverse(
            self.beta_col * self.strict_decay * self.kk, self.eye)
        self.solve = (self.inverse * beta_row).astype(dt)          # T
        k32 = k.astype(jnp.float32)
        self.k_start = (self.from_start * k32).astype(dt)
        self.k_end = (self.to_end * k32).astype(dt)
        self.q_start = (self.from_start * q.astype(jnp.float32)).astype(dt)
        self.u0 = _dot(self.solve, v, _NN)
        self.w = _dot(self.solve, self.k_start, _NN).astype(dt)
        self.qk = _dot(q, k, _NT)
        self.within = (self.qk * self.decay).astype(dt)

    def to_row(self, col):
        return jnp.sum(jnp.where(self.eye, col, 0.0), axis=0, keepdims=True)

    def to_col(self, row):
        return jnp.sum(jnp.where(self.eye, row, 0.0), axis=1, keepdims=True)

    def carry_of(self, a: int):
        """``exp(b_C)`` of block ``a``, [1, 1]."""
        return self.carry[a * BLOCK:a * BLOCK + 1, :]


def _rows(j):
    return pl.ds(pl.multiple_of(j * ROWS, ROWS), ROWS)


def _load(j, rows_refs, gate_refs):
    """Pack ``j`` of a tile: its rows of each of ``rows_refs``, its row of
    each of ``gate_refs``."""
    return ([ref[_rows(j), :] for ref in rows_refs]
            + [ref[pl.ds(j, 1), :] for ref in gate_refs])


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, *rest, packs):
    *states_ref, state = rest
    dt = q_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    def pack(j, carry):
        pk = _Pack(*_load(j, (q_ref, k_ref, v_ref), (g_ref, beta_ref)))
        s = state[...]
        us, across = [], []
        # The state walks the pack's blocks in order.
        for a in range(PACK):
            if states_ref:
                states_ref[0][j * PACK + a] = s
            s_op = s.astype(dt)
            u = (pk.u0[_block(a)] - _dot(pk.w[_block(a)], s_op, _NN)
                 ).astype(dt)
            across.append(_dot(pk.q_start[_block(a)], s_op, _NN))
            s = pk.carry_of(a) * s + _dot(pk.k_end[_block(a)], u, _TN)
            us.append(u)
        state[...] = s
        o = (jnp.concatenate(across, axis=0)
             + _dot(pk.within, jnp.concatenate(us, axis=0), _NN))
        o_ref[_rows(j), :] = o.astype(o_ref.dtype)
        return carry

    lax.fori_loop(0, packs, pack, None)


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, do_ref, states_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate, *, packs):
    dt = q_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)

    def pack(step, carry):
        j = packs - 1 - step                 # the tile's last pack first
        rows = _rows(j)
        q, k, v, do, g_row, beta_row = _load(
            j, (q_ref, k_ref, v_ref, do_ref), (g_ref, beta_ref))
        pk = _Pack(q, k, v, g_row, beta_row)
        # Each block's u again, from the state it started from.
        states = [states_ref[j * PACK + a] for a in range(PACK)]
        s_ops = [s.astype(dt) for s in states]
        u = jnp.concatenate(
            [(pk.u0[_block(a)] - _dot(pk.w[_block(a)], s_ops[a], _NN)
              ).astype(dt) for a in range(PACK)], axis=0)
        # o = q_start s + within u;  s' = carry s + k_end^T u;
        # u = u0 - w s.  dS walks the pack's blocks from the last.
        d_within = _dot(do, u, _NT)
        du_within = _dot(pk.within, do, _TN)
        ds = dstate[...]
        parts = []
        carry_terms = jnp.zeros((1, ROWS), jnp.float32)
        col = lax.broadcasted_iota(jnp.int32, (1, ROWS), 1) // BLOCK
        for a in reversed(range(PACK)):
            blk, ds_op = _block(a), ds.astype(dt)
            du = (du_within[blk] + _dot(pk.k_end[blk], ds_op, _NN)).astype(dt)
            dw = (-_dot(du, s_ops[a], _NT)).astype(dt)
            parts.append((du, dw, _dot(u[blk], ds_op, _NT),
                          _dot(do[blk], s_ops[a], _NT)))
            # b_C is the state's in carry.
            carry_terms += jnp.where(col == a, pk.carry_of(a) * jnp.sum(
                jnp.sum(ds * states[a], axis=1, keepdims=True),
                axis=0, keepdims=True), 0.0)
            ds = (pk.carry_of(a) * ds + _dot(pk.q_start[blk], do[blk], _TN)
                  - _dot(pk.w[blk], du, _TN))
        dstate[...] = ds
        du, dw, d_k_end, d_q_start = (
            jnp.concatenate(x[::-1], axis=0) for x in zip(*parts))
        # u0 = T v;  w = T k_start.
        d_solve = _dot(du, v, _NT) + _dot(dw, pk.k_start, _NT)
        dv_ref[rows, :] = _dot(pk.solve, du, _TN).astype(dv_ref.dtype)
        d_k_start = _dot(pk.solve, dw, _TN)
        # T = (I + N)^-1 diag(beta);  N = beta_i strict_decay kk.
        dbeta_row = jnp.sum(d_solve * pk.inverse, axis=0, keepdims=True)
        dn = -_dot(_dot(pk.inverse, d_solve * pk.beta_row, _TN, _HIGHEST),
                   pk.inverse, _NT, _HIGHEST)
        dn_decay = jnp.where(pk.strict, dn, 0.0) * pk.strict_decay
        dbeta_row += pk.to_row(
            jnp.sum(dn_decay * pk.kk, axis=1, keepdims=True))
        dbeta_ref[pl.ds(j, 1), :] = dbeta_row
        dkk = (pk.beta_col * dn_decay).astype(dt)
        dqk = (d_within * pk.decay).astype(dt)
        # Both decays are exp(b_i - b_j): b_i collects its row, b_j loses
        # its column.
        d_diff = d_within * pk.qk * pk.decay + pk.beta_col * dn_decay * pk.kk
        q32, k32 = q.astype(jnp.float32), k.astype(jnp.float32)
        start = jnp.sum(d_q_start * q32 + d_k_start * k32, axis=1,
                        keepdims=True) * pk.from_start
        end = jnp.sum(d_k_end * k32, axis=1, keepdims=True) * pk.to_end
        db_col = (jnp.sum(d_diff, axis=1, keepdims=True)
                  - pk.to_col(jnp.sum(d_diff, axis=0, keepdims=True))
                  + start - end)
        # g_m is in every b_i of its block with i >= m, and in its b_C,
        # which is every row's of the block in to_end.
        dg_ref[pl.ds(j, 1), :] = (
            jnp.sum(jnp.where(pk.lower, db_col, 0.0), axis=0, keepdims=True)
            + jnp.sum(jnp.where(pk.same, end, 0.0), axis=0, keepdims=True)
            + carry_terms)
        dq_ref[rows, :] = (_dot(dqk, k, _NN)
                           + pk.from_start * d_q_start).astype(dq_ref.dtype)
        dk_ref[rows, :] = (
            _dot(dqk, q, _TN) + _dot(dkk, k, _NN) + _dot(dkk, k, _TN)
            + pk.from_start * d_k_start + pk.to_end * d_k_end
        ).astype(dk_ref.dtype)
        return carry

    lax.fori_loop(0, packs, pack, None)


def takes(x) -> bool:
    """Whether the kernels can run the recurrence over an operand ``x``
    [B, T, ...], read for its length, the mesh that executes it and the
    axes it varies over: see the module's docstring."""
    return (_packs(x.shape[1]) is not None
            and not (_interpret(x) and _vma(x)))


def _specs(packs: int, dk: int, dv: int, tile_of):
    """Block specs of a tile of q or k, of v, of the gates and of the
    block states; ``tile_of(t)`` is the tile grid step ``t`` works on."""
    def spec(*shape):
        return pl.BlockSpec(
            (None, *shape),
            lambda bh, t: (bh, tile_of(t)) + (0,) * (len(shape) - 1))

    return (spec(packs * ROWS, dk), spec(packs * ROWS, dv),
            spec(packs, ROWS), spec(packs * PACK, dk, dv))


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))


# The calls are jitted with what is static among their arguments, and
# inlined: the linear layers of a step, each traced forward, recomputed
# and backward, share one traced kernel and one lowering a kind.

@functools.partial(jax.jit, static_argnames=("packs", "save_states",
                                             "interpret"), inline=True)
def _fwd_call(q, k, v, g, beta, *, packs, save_states, interpret):
    (bh, t, dk), dv = q.shape, v.shape[-1]
    vma = _vma(q, k, v, g, beta)
    qk, vo, gates, states = _specs(packs, dk, dv, lambda t_i: t_i)
    out_shape = [jax.ShapeDtypeStruct((bh, t, dv), q.dtype, vma=vma)]
    out_specs = [vo]
    if save_states:
        out_shape.append(jax.ShapeDtypeStruct(
            (bh, t // BLOCK, dk, dv), jnp.float32, vma=vma))
        out_specs.append(states)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, packs=packs),
        out_shape=out_shape,
        grid=(bh, t // (packs * ROWS)),
        in_specs=[qk, qk, vo, gates, gates],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret, name=scopes.GDN_SCAN_FWD,
        compiler_params=_COMPILER_PARAMS,
    )(q, k, v, g, beta)


@functools.partial(jax.jit, static_argnames=("packs", "interpret"),
                   inline=True)
def _bwd_call(q, k, v, g, beta, do, states, *, packs, interpret):
    (bh, t, dk), dv = q.shape, v.shape[-1]
    vma = _vma(q, k, v, g, beta, do, states)
    last = t // (packs * ROWS) - 1
    qk, vo, gates, saved = _specs(packs, dk, dv, lambda t_i: last - t_i)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, packs=packs),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma)
                   for x in (q, k, v, g, beta)],
        grid=(bh, last + 1),
        in_specs=[qk, qk, vo, gates, gates, vo, saved],
        out_specs=[qk, qk, vo, gates, gates],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret, name=scopes.GDN_SCAN_BWD,
        compiler_params=_COMPILER_PARAMS,
    )(q, k, v, g, beta, do, states)


def head_major(x):
    """[B, T, H, ...] -> [B * H, T, ...]."""
    x = jnp.moveaxis(x, 2, 1)
    return x.reshape((-1,) + x.shape[2:])


def _gates(x):
    """[B, T, H] -> [B * H, T / ROWS, ROWS] float32: a pack's gates are
    one row along the lanes."""
    x = head_major(x.astype(jnp.float32))
    return x.reshape(x.shape[0], -1, ROWS)


def token_major(x, batch: int):
    """[B * H, T, ...] -> [B, T, H, ...]."""
    return jnp.moveaxis(x.reshape((batch, -1) + x.shape[1:]), 1, 2)


def _forward(q, k, v, g, beta, save_states: bool):
    o, *states = _fwd_call(q, k, v, g, beta, packs=tiles(q.shape[1]),
                           save_states=save_states, interpret=_interpret(q))
    return o, (q, k, v, g, beta) + tuple(states)


@jax.custom_vjp
def _recurrence(q, k, v, g, beta):
    """The kernels on their own operands: ``q``, ``k``, ``v`` head-major
    and the gates a pack a row (:func:`_gates`)."""
    return _forward(q, k, v, g, beta, save_states=False)[0]


def _recurrence_fwd(q, k, v, g, beta):
    return _forward(q, k, v, g, beta, save_states=True)


def _recurrence_bwd(residuals, do):
    return tuple(_bwd_call(*residuals[:5], do, residuals[5],
                           packs=tiles(do.shape[1]),
                           interpret=_interpret(do)))


_recurrence.defvjp(_recurrence_fwd, _recurrence_bwd)


def gated_delta_rule_head_major(q, k, v, g, beta):
    """The recurrence from ``S_0 = 0`` on head-major operands, the
    kernels' own layout and what :mod:`horovod_tpu.ops.short_conv`
    writes: ``q``, ``k`` [B * H, T, d_k] (normalised, ``q`` scaled),
    ``v`` [B * H, T, d_v] in the model dtype, ``g`` (``log alpha <= 0``)
    and ``beta`` [B, T, H] float32 -> ``o`` [B * H, T, d_v] in the model
    dtype.  ``T`` is one that :func:`takes` accepts.  Differentiable in
    all five."""
    t = q.shape[1]
    if _packs(t) is None:
        raise ValueError(
            f"gated delta rule: a sequence of {t} tokens does not cut into "
            f"tiles of whole blocks of {BLOCK} (takes())")
    if t % ROWS:
        # A last pack that is not whole is filled with tokens that leave
        # the state as it is (beta = 0, alpha = 1).
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, -t % ROWS)) + ((0, 0),) * (x.ndim - 2))
            for x in (q, k, v, g, beta))
    return _recurrence(q, k, v, _gates(g), _gates(beta))[:, :t]


def gated_delta_rule(q, k, v, g, beta):
    """:func:`gated_delta_rule_head_major` on token-major operands:
    ``q``, ``k`` [B, T, H, d_k], ``v`` [B, T, H, d_v] -> ``o`` [B, T, H,
    d_v]."""
    o = gated_delta_rule_head_major(head_major(q), head_major(k),
                                    head_major(v), g, beta)
    return token_major(o, q.shape[0])
