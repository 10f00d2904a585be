"""The Mamba-2 (SSD) recurrence of a state-space layer as Pallas TPU
kernels: the chunked form of :mod:`horovod_tpu.models.mamba2` with a
group's head states kept in VMEM over all of its chunks.

Per head of a group (``R`` heads of ``P`` channels that share ``B`` and
``C``, a state of ``N`` a channel), with ``b_i`` the running sum of
``log a`` from the start of a chunk of ``L`` tokens and ``S_0`` the state
the chunk starts from (the mathematics is the Mamba-2 module's
docstring)::

    y_i   = sum_{j <= i} exp(b_i - b_j) (C_i . B_j) delta_j x_j
            + exp(b_i) S_0 C_i + D x_i
    S_end = exp(b_L) S_0 + sum_j exp(b_L - b_j) delta_j x_j B_j^T

**Grid.**  ``(batch, groups, T / tile)``: the first two ``parallel``, the
last ``arbitrary`` and walked in order (the backward kernel walks it in
reverse).  A grid step holds a tile of up to :data:`TILE_CHUNKS` chunks
of a group's operands as the convolution wrote them, token-major: ``x``
``[tile, R P]`` (a column slab of ``[B, T, H P]``), ``B`` and ``C``
``[tile, N]`` (of ``[B, T, G N]``), and the gates ``delta`` and ``log a``
``[R, tile]``, tokens along the lanes (of ``[B, G, R, T]``: the one
relayout).  Inside a grid step a loop walks the tile's chunks.

**A chunk** is four kinds of product.  ``C B^T`` ``[L, L]`` is one for
the group's ``R`` heads; the carried states' part of the output, ``C
[L, N] @ S [N, R P]``, and the chunk's own state, ``B^T [N, L] @
(exp(b_L - b) delta x) [L, R P]``, are one wide product each; only the
masked product ``(C B^T * decay_r) @ (delta x)_r`` is a head's own.  What
a head multiplies a row of ``x`` or ``y`` by (``delta``, ``exp(b)``,
``exp(b_L - b)``) is made ``[R, L]``, two vector registers, and spread
over the head's ``P`` lanes by the MXU: a 0/1 matrix times the value's
three bfloat16 parts, which is exact.  The sums over a head's ``P`` lanes
that the gates' gradients are go the same way back.

**What stays in VMEM.**  The group's ``[N, R P]`` float32 state (``dS``
in the backward kernel) lives in a scratch for the whole walk.  Of a
chunk, the running sums, the ``R`` decay masks (masked before the
``exp``), ``C B^T``, the masked and decayed scores, ``delta x`` and
``exp(b_L - b) delta x`` exist in VMEM and registers only: ``x``, ``B``,
``C``, ``delta`` and ``log a`` are read once and ``y`` is written once,
the ``D x`` skip in it.

**Precision.**  That of the Mamba-2 module: ``delta``, ``log a``, its
sums, every decay and the carried state are float32; every product takes
operands in the model dtype (``x``'s) and accumulates in float32; ``y``
leaves float32.  Nothing is rounded that the ``jax.numpy`` form does not
round.

**Backward.**  The forward kernel that runs under differentiation also
writes the float32 state at each chunk's start (``[B, G, T / L, N, R
P]``: :func:`horovod_tpu.models.mamba2.saved_state_bytes`); the primal
call does not.  The backward kernel walks the chunks from the last to the
first with ``dS`` in VMEM, recomputes a chunk's intermediates from its
inputs and its saved state, and writes ``dx``, ``dB``, ``dC`` (model
dtype; ``dB`` and ``dC`` summed over the group's heads), ``d delta`` and
``d log a`` (float32, ``[R, tile]``).  It reads ``y`` again, which the
layer's backward keeps anyway: the gradient of ``b_i`` through ``y_i`` is
``sum_p dy_ip (y_ip - D x_ip)``, of ``b_j`` through everything that
decays from token ``j`` minus the sum over ``p`` of each operand ``delta
x`` is times its gradient, and of ``b_L`` the product of ``dS`` with the
state the chunk ends in, so no ``[L, L]`` matrix is summed along a row.
The gradient of ``log a`` is what is left when these cancel, so each is
made of the products the other is, of operands rounded as the matmuls
see them.  ``dD`` is ``jax.numpy`` outside.

**Where it runs.**  Compiled by Mosaic where the executing mesh is TPU,
in the Pallas interpreter (the same code) elsewhere:
``topology.exec_on_tpu``.  :func:`takes` says whether the kernels can run
on a layer's operands.  The sizes are :func:`tiles`'s to refuse: the
chunk has to be whole lanes, the length whole chunks, ``R P`` and ``N``
whole lanes, ``R`` whole sublanes and at most ``L``, and a grid step has
to fit the VMEM a kernel may use, which bounds ``L (R P)``
(:func:`vmem_bytes`: a wide group is given a tile of fewer chunks before
it is refused; the published Mamba-2 models' one group of 80 heads of 64
channels in chunks of 256 is refused).  And the interpreter cannot run
them inside ``shard_map(check_vma=True)`` (its loop over a tile's chunks
carries the scratch, which it makes unvarying, beside the operands, which
vary over the batch axes).  The caller runs the ``jax.numpy`` form where
they cannot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.gated_delta_rule import _HIGHEST, _NN, _NT, _TN, _dot
from horovod_tpu.ops.grouped_matmul import _interpret, _vma
from horovod_tpu.telemetry import scopes

LANES, SUBLANES = 128, 8
# Chunks a grid step holds at most (docs/kernels.md, "Mamba-2 scan": the
# sweep on the chip).
TILE_CHUNKS = 2
# What a kernel may use of a v5e's 128 MiB of VMEM (the compiler's default
# allowance is 16 MiB).  At Nemotron's widths the backward kernel takes 14
# MiB of it; what a group's width may be is set by it (:func:`vmem_bytes`).
VMEM_LIMIT = 64 * 2 ** 20
# Parts a float32 value is cut into on its way through the MXU against a
# 0/1 matrix: three bfloat16 parts hold its 24 bits.
PARTS = 3

_F32, _BF16 = jnp.float32, jnp.bfloat16


def vmem_bytes(chunks: int, chunk: int, heads: int, width: int,
               state: int) -> int:
    """VMEM the backward kernel, the larger of the two, takes for a grid
    step of ``chunks`` chunks of ``chunk`` tokens of a group of ``heads``
    heads, ``width`` channels and a state of ``state``: an estimate from
    above, every element counted as four bytes.  Twice (the pipeline's
    two buffers) a tile of ``x``, ``y``, ``dy``, ``dx`` [L, R P], the
    saved state [N, R P], ``B``, ``C`` and their gradients [L, N] and the
    gates and theirs [R, L]; the ``dS`` scratch; and of the chunk at
    hand the nine [L, R P] and three [N, R P] float32 values Mosaic holds
    at once (read off what it asked for at seven sizes: docs/kernels.md,
    "Mamba-2 scan")."""
    rows, wide = chunk * width, state * width
    tile = chunks * (4 * rows + wide + 4 * chunk * state + 4 * heads * chunk)
    return 4 * (2 * tile + 9 * rows + 4 * wide)


def tiles(t: int, chunk: int, heads: int, head_dim: int, state: int):
    """Chunks a grid step holds for ``t`` tokens in chunks of ``chunk`` of
    a group of ``heads`` heads of ``head_dim`` channels and a state of
    ``state``: the largest divisor of their number up to
    :data:`TILE_CHUNKS` that :data:`VMEM_LIMIT` holds.  None where the
    kernels cannot run these sizes: the chunk has to be whole lanes, the
    length whole chunks, a group's channels and the state whole lanes,
    its heads whole sublanes and no more than a chunk's tokens (the
    running sums are transposed as a [L, L] matrix a head a column), and
    a grid step of one chunk has to fit."""
    width = heads * head_dim
    if (chunk % LANES or t % chunk or width % LANES or state % LANES
            or heads % SUBLANES or heads > chunk):
        return None
    n = t // chunk
    return next((c for c in range(min(n, TILE_CHUNKS), 0, -1)
                 if n % c == 0 and vmem_bytes(c, chunk, heads, width, state)
                 <= VMEM_LIMIT), None)


def takes(x, chunk: int, heads: int, head_dim: int, state: int) -> bool:
    """Whether the kernels can run the recurrence of a layer whose groups
    have ``heads`` heads of ``head_dim`` channels and a state of ``state``
    in chunks of ``chunk`` over an operand ``x`` [B, T, ...], read for
    its length, the mesh that executes it and the axes it varies over:
    sizes :func:`tiles` has an answer for, and not the interpreter inside
    ``shard_map(check_vma=True)`` (the module's docstring)."""
    return (tiles(x.shape[1], chunk, heads, head_dim, state) is not None
            and not (_interpret(x) and _vma(x)))


def _parts(v):
    """The float32 ``v`` as :data:`PARTS` bfloat16 values that add up to
    it."""
    out = []
    for _ in range(PARTS):
        out.append(v.astype(_BF16))
        v = v - out[-1].astype(_F32)
    return out


class _Group:
    """What does not change over a grid step: the masks of a chunk of
    ``L`` tokens and the 0/1 matrices that take a value a head and token
    to the head's ``P`` lanes and back."""

    def __init__(self, heads: int, width: int, chunk: int):
        self.heads, self.p = heads, width // heads
        ii = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        jj = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        self.lower = ii >= jj
        # Running sums along the lanes, and their transpose's.
        self.sum_to = jnp.where(ii <= jj, 1.0, 0.0)
        self.sum_from = jnp.where(self.lower, 1.0, 0.0)
        head = lax.broadcasted_iota(jnp.int32, (heads, width), 0)
        lane = lax.broadcasted_iota(jnp.int32, (heads, width), 1)
        self.own = jnp.where(lane // self.p == head, 1.0, 0.0).astype(_BF16)
        self.own_parts = jnp.concatenate([self.own] * PARTS, axis=0)
        self.last = lax.broadcasted_iota(
            jnp.int32, (heads, chunk), 1) == chunk - 1

    def running_sums(self, rows):
        """``rows`` [R, L] float32 summed along the lanes up to each."""
        return _dot(rows, self.sum_to, _NN, _HIGHEST)

    def spread(self, rows):
        """``rows`` [R, L] float32 -> [L, R P]: a head's value of a token
        on each of the head's lanes of the token's row."""
        return _dot(jnp.concatenate(_parts(rows), axis=0), self.own_parts,
                    _TN)

    def gather(self, z):
        """``z`` [L, R P] float32 -> [R, L]: the sum over a head's
        lanes."""
        return sum(_dot(self.own, part, _NT) for part in _parts(z))

    def head(self, v, r: int):
        """Head ``r``'s lanes of ``v`` [L, R P]."""
        return v[:, r * self.p:(r + 1) * self.p]


class _Chunk:
    """What both kernels compute of a chunk before it meets the state,
    from ``x`` [L, R P], ``b_in``, ``c_in`` [L, N] (model dtype) and the
    gates ``delta``, ``log_a`` [R, L] (float32)."""

    def __init__(self, grp, x, b_in, c_in, delta, log_a):
        dt = x.dtype
        chunk = x.shape[0]
        self.grp = grp
        self.b = grp.running_sums(log_a)                     # [R, L]
        # b_i down the sublanes, a head a lane: the decays' other side.
        pad = jnp.zeros((chunk - grp.heads, chunk), _F32)
        self.b_col = (jnp.concatenate([self.b, pad], axis=0)
                      if grp.heads < chunk else self.b).T
        total = self.b[:, chunk - 1:]                        # b_L [R, 1]
        self.delta_e = grp.spread(delta)
        self.from_start_e = grp.spread(jnp.exp(self.b))      # exp(b_i)
        self.to_end_e = grp.spread(jnp.exp(total - self.b))  # exp(b_L - b)
        self.carry_e = self.from_start_e[chunk - 1:]         # exp(b_L)
        self.x32 = x.astype(_F32)
        xd = self.delta_e * self.x32
        self.xd = xd.astype(dt)
        self.x_end = (self.to_end_e * xd).astype(dt)
        self.scores = _dot(c_in, b_in, _NT)                  # [L, L]

    def within(self):
        """The chunk's own part of ``y`` [L, R P]: a masked product a
        head."""
        grp = self.grp
        return jnp.concatenate(
            [_dot((self.scores * self.decay(r)).astype(self.xd.dtype),
                  grp.head(self.xd, r), _NN) for r in range(grp.heads)],
            axis=1)

    def decay(self, r: int):
        """``exp(b_i - b_j)`` of head ``r`` for ``j <= i``, else 0 (masked
        before the exp: above the diagonal the difference is positive
        and may overflow)."""
        diff = self.b_col[:, r:r + 1] - self.b[r:r + 1, :]
        return jnp.where(self.grp.lower,
                         jnp.exp(jnp.where(self.grp.lower, diff, 0.0)), 0.0)


def _rows(j, chunk: int):
    return pl.ds(pl.multiple_of(j * chunk, chunk), chunk)


def _load(j, chunk, rows_refs, gate_refs):
    """Chunk ``j`` of a tile: its rows of each of ``rows_refs``, its lanes
    of each of ``gate_refs``."""
    rows = _rows(j, chunk)
    return ([ref[rows, :] for ref in rows_refs]
            + [ref[:, rows] for ref in gate_refs])


def _fwd_kernel(x_ref, b_ref, c_ref, delta_ref, log_a_ref, d_ref, y_ref,
                *rest, chunk, chunks):
    *states_ref, state = rest
    dt = x_ref.dtype
    grp = _Group(delta_ref.shape[0], x_ref.shape[1], chunk)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    def one(j, carry):
        x, b_in, c_in, delta, log_a = _load(
            j, chunk, (x_ref, b_ref, c_ref), (delta_ref, log_a_ref))
        ck = _Chunk(grp, x, b_in, c_in, delta, log_a)
        s = state[...]
        if states_ref:
            states_ref[0][j] = s
        y_ref[_rows(j, chunk), :] = (
            ck.within() + ck.from_start_e * _dot(c_in, s.astype(dt), _NN)
            + d_ref[...] * ck.x32)
        state[...] = ck.carry_e * s + _dot(b_in, ck.x_end, _TN)
        return carry

    lax.fori_loop(0, chunks, one, None)


def _bwd_kernel(x_ref, b_ref, c_ref, delta_ref, log_a_ref, d_ref, y_ref,
                dy_ref, states_ref, dx_ref, db_ref, dc_ref, ddelta_ref,
                dlog_a_ref, dstate, dtotal, *, chunk, chunks):
    dt = x_ref.dtype
    grp = _Group(delta_ref.shape[0], x_ref.shape[1], chunk)

    @pl.when(pl.program_id(2) == 0)
    def _start():
        dstate[...] = jnp.zeros_like(dstate)
        dtotal[...] = jnp.zeros_like(dtotal)

    def one(step, carry):
        j = chunks - 1 - step                # the tile's last chunk first
        rows = _rows(j, chunk)
        x, b_in, c_in, y, dy, delta, log_a = _load(
            j, chunk, (x_ref, b_ref, c_ref, y_ref, dy_ref),
            (delta_ref, log_a_ref))
        ck = _Chunk(grp, x, b_in, c_in, delta, log_a)
        s, ds = states_ref[j], dstate[...]
        s_op, ds_op, dy_op = s.astype(dt), ds.astype(dt), dy.astype(dt)
        # y = within + from_start (C s) + D x;  s' = carry s + B^T x_end.
        d_across = (ck.from_start_e * dy).astype(dt)
        dc = _dot(d_across, s_op, _NT)
        db = _dot(ck.x_end, ds_op, _NT)
        ds_start = ck.carry_e * ds + _dot(c_in, d_across, _TN)
        dxd, dscores = [], jnp.zeros_like(ck.scores)
        for r in range(grp.heads):
            decay = ck.decay(r)
            dy_r = grp.head(dy_op, r)
            dxd.append(_dot((ck.scores * decay).astype(dt), dy_r, _TN))
            dscores += _dot(dy_r, grp.head(ck.xd, r), _NT) * decay
        dscores = dscores.astype(dt)
        dc_ref[rows, :] = (dc + _dot(dscores, b_in, _NN)).astype(dc_ref.dtype)
        db_ref[rows, :] = (db + _dot(dscores, c_in, _TN)).astype(db_ref.dtype)
        # delta x is an operand twice: of its head's masked product and,
        # decayed to the chunk's end, of the state.
        dxd_own = jnp.concatenate(dxd, axis=1)
        dx_end = _dot(b_in, ds_op, _NN)
        dxd = dxd_own + ck.to_end_e * dx_end
        skip = d_ref[...]
        dx_ref[rows, :] = (ck.delta_e * dxd + skip * dy).astype(dx_ref.dtype)
        ddelta_ref[:, rows] = grp.gather(ck.x32 * dxd)
        # b_i is in y_i's two decayed parts, b_j in everything that decays
        # from token j (with the other sign), b_L in all of the end state.
        # What the running sum below adds up is what is left of sums that
        # cancel: both sides are made of the same products of the same
        # rounded operands, so they cancel as the unrounded ones would.
        db_sums = grp.gather(
            dy_op.astype(_F32) * (y - skip * ck.x32)
            - ck.xd.astype(_F32) * dxd_own
            - ck.x_end.astype(_F32) * dx_end)
        db_sums += jnp.where(grp.last, dtotal[:, :1], 0.0)
        dlog_a_ref[:, rows] = _dot(db_sums, grp.sum_from, _NN, _HIGHEST)
        dstate[...] = ds_start
        # The chunk before ends in s: its b_L's gradient, a head's sum of
        # one row (gathered as a register's eight).
        ends = jnp.sum(ds_start.astype(dt).astype(_F32) * s, axis=0,
                       keepdims=True)
        dtotal[...] = jnp.broadcast_to(
            grp.gather(jnp.broadcast_to(ends, (SUBLANES, ends.shape[1])))
            [:, :1], dtotal.shape)
        return carry

    lax.fori_loop(0, chunks, one, None)


def _specs(chunk: int, chunks: int, heads: int, width: int, n: int, tile_of):
    """Block specs of a tile of x, of B or C, of the gates, of the skip's
    weights and of the chunk states; ``tile_of(t)`` is the tile grid step
    ``t`` works on."""
    tile = chunk * chunks
    return (pl.BlockSpec((None, tile, width),
                         lambda b, g, t: (b, tile_of(t), g)),
            pl.BlockSpec((None, tile, n), lambda b, g, t: (b, tile_of(t), g)),
            pl.BlockSpec((None, None, heads, tile),
                         lambda b, g, t: (b, g, 0, tile_of(t))),
            pl.BlockSpec((None, 1, width), lambda b, g, t: (g, 0, 0)),
            pl.BlockSpec((None, None, chunks, n, width),
                         lambda b, g, t: (b, g, tile_of(t), 0, 0)))


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT)


# The calls are jitted with what is static among their arguments, and
# inlined: the state-space layers of a step, each traced forward,
# recomputed and backward, share one traced kernel and one lowering a
# kind.

@functools.partial(jax.jit, static_argnames=("chunk", "save_states",
                                             "interpret"), inline=True)
def _fwd_call(x, b_in, c_in, delta, log_a, d, *, chunk, save_states,
              interpret):
    (bsz, t, _), (groups, heads) = x.shape, delta.shape[1:3]
    width, n = x.shape[2] // groups, b_in.shape[2] // groups
    chunks = tiles(t, chunk, heads, width // heads, n)
    vma = _vma(x, b_in, c_in, delta, log_a, d)
    rows, bc, gates, skip, states = _specs(chunk, chunks, heads, width, n,
                                           lambda t_i: t_i)
    out_shape = [jax.ShapeDtypeStruct(x.shape, _F32, vma=vma)]
    out_specs = [rows]
    if save_states:
        out_shape.append(jax.ShapeDtypeStruct(
            (bsz, groups, t // chunk, n, width), _F32, vma=vma))
        out_specs.append(states)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, chunks=chunks),
        out_shape=out_shape,
        grid=(bsz, groups, t // (chunk * chunks)),
        in_specs=[rows, bc, bc, gates, gates, skip],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((n, width), _F32)],
        interpret=interpret, name=scopes.SSM_SCAN_FWD,
        compiler_params=_COMPILER_PARAMS,
    )(x, b_in, c_in, delta, log_a, d)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"),
                   inline=True)
def _bwd_call(x, b_in, c_in, delta, log_a, d, y, dy, states, *, chunk,
              interpret):
    (bsz, t, _), (groups, heads) = x.shape, delta.shape[1:3]
    width, n = x.shape[2] // groups, b_in.shape[2] // groups
    chunks = tiles(t, chunk, heads, width // heads, n)
    vma = _vma(x, b_in, c_in, delta, log_a, d, y, dy, states)
    last = t // (chunk * chunks) - 1
    rows, bc, gates, skip, saved = _specs(chunk, chunks, heads, width, n,
                                          lambda t_i: last - t_i)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, chunks=chunks),
        out_shape=[jax.ShapeDtypeStruct(v.shape, v.dtype, vma=vma)
                   for v in (x, b_in, c_in, delta, log_a)],
        grid=(bsz, groups, last + 1),
        in_specs=[rows, bc, bc, gates, gates, skip, rows, rows, saved],
        out_specs=[rows, bc, bc, gates, gates],
        scratch_shapes=[pltpu.VMEM((n, width), _F32),
                        pltpu.VMEM((heads, LANES), _F32)],
        interpret=interpret, name=scopes.SSM_SCAN_BWD,
        compiler_params=_COMPILER_PARAMS,
    )(x, b_in, c_in, delta, log_a, d, y, dy, states)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _recurrence(x, b_in, c_in, delta, log_a, d, chunk):
    return _fwd_call(x, b_in, c_in, delta, log_a, d, chunk=chunk,
                     save_states=False, interpret=_interpret(x))[0]


def _recurrence_fwd(x, b_in, c_in, delta, log_a, d, chunk):
    operands = (x, b_in, c_in, delta, log_a, d)
    y, states = _fwd_call(*operands, chunk=chunk, save_states=True,
                          interpret=_interpret(x))
    return y, operands + (y, states)


def _recurrence_bwd(chunk, residuals, dy):
    x, d = residuals[0], residuals[5]
    grads = _bwd_call(*residuals[:7], dy, residuals[7], chunk=chunk,
                      interpret=_interpret(dy))
    dd = jnp.sum(dy * x.astype(_F32), axis=(0, 1)).reshape(d.shape)
    return (*grads, dd)


_recurrence.defvjp(_recurrence_fwd, _recurrence_bwd)


def mamba2_scan(x, b_in, c_in, delta, log_a, d, chunk: int, groups: int):
    """The recurrence from ``S_0 = 0`` with the ``D x`` skip, on the
    token-major arrays the convolution writes: ``x`` [B, T, H P], ``b_in``,
    ``c_in`` [B, T, G N] in the model dtype (head ``h`` reads group ``h //
    (H / G)``), ``delta`` and ``log_a`` (``<= 0``) [B, T, H] float32, ``d``
    [H] float32 -> ``y`` [B, T, H P] float32.  Sizes are ones that
    :func:`takes` accepts.  Differentiable in all six."""
    (bsz, t, width), h = x.shape, delta.shape[2]
    sizes = (t, chunk, h // groups, width // h, b_in.shape[2] // groups)
    if tiles(*sizes) is None:
        raise ValueError(
            "mamba2 scan: the kernels do not take (tokens, chunk, a "
            f"group's heads, a head's channels, state) = {sizes}: "
            "tiles(), takes()")

    def gates(v):      # [B, T, H] -> [B, G, R, T]: tokens along the lanes
        return jnp.moveaxis(v.astype(_F32), 1, 2).reshape(
            bsz, groups, h // groups, t)

    return _recurrence(
        x, b_in, c_in, gates(delta), gates(log_a),
        jnp.repeat(d.astype(_F32), width // h).reshape(groups, 1, -1), chunk)
