"""The Mamba-1 selective scan of a state-space layer as Pallas TPU kernels:
a recurrence whose decay is its own for every channel **and** every state
index, so that nothing of it is a matrix product.

Per channel ``c`` of ``C`` and state index ``n`` of ``N`` (the mathematics
is :mod:`horovod_tpu.models.mamba1`'s docstring; ``A < 0``)::

    delta_t[c] = softplus(dt_t[c])
    h_t[n, c] = exp(delta_t[c] A[n, c]) h_{t-1}[n, c]
                + delta_t[c] x_t[c] B_t[n]                       h_0 = 0
    y_t[c]    = sum_n C_t[n] h_t[n, c] + D[c] x_t[c]

**The layout is the design.**  The work is elementwise on ``[N, C]`` a
token and sequential in the tokens, so a token must fill whole vector
registers by itself.  Token-major rows ``[T, C]`` put a token on one
sublane (an eighth of each register).  Here a token's channels fill both
dimensions of a register: a slab of :data:`SLAB` = 8 x 128 channels is one
``[8, 128]`` register, the wrapper hands the kernels ``x``, ``dt`` and
``y`` as ``[B, T, C / 128, 128]`` (a relayout that XLA fuses into the
producer's write and the consumer's read where it can: the step's bias,
the gate), the state of a slab is ``N`` such
registers, one a state index, and ``A`` as many.  ``B_t[n]`` and
``C_t[n]`` are then neither rows nor columns but **scalars**: they come
through SMEM (``[B, T N]`` float32, a tile's at a time) and meet a
register as a splat.  No value is broadcast along sublanes or lanes, the
sum over ``n`` is ``N`` multiply-adds of whole registers and no reduction
inside a register, and a state-element update is six vector operations
and one ``exp``.

**Grid.**  ``(batch, C / SLAB, T / tile)``: the first two ``parallel``,
the last ``arbitrary`` and walked in order (the backward kernel walks it
in reverse).  A grid step holds a tile of ``tile`` tokens of one slab
(:func:`tiles`); inside it a loop walks the tokens with the slab's state
``[N, 8, 128]`` float32 in registers, kept in a VMEM scratch from one
tile to the next.

**Precision.**  Everything is float32: ``softplus`` (taken inside, so
that the backward keeps the pre-activation alone), ``delta``, the decay
``exp(delta A)`` (of a product ``<= 0``: at most 1, nothing is divided, a
large ``delta`` underflows to an exact 0), the state, the sum over
``n``; ``y`` leaves float32.  ``x`` is read in the model dtype and cast a
register at a time; its cotangent leaves in it.

**Backward.**  The forward kernel that runs under differentiation also
writes the float32 state at each tile's start (``[B, T / tile, N, C]``:
:func:`horovod_tpu.models.mamba1.saved_state_bytes`); the primal call
does not.  The backward
kernel walks the tiles from the last to the first: it recomputes a tile's
states from the saved one into a VMEM scratch (``tile + 1`` states), then
walks the tile's tokens backward with the state's cotangent in registers
and writes ``dx``, ``d dt`` (the slab's layout), the float32 sums
``dA`` ``[N, C]`` and ``dD`` ``[C]`` (output blocks that stay in VMEM over
a slab's walk, a batch row each; the wrapper adds the rows), and ``dB``,
``dC``.  Those two are sums over a slab's channels, a whole register
each: the kernel adds a register's sublanes a token and state index
(``[tile, N, 128]`` in a VMEM scratch), its lanes once a tile, and writes
``[B, C / SLAB, T, N]``; the wrapper adds the slabs.

**Where it runs.**  Compiled by Mosaic where the executing mesh is TPU,
in the Pallas interpreter (the same code) elsewhere:
``topology.exec_on_tpu``.  :func:`takes` says whether the kernels can run
on a layer's operands (:func:`tiles`: the channels whole slabs, the
length whole tiles of at least a register's sublanes, a grid step inside
the VMEM a kernel may use); and the interpreter cannot run them inside
``shard_map(check_vma=True)`` (its loop over a tile's tokens carries the
scratch, which it makes unvarying, beside operands that vary over the
batch axes).  The caller runs the ``jax.numpy`` form where they cannot
(:func:`horovod_tpu.models.mamba1.scan_xla`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from horovod_tpu.ops.grouped_matmul import _interpret, _vma
from horovod_tpu.telemetry import scopes

LANES, SUBLANES = 128, 8
# Channels of a grid step: one vector register a token.
SLAB = SUBLANES * LANES
# Tokens a grid step holds at most (docs/kernels.md, "Selective scan").
TILE = 256
# What a kernel may use of a v5e's 128 MiB of VMEM (the compiler's default
# allowance is 16 MiB).
VMEM_LIMIT = 64 * 2 ** 20

_F32 = jnp.float32
_LN2 = math.log(2.0)


def vmem_bytes(tile: int, state: int) -> int:
    """VMEM the backward kernel, the larger of the two, takes for a grid
    step of ``tile`` tokens of a slab under a state of ``state``: twice
    (the pipeline's two buffers) a tile of ``x``, ``dt``, ``dy``, ``dx``
    and ``d dt`` [tile, 8, 128] (each counted float32), of ``dB`` and
    ``dC`` [tile, N] (a register's lanes each row), the saved state,
    ``A`` and ``dA`` [N, 8, 128]; the recomputed states [tile + 1, N, 8,
    128]; the two lane sums [tile, N, 128]; and the cotangent carried
    across tiles."""
    reg = SLAB * 4
    rows = -(-state // SUBLANES) * LANES * 4
    return (2 * (5 * tile * reg + 2 * tile * LANES * 4 + 3 * state * reg)
            + (tile + 1) * state * reg + 2 * tile * rows * SUBLANES
            + state * reg)


def tiles(t: int, channels: int, state: int):
    """Tokens a grid step holds for ``t`` tokens of ``channels`` channels
    under a state of ``state``: the largest divisor of ``t`` up to
    :data:`TILE` that is whole sublanes and that :data:`VMEM_LIMIT`
    holds.  None where the kernels cannot run these sizes: the channels
    have to be whole slabs of :data:`SLAB`, and there has to be such a
    divisor."""
    if channels % SLAB or channels <= 0 or state <= 0 or t <= 0:
        return None
    return next((tile for tile in range(min(t, TILE), 0, -1)
                 if t % tile == 0 and tile % SUBLANES == 0
                 and vmem_bytes(tile, state) <= VMEM_LIMIT), None)


def takes(x, channels: int, state: int) -> bool:
    """Whether the kernels can run the scan of a layer of ``channels``
    channels and a state of ``state`` over an operand ``x`` [B, T, ...],
    read for its length, the mesh that executes it and the axes it varies
    over: sizes :func:`tiles` has an answer for, and not the interpreter
    inside ``shard_map(check_vma=True)`` (the module's docstring)."""
    return (tiles(x.shape[1], channels, state) is not None
            and not (_interpret(x) and _vma(x)))


def _step(h, decay, dx, b_ref, t, n: int):
    """``h_t`` of every state index from ``h_{t-1}``: ``n`` registers."""
    return [decay[i] * h[i] + dx * b_ref[t * n + i] for i in range(n)]


def _softplus(v):
    """``log(1 + exp(v))`` without overflow: ``max(v, 0) + log1p(exp(-|v|))``."""
    return jnp.maximum(v, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(v)))


def _decay(delta, a2, n: int):
    """``exp(delta A)`` of every state index from ``a2 = A log2(e)``: the
    unit's exponential is a power of two, so the scale goes into ``A``
    once and not into every product."""
    return [jnp.exp2(delta * a2[i]) for i in range(n)]


def _fwd_kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, y_ref, *rest,
                tile: int, n: int):
    *saved_ref, state = rest

    @pl.when(pl.program_id(2) == 0)
    def _start():
        state[...] = jnp.zeros_like(state)

    if saved_ref:
        saved_ref[0][...] = state[...]
    a = [a_ref[i] for i in range(n)]
    skip = d_ref[...]

    def one(t, h):
        delta, x = _softplus(dt_ref[t]), x_ref[t].astype(_F32)
        h = _step(h, _decay(delta, a, n), delta * x, b_ref, t, n)
        y = skip * x
        for i in range(n):
            y = y + h[i] * c_ref[t * n + i]
        y_ref[t] = y
        return tuple(h)

    h = lax.fori_loop(0, tile, one, tuple(state[i] for i in range(n)))
    for i in range(n):
        state[i] = h[i]


def _bwd_kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, dy_ref,
                saved_ref, dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dd_ref,
                states, carried, db_lanes, dc_lanes, *, tile: int, n: int):
    @pl.when(pl.program_id(2) == 0)
    def _start():
        carried[...] = jnp.zeros_like(carried)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    a = [a_ref[i] for i in range(n)]
    skip = d_ref[...]

    # states[t + 1] = h_t; states[0] the state the tile starts from.
    states[0] = saved_ref[...]

    def again(t, h):
        delta = _softplus(dt_ref[t])
        h = _step(h, _decay(delta, a, n), delta * x_ref[t].astype(_F32),
                  b_ref, t, n)
        for i in range(n):
            states[t + 1, i] = h[i]
        return tuple(h)

    # (Read back from the scratch: inside shard_map a value read from an
    # operand outside the loop is typed as varying, one read inside it is
    # not, and a loop's carry has to keep its type.)
    lax.fori_loop(0, tile, again, tuple(states[0, i] for i in range(n)))

    def back(s, g):
        """``g``: what the tokens after ``t`` send to ``h_t``."""
        t = tile - 1 - s
        delta, x, dy = _softplus(dt_ref[t]), x_ref[t].astype(_F32), dy_ref[t]
        dx = delta * x
        decay = _decay(delta, a, n)
        through_b = jnp.zeros_like(x)       # sum_n dh_t[n] B_t[n]
        through_a = jnp.zeros_like(x)       # sum_n d(delta A)[n] A[n]
        out, to_b, to_c = [], [], []
        for i in range(n):
            dh = dy * c_ref[t * n + i] + g[i]
            to_c.append(dy * states[t + 1, i])
            to_b.append(dh * dx)
            through_b = through_b + dh * b_ref[t * n + i]
            # d(delta_t A): the decay's cotangent times the decay.
            e = dh * states[t, i] * decay[i]
            through_a = through_a + e * a[i]       # a: A log2(e)
            da_ref[i] += e * delta
            out.append(decay[i] * dh)
        dx_ref[t] = (through_b * delta + skip * dy).astype(dx_ref.dtype)
        # softplus' = sigmoid = 1 - exp(-softplus).
        ddt_ref[t] = ((through_b * x + through_a * _LN2)
                      * (1.0 - jnp.exp(-delta)))
        dd_ref[...] += dy * x
        db_lanes[t] = jnp.sum(jnp.stack(to_b), axis=1)
        dc_lanes[t] = jnp.sum(jnp.stack(to_c), axis=1)
        return tuple(out)

    g = lax.fori_loop(0, tile, back, tuple(carried[i] for i in range(n)))
    for i in range(n):
        carried[i] = g[i]
    db_ref[...] = jnp.sum(db_lanes[...], axis=-1)
    dc_ref[...] = jnp.sum(dc_lanes[...], axis=-1)


def _specs(tile: int, n: int, tile_of):
    """Block specs of a tile of a slab's ``x`` (``dt``, ``y``, ...),
    of ``A`` (``dA``), of ``D`` (``dD``), of the scalars ``B`` and ``C``,
    of the saved states and of ``dB`` (``dC``); ``tile_of(t)`` is the tile
    grid step ``t`` works on."""
    return (pl.BlockSpec((None, tile, SUBLANES, LANES),
                         lambda b, j, t: (b, tile_of(t), j, 0)),
            pl.BlockSpec((n, SUBLANES, LANES), lambda b, j, t: (0, j, 0)),
            pl.BlockSpec((SUBLANES, LANES), lambda b, j, t: (j, 0)),
            pl.BlockSpec((None, tile * n), lambda b, j, t: (b, tile_of(t)),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, None, n, SUBLANES, LANES),
                         lambda b, j, t: (b, tile_of(t), 0, j, 0)),
            pl.BlockSpec((None, None, tile, n),
                         lambda b, j, t: (b, j, tile_of(t), 0)))


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=VMEM_LIMIT)


# The calls are jitted with what is static among their arguments, and
# inlined: the Mamba layers of a step, each traced forward, recomputed and
# backward, share one traced kernel and one lowering a kind.

@functools.partial(jax.jit, static_argnames=("tile", "save_states",
                                             "interpret"), inline=True)
def _fwd_call(x, dt, a, d, b_in, c_in, *, tile, save_states, interpret):
    bsz, t, rows, _ = x.shape
    n = a.shape[0]
    vma = _vma(x, dt, a, d, b_in, c_in)
    slab, a_spec, d_spec, scalars, saved, _ = _specs(tile, n, lambda t_i: t_i)
    out_shape = [jax.ShapeDtypeStruct(x.shape, _F32, vma=vma)]
    out_specs = [slab]
    if save_states:
        out_shape.append(jax.ShapeDtypeStruct(
            (bsz, t // tile, n, rows, LANES), _F32, vma=vma))
        out_specs.append(saved)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, tile=tile, n=n),
        out_shape=out_shape,
        grid=(bsz, rows // SUBLANES, t // tile),
        in_specs=[slab, slab, a_spec, d_spec, scalars, scalars],
        out_specs=out_specs,
        scratch_shapes=[pltpu.VMEM((n, SUBLANES, LANES), _F32)],
        interpret=interpret, name=scopes.MAMBA_SCAN_FWD,
        compiler_params=_COMPILER_PARAMS,
    )(x, dt, a, d, b_in, c_in)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"),
                   inline=True)
def _bwd_call(x, dt, a, d, b_in, c_in, dy, states, *, tile, interpret):
    bsz, t, rows, _ = x.shape
    n = a.shape[0]
    slabs = rows // SUBLANES
    vma = _vma(x, dt, a, d, b_in, c_in, dy, states)
    last = t // tile - 1
    slab, a_spec, d_spec, scalars, saved, sums = _specs(
        tile, n, lambda t_i: last - t_i)

    def shape(*dims):
        return jax.ShapeDtypeStruct(dims, _F32, vma=vma)

    def a_batch(spec):       # a batch row of its own: the axis is parallel
        return pl.BlockSpec(
            (None,) + tuple(spec.block_shape),
            lambda b, j, t: (b,) + tuple(spec.index_map(b, j, t)))

    return pl.pallas_call(
        functools.partial(_bwd_kernel, tile=tile, n=n),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma),
                   shape(*x.shape),
                   shape(bsz, slabs, t, n), shape(bsz, slabs, t, n),
                   shape(bsz, *a.shape), shape(bsz, *d.shape)],
        grid=(bsz, slabs, last + 1),
        in_specs=[slab, slab, a_spec, d_spec, scalars, scalars, slab, saved],
        out_specs=[slab, slab, sums, sums, a_batch(a_spec), a_batch(d_spec)],
        scratch_shapes=[pltpu.VMEM((tile + 1, n, SUBLANES, LANES), _F32),
                        pltpu.VMEM((n, SUBLANES, LANES), _F32),
                        pltpu.VMEM((tile, n, LANES), _F32),
                        pltpu.VMEM((tile, n, LANES), _F32)],
        interpret=interpret, name=scopes.MAMBA_SCAN_BWD,
        compiler_params=_COMPILER_PARAMS,
    )(x, dt, a, d, b_in, c_in, dy, states)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(x, dt, a, d, b_in, c_in, tile):
    return _fwd_call(x, dt, a, d, b_in, c_in, tile=tile,
                     save_states=False, interpret=_interpret(x))[0]


def _scan_fwd(x, dt, a, d, b_in, c_in, tile):
    operands = (x, dt, a, d, b_in, c_in)
    y, states = _fwd_call(*operands, tile=tile, save_states=True,
                          interpret=_interpret(x))
    return y, operands + (states,)


def _scan_bwd(tile, residuals, dy):
    dx, ddt, db, dc, da, dd = _bwd_call(
        *residuals[:6], dy, residuals[6], tile=tile,
        interpret=_interpret(dy))
    shape = residuals[4].shape
    # The kernel sums the cotangent of A; the operand is A log2(e).
    return (dx, ddt, da.sum(0) * _LN2, dd.sum(0),
            db.sum(1).reshape(shape),
            dc.sum(1).reshape(shape))


_scan.defvjp(_scan_fwd, _scan_bwd)


def mamba_scan(x, dt, a, b_in, c_in, d):
    """The scan from ``h_0 = 0`` with the ``D x`` skip and the step
    ``delta = softplus(dt)``: ``x`` [B, T, C] in the model dtype, ``dt``
    [B, T, C] float32, ``a`` [C, N] float32 (``< 0``), ``b_in``, ``c_in``
    [B, T, N] float32, ``d`` [C] float32 -> ``y`` [B, T, C] float32.
    Sizes are ones that :func:`takes` accepts.  Differentiable in all
    six."""
    (bsz, t, channels), n = x.shape, a.shape[1]
    tile = tiles(t, channels, n)
    if tile is None:
        raise ValueError(
            "mamba scan: the kernels do not take (tokens, channels, state)"
            f" = {(t, channels, n)}: tiles(), takes()")
    rows = channels // LANES

    def slabs(v):          # [B, T, C] -> [B, T, C / 128, 128]
        return v.reshape(bsz, t, rows, LANES)

    def scalars(v):        # [B, T, N] -> [B, T N] float32
        return v.astype(_F32).reshape(bsz, t * n)

    # The kernels take A log2(e) (``_decay``); its cotangent comes back
    # through this product.
    a2 = a.astype(_F32).T.reshape(n, rows, LANES) * (1.0 / _LN2)
    y = _scan(slabs(x), slabs(dt.astype(_F32)), a2,
              d.astype(_F32).reshape(rows, LANES), scalars(b_in),
              scalars(c_in), tile)
    return y.reshape(bsz, t, channels)
