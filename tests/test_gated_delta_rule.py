"""The gated-delta-rule Pallas kernels (``ops/gated_delta_rule.py``) in the
Pallas interpreter on the CPU: the same code Mosaic compiles for the chip
(``tests/test_flash_compile.py`` holds that it does).

Oracles: the benchmark's token-by-token recurrence
(``perfbench/reference/hybrid_lm.py``), which shares no code with the
program, and the ``jax.numpy`` chunked form the kernels took the place of
(``models/linear_attention.py::gated_delta_rule``).  Tolerances are those
of ``tests/test_hybrid_lm.py``: float32 rounding.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import linear_attention as la
from horovod_tpu.ops import gated_delta_rule as op
from perfbench.reference import hybrid_lm as reference
from tests.test_hybrid_lm import F32_REL, GATES, _rel

TILE = op.TILE_PACKS * op.ROWS
# One block (half a pack: the rest is filled), an odd number of blocks,
# one tile, several tiles.
LENGTHS = {"one_block": op.BLOCK, "five_blocks": 5 * op.BLOCK,
           "one_tile": TILE, "three_tiles": 3 * TILE}


def _inputs(t, gates, batch=2, h=2, dk=24, dv=40, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(t), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (batch, t, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (batch, t, h, dk)))
    v = jax.random.normal(ks[2], (batch, t, h, dv))
    g = jax.random.uniform(ks[3], (batch, t, h), minval=gates[0],
                           maxval=gates[1])
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(ks[4], (batch, t, h)) + 1.0)
    do = jax.random.normal(ks[5], (batch, t, h, dv))
    return (q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta,
            do.astype(dtype))


def _by_token(q, k, v, g, beta):
    # The reference walks whole runs of tokens: fill the last one with
    # tokens that write nothing (beta = 0).
    t = q.shape[1]
    fill = -t % min(reference.SCAN_RUN, t)
    q, k, v, g, beta = (
        jnp.pad(x, ((0, 0), (0, fill)) + ((0, 0),) * (x.ndim - 2))
        for x in (q, k, v, g, beta))
    return jax.vmap(lambda *a: reference._delta_rule(
        a[0], a[1], a[2], jnp.exp(a[3]), a[4], None))(
            q, k, v, g, beta)[:, :t]


def _with_grads(f, *inputs):
    *operands, do = inputs
    out, pull = jax.vjp(f, *operands)
    return (out,) + pull(do.astype(out.dtype))


@pytest.mark.parametrize("t", LENGTHS.values(), ids=LENGTHS.keys())
@pytest.mark.parametrize("gates", GATES.values(), ids=GATES.keys())
def test_kernels_match_both_oracles(gates, t):
    """Output and all five gradients, batch and heads above one, beta up
    to 2, alpha near 1 and near 0 (where the decays underflow)."""
    inputs = _inputs(t, gates)
    assert float(inputs[4].max()) > 1.5
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: _with_grads(op.gated_delta_rule, *a))(
            *inputs)
        for oracle in (_by_token,
                       lambda *a: la.gated_delta_rule(*a, jnp.float32)):
            want = jax.jit(lambda *a: _with_grads(oracle, *a))(*inputs)
            assert _rel(got[0], want[0]) <= F32_REL
            for name, a, b in zip("q k v g beta".split(), got[1:], want[1:]):
                # The floor of test_hybrid_lm's token-by-token test: near
                # alpha = 0 the gradient of g is what is left of sums that
                # cancel.
                bound = (2e-5 * np.linalg.norm(b)
                         + 1e-6 * np.linalg.norm(want[3]))
                assert np.linalg.norm(np.asarray(a - b)) <= bound, name


def test_bfloat16_operands_are_no_further_from_float32_than_the_jax_numpy_form():
    """The kernels round where the module's docstring says and nowhere
    else: against the float32 token-by-token recurrence they read no more
    than the ``jax.numpy`` form with the same operands."""
    inputs = _inputs(4 * op.BLOCK, GATES["alpha_near_1"], dtype=jnp.bfloat16)
    f32 = tuple(x.astype(jnp.float32) for x in inputs)
    with jax.default_matmul_precision("highest"):
        want = _with_grads(_by_token, *f32)
    got = _with_grads(op.gated_delta_rule, *inputs)
    xla = _with_grads(lambda *a: la.gated_delta_rule(*a, jnp.bfloat16),
                      *inputs)
    for name, a, b, w in zip("o q k v g beta".split(), got, xla, want):
        # Roundings fall differently: a fifth is their noise at this size.
        assert _rel(a.astype(jnp.float32), w) <= 1.2 * _rel(
            b.astype(jnp.float32), w), name
        assert _rel(a.astype(jnp.float32), w) <= 2e-2, name


@pytest.mark.parametrize("t,packs", [
    (op.ROWS, 1), (5 * op.ROWS, 5), (TILE, op.TILE_PACKS),
    (16 * TILE, op.TILE_PACKS), (12 * op.ROWS, None), (op.ROWS + 8, None)])
def test_tiles(t, packs):
    assert op.tiles(t) == packs


def test_the_path_is_read_from_the_operand(hvd):
    """The kernels wherever they can run; the ``jax.numpy`` form for a
    length that does not cut into tiles and, on the CPU, inside
    ``shard_map(check_vma=True)``, where the interpreter's loop does not
    type."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.topology import build_mesh

    x = jnp.zeros((2, 4 * op.BLOCK, 8))
    assert la.recurrence_path(x) == "kernel"
    assert la.recurrence_path(x[:, :op.BLOCK]) == "kernel"
    assert la.recurrence_path(jnp.zeros((2, 12 * op.ROWS, 8))) == "xla"
    assert la.recurrence_path(x[:, :op.BLOCK + 8]) == "xla"
    mesh = build_mesh(axes=("data",), devices=jax.devices()[:2])
    seen = {}

    def inside(x, check):
        seen[check] = la.recurrence_path(x)
        return x

    for check in (True, False):
        jax.eval_shape(jax.shard_map(
            lambda x: inside(x, check), mesh=mesh, in_specs=P("data"),
            out_specs=P("data"), check_vma=check), x)
    assert seen == {True: "xla", False: "kernel"}
    with pytest.raises(ValueError, match="whole blocks"):
        op.gated_delta_rule(*_inputs(op.BLOCK + 8, GATES["alpha_mid"])[:5])


def test_linear_layers_share_one_traced_kernel_a_kind(monkeypatch):
    """Forward, recomputed forward and backward of every layer go through
    the same jitted calls: the kernels' bodies are traced once a kind
    (the forward with and without the saved states), whatever the
    depth."""
    traced = {"fwd": 0, "bwd": 0}

    def counting(kind, kernel):
        def body(*refs, **kw):
            traced[kind] += 1
            return kernel(*refs, **kw)
        return body

    monkeypatch.setattr(op, "_fwd_kernel", counting("fwd", op._fwd_kernel))
    monkeypatch.setattr(op, "_bwd_kernel", counting("bwd", op._bwd_kernel))
    # Shapes no other test has: nothing of this is in the jit caches.
    inputs = _inputs(2 * op.BLOCK, GATES["alpha_mid"], dk=8, dv=16)

    def three_layers(q, k, v, g, beta):
        for _ in range(3):
            v = jax.checkpoint(op.gated_delta_rule)(q, k, v, g, beta)
        return jnp.sum(v)

    jax.jit(jax.grad(three_layers, (0, 1, 2, 3, 4))).lower(*inputs[:5])
    assert traced == {"fwd": 2, "bwd": 1}
