"""The sum into the tokens of a share's expert layer and the gather it is
the gradient of (``ops/moe_rows.py``) in the Pallas interpreter, against
their ``jax.numpy`` oracles: a gather of the prefix and a float32
scatter-add (``models/moe._take``, ``jax.ops.segment_sum``), what
``_head_ffn`` runs where the kernels refuse."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from horovod_tpu.models import moe
from horovod_tpu.ops import moe_rows as op

BF16, F32 = jnp.bfloat16, jnp.float32
# 256 tokens of 8 slots: two token tiles (an SMEM block of slots each); a
# prefix of half the slots, four tiles of the layout kernel; the least row,
# one tile of float32.
N, S, D = 256, 8, 1024
M = N * S // 2
HELD = 4
TILE = op.LAYOUT_TILE
LIVE = (0, 1, TILE - 1, TILE, TILE + 1, M)


def _routing(live: int, seed: int = 0):
    """``(head [M], lists, weights [N, S])`` of a batch with ``live`` held
    slots: token 0 holds all ``S`` of its slots where that many are live,
    token 1 exactly one, token 2 none."""
    rng = np.random.default_rng(seed + live)
    held = np.zeros((N, S), bool)
    forced = [(0, a) for a in range(S)] if live > S else []
    forced = ([(1, 3)] + forced)[:live]
    for t, a in forced:
        held[t, a] = True
    free = [(t, a) for t in range(3, N) for a in range(S)]
    for i in rng.permutation(len(free))[:live - len(forced)]:
        held[free[i]] = True
    assert held.sum() == live and not held[2].any()
    slot_e = np.where(held, rng.integers(0, HELD, (N, S)), HELD)
    order = jnp.argsort(jnp.asarray(slot_e.reshape(-1)), stable=True).astype(
        jnp.int32)
    head = order[:M]
    return head, op.by_token(head, jnp.int32(live), N * S), jnp.asarray(
        np.where(held, rng.uniform(0.1, 1.0, (N, S)), 0), F32)


def _rows(seed: int, rows: int, live=None):
    """bf16 rows; past ``live`` they are ``nan``."""
    x = jax.random.normal(jax.random.PRNGKey(seed), (rows, D), BF16)
    if live is not None:
        x = jnp.where((jnp.arange(rows) < live)[:, None], x, jnp.nan)
    return x


def _rows_out_oracle(h, head, live):
    """The gather, its result past the live count left out of account."""
    return jnp.where((jnp.arange(M) < live)[:, None],
                     moe._take(h, head // S), 0)


def _rows_back_oracle(out, weights, head, live):
    is_live = jnp.arange(M) < live
    weighted = (jnp.where(is_live[:, None], out.astype(F32), 0)
                * jnp.where(is_live, moe._take(weights.reshape(-1), head),
                            0)[:, None])
    return jax.ops.segment_sum(weighted, head // S, num_segments=N)


@pytest.mark.parametrize("live", LIVE)
def test_the_lists_are_the_live_slots_by_token(live):
    head, lists, _ = _routing(live)
    slot, place, starts = (np.asarray(x) for x in lists)
    assert slot.shape == place.shape == (M,) and starts.shape == (N * S
                                                                  // 1024 + 1,)
    assert (np.diff(slot[:live]) > 0).all() and (slot[live:] == N * S).all()
    np.testing.assert_array_equal(np.asarray(head)[place[:live]], slot[:live])
    assert (place[:live] < live).all() and starts[0] == 0 and (
        starts[-1] == live)
    for i in range(N * S // 1024):
        mine = slot[starts[i]:starts[i + 1]]
        assert ((mine >= i * 1024) & (mine < (i + 1) * 1024)).all()


@pytest.mark.parametrize("live", LIVE)
def test_the_sum_is_the_float32_sum_of_the_live_rows(live):
    """The tail of the source poisoned: nothing past the live count is
    read.  Token 0 sums all its slots, token 1 one, token 2 reads zeros."""
    head, lists, weights = _routing(live)
    out = _rows(2, M, live)
    y = op.sum_by_token(out, weights, head, lists, jnp.int32(live))
    assert y.shape == (N, D) and y.dtype == BF16
    y = np.asarray(y, F32)
    assert np.isfinite(y).all()
    oracle = np.asarray(_rows_back_oracle(out, weights, head, live))
    np.testing.assert_allclose(y, oracle, rtol=2 ** -8, atol=1e-6)
    assert not y[2].any()
    if live > S:
        assert np.abs(y[0]).max() > 0 and np.abs(y[1]).max() > 0


@pytest.mark.parametrize("live", LIVE)
@pytest.mark.parametrize("move", ["out", "back"])
def test_each_move_is_the_others_gradient(move, live):
    """The ``custom_vjp`` of each against autodiff of its oracle; the
    cotangent's dead tail is poisoned where the move's result has one."""
    head, lists, weights = _routing(live)
    count = jnp.int32(live)
    if move == "out":
        h, g = _rows(3, N), _rows(4, M, live)
        x, pull = jax.vjp(
            lambda h: op.rows_by_token(S, h, head // S, lists, count), h)
        np.testing.assert_array_equal(np.asarray(x, F32), np.asarray(
            moe._take(h, head // S), F32))
        (d_h,) = pull(g)
        (oracle,) = jax.vjp(
            lambda h: _rows_out_oracle(h, head, live), h.astype(F32))[1](
                jnp.where(jnp.isnan(g), 0, g).astype(F32))
        assert d_h.dtype == BF16
        np.testing.assert_allclose(np.asarray(d_h, F32), np.asarray(oracle),
                                   rtol=2 ** -8, atol=1e-6)
        return
    out, g = _rows(5, M, live), _rows(6, N)
    d_out, d_w = jax.vjp(
        lambda out, w: op.sum_by_token(out, w, head, lists, count), out,
        weights)[1](g)
    clean = jnp.where(jnp.isnan(out), 0, out).astype(F32)
    o_out, o_w = jax.vjp(
        lambda out, w: _rows_back_oracle(out, w, head, live), clean,
        weights)[1](g.astype(F32))
    assert d_out.dtype == BF16 and d_w.dtype == F32
    assert np.isfinite(np.asarray(d_out, F32)).all()
    np.testing.assert_allclose(np.asarray(d_out, F32), np.asarray(o_out),
                               rtol=2 ** -8, atol=1e-6)
    np.testing.assert_allclose(np.asarray(d_w), np.asarray(o_w), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("live", [0, TILE - 1, TILE + 1, M])
def test_row_tiles_lays_out_the_live_tiles(live):
    x = _rows(7, M)
    tiles = op.row_tiles(x, jnp.int32(live))
    assert tiles.shape == (M, D // op.LANES, op.LANES) and tiles.dtype == F32
    rows = -(-live // TILE) * TILE
    np.testing.assert_array_equal(
        np.asarray(tiles[:rows]).reshape(rows, D), np.asarray(x[:rows], F32))


@pytest.mark.parametrize("why,h,slots", [
    ("a row that is no whole tile", jnp.zeros((N, 1000), BF16), S),
    ("a row of half a tile", jnp.zeros((N, 512), BF16), S),
    ("float32 rows", jnp.zeros((N, D), F32), S),
    ("a row wider than the buffer holds", jnp.zeros((N, 8192), BF16), S),
    ("slots that do not divide a block", jnp.zeros((N, D), BF16), 6),
    ("tokens that are no whole blocks", jnp.zeros((N + 16, D), BF16), S),
])
def test_takes_refuses(why, h, slots):
    assert op.takes(jnp.zeros((N, D), BF16), S)
    assert not op.takes(h, slots), why


def test_takes_refuses_the_interpreter_under_check_vma(hvd):
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.topology import build_mesh

    mesh = build_mesh(axes=("data",), devices=jax.devices()[:2])
    seen = {}

    def inside(h, check):
        seen[check] = op.takes(h, S)
        return h

    for check in (True, False):
        jax.eval_shape(jax.shard_map(
            lambda h: inside(h, check), mesh=mesh, in_specs=P("data"),
            out_specs=P("data"), check_vma=check), jnp.zeros((2 * N, D), BF16))
    assert seen == {True: False, False: True}


def _layer(live: int, dtype, width: int = D, d_expert: int = 128):
    """A share's inputs as ``_head_ffn`` takes them: ``(h, slot_w, order,
    group_sizes, layer)``."""
    rng = np.random.default_rng(11)
    held = np.zeros(N * S, bool)
    held[rng.permutation(N * S)[:live]] = True
    slot_e = np.where(held, rng.integers(0, HELD, N * S), HELD)
    order = jnp.argsort(jnp.asarray(slot_e), stable=True).astype(jnp.int32)
    sizes = jnp.asarray(np.bincount(slot_e, minlength=HELD + 1)[:HELD],
                        jnp.int32)
    weights = jnp.asarray(np.where(held, rng.uniform(0.1, 1.0, N * S), 0),
                          F32).reshape(N, S)
    keys = jax.random.split(jax.random.PRNGKey(12), 3)
    layer = dict(
        w_up=jax.random.normal(keys[0], (HELD, width, d_expert), F32) * 0.03,
        w_down=jax.random.normal(keys[1], (HELD, d_expert, width), F32) * 0.1)
    return (jax.random.normal(keys[2], (N, width), dtype), weights, order,
            sizes, layer)


# A prefix of one row tile of the grouped matmuls, for 300 live rows.
PREFIX = 512


def _traced(jaxpr):
    """``(scatter-adds of rows, kernel names, float32 results' shapes)``;
    the grouped matmuls' visit lists scatter-add a few integers."""
    from tests.test_ssm_moe_lm import _eqns

    eqns = list(_eqns(jaxpr))
    return ([e for e in eqns if e.primitive.name == "scatter-add"
             and len(e.outvars[0].aval.shape) == 2],
            {e.params["name"] for e in eqns
             if e.primitive.name == "pallas_call"},
            {v.aval.shape for e in eqns for v in e.outvars
             if getattr(v.aval, "dtype", None) == F32})


@pytest.mark.parametrize("why,dtype,width", [
    ("float32 rows", F32, D), ("half a tile", BF16, 512)])
def test_head_ffn_traces_todays_lines_where_the_kernels_refuse(why, dtype,
                                                               width):
    h, weights, order, sizes, layer = _layer(300, dtype, width)
    jaxpr = jax.make_jaxpr(jax.grad(lambda h: jnp.sum(moe._head_ffn(
        PREFIX, "relu2", dtype, h, weights, order, sizes,
        layer).astype(F32))))(h).jaxpr
    scatter_adds, kernels, _ = _traced(jaxpr)
    assert scatter_adds, why
    assert not {k for k in kernels if k.startswith("moe_row")}, why


def test_head_ffn_on_the_kernels_is_the_scatter_form(monkeypatch):
    """Value and gradients of the layer over a prefix of one tile, on the
    kernels and on the lines they replace; no scatter-add is traced on
    the kernels' path and no float32 rows a prefix long."""
    h, weights, order, sizes, layer = _layer(300, BF16)

    def run(h, weights, layer):
        return moe._head_ffn(PREFIX, "relu2", BF16, h, weights, order,
                             sizes, layer)

    def loss(h, weights, layer):
        return jnp.sum(jnp.sin(run(h, weights, layer).astype(F32)))

    assert op.takes(h, S)
    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(h, weights, layer)
    scatter_adds, kernels, _ = _traced(jaxpr.jaxpr)
    assert {"moe_rows_back", "moe_row_tiles"} <= kernels
    assert not scatter_adds
    # Forward, nothing float32 is the prefix's rows by the rows' width
    # (the scatter form's product is); the rows travel as float32 tiles.
    forward = jax.make_jaxpr(run)(h, weights, layer).jaxpr
    assert (PREFIX, D) not in _traced(forward)[2]
    assert (PREFIX, D // op.LANES, op.LANES) in _traced(forward)[2]
    y, grads = run(h, weights, layer), jax.grad(loss, (0, 1, 2))(
        h, weights, layer)
    monkeypatch.setattr(op, "takes", lambda h, slots: False)
    assert (PREFIX, D) in _traced(jax.make_jaxpr(
        lambda *inputs: run(*inputs))(h, weights, layer).jaxpr)[2]
    y_xla, grads_xla = run(h, weights, layer), jax.grad(loss, (0, 1, 2))(
        h, weights, layer)
    np.testing.assert_allclose(np.asarray(y, F32), np.asarray(y_xla, F32),
                               rtol=2 ** -7, atol=1e-3)
    for got, want in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_xla)):
        got, want = np.asarray(got, F32), np.asarray(want, F32)
        assert (np.linalg.norm(got - want)
                <= 0.01 * np.linalg.norm(want) + 1e-6)
