"""The config-driven transformer block (rotary, QK-norm, SwiGLU, untied
head) and the dropless mixture-of-experts layer (``models/moe.py``,
``ops/grouped_matmul.py``), at tiny sizes on the virtual CPU mesh.

Oracles: a per-expert Python loop for the layer, the benchmark's plain
float32 reference (``perfbench/reference/moe_lm.py``, which shares no code
with the program) for the block and the train step, hand values for the
auxiliary losses.  Tolerances, float32 everywhere unless a test says
otherwise: 2e-5 relative, which is float32 rounding through two layers (a
bfloat16 anywhere reads 1e-3 and up, and one test proves that for the
router's softmax).
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models import attention, moe
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import grouped_matmul as gm
from perfbench.reference import moe_lm as reference

F32_RTOL = 2e-5

# The families every configuration shares, and the table of configurations
# (tests/test_lm_configs.py); those that compile this row's program run
# here, in the row's own file: a file is one worker's chain.
from test_lm_configs import *  # noqa: E402,F401,F403
from test_lm_configs import OLMOE_TINY  # noqa: E402

COSTLY_ROWS = ("olmoe",)


def _batch(cfg, batch=4, seq=32, seed=1):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq + 1), 0,
                              cfg.vocab_size)
    return toks[:, :-1], toks[:, 1:]


# --- the grouped matmul kernels --------------------------------------------

@pytest.fixture()
def small_tiles(monkeypatch):
    """Tiles that make tiny shapes span several tiles and visits, each
    tile in four sub-tiles."""
    monkeypatch.setattr(gm, "TILE_M", 16)
    monkeypatch.setattr(gm, "SUB_M", 4)
    monkeypatch.setattr(gm, "TILE_K", 32)
    monkeypatch.setattr(gm, "TILE_N", 32)


GROUPS = {"ragged": [30, 0, 50, 1, 47, 0], "one_group": [0, 0, 128, 0, 0, 0],
          "tile_aligned": [16, 16, 32, 16, 32, 16],
          "first_row_alone": [1, 127, 0, 0, 0, 0],
          # Tiles of 16 rows in sub-tiles of 4: boundaries at 20 and 44,
          # on a sub-tile's edge inside a tile;
          "boundary_on_a_sub_tile_edge": [20, 24, 36, 48, 0, 0],
          # at 19 and 45, one row before and one after such an edge;
          "boundary_one_row_off_an_edge": [19, 26, 35, 48, 0, 0],
          # rows 16-17, 18 and 19 of one sub-tile are three groups';
          "three_groups_in_a_sub_tile": [18, 1, 1, 108, 0, 0],
          # rows 20-23 are a group: one sub-tile, whole;
          "group_is_one_sub_tile": [20, 4, 40, 64, 0, 0],
          # boundaries at 18, 22, 26 and 30: every sub-tile of tile 1.
          "boundary_in_every_sub_tile": [18, 4, 4, 4, 98, 0]}


@pytest.mark.parametrize("sizes", GROUPS.values(), ids=GROUPS.keys())
def test_grouped_matmul_matches_ragged_dot(small_tiles, sizes):
    """Forward and both gradients against XLA's own ``ragged_dot``; empty
    groups get no rows and a zero weight gradient."""
    x = jax.random.normal(jax.random.key(0), (128, 64), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (6, 64, 96), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)

    def loss(mm):
        return lambda x, w: jnp.sum(jnp.sin(mm(x, w, gs)))

    got, got_g = jax.value_and_grad(loss(gm.grouped_matmul), (0, 1))(x, w)
    want, want_g = jax.value_and_grad(
        loss(lambda *a: jax.lax.ragged_dot(*a, precision="highest")),
        (0, 1))(x, w)
    # The interpreter's float32 matmul on the CPU sums in another order.
    assert abs(got - want) <= 1e-4 * abs(want) + 1e-4
    for g, wnt in zip(got_g, want_g):
        np.testing.assert_allclose(g, wnt, atol=2e-4)
    empty = np.asarray(sizes) == 0
    assert not np.asarray(got_g[1])[empty].any()


def test_grouped_matmul_visits_cover_each_row_once():
    sizes = jnp.asarray([30, 0, 50, 1, 47, 0], jnp.int32)
    offsets, group, tile, n = gm._visits(sizes, 128, 16, visit_empty=False)
    offsets, group, tile = map(np.asarray, (offsets, group, tile))
    rows = np.zeros(128, int)
    for v in range(int(n)):
        lo = max(offsets[group[v]], tile[v] * 16)
        hi = min(offsets[group[v] + 1], tile[v] * 16 + 16)
        assert hi > lo            # no visit without rows of its own
        rows[lo:hi] += 1
    assert (rows == 1).all()
    # A shared tile is visited twice in a row: output tiles are revisited
    # only consecutively.
    assert (np.diff(tile[:int(n)]) >= 0).all()
    *_, n_all = gm._visits(sizes, 128, 16, visit_empty=True)
    assert int(n_all) == int(n) + 2


def test_matmul_rows_is_what_the_kernels_multiply(small_tiles, monkeypatch):
    """``matmul_rows`` against a count taken inside the interpreter: every
    matmul a kernel runs reports the rows of its piece, per K and N tile
    (here 2 and 3 of them)."""
    seen = []
    dot = gm._dot

    def counted(rows, other, contract):
        jax.debug.callback(lambda: seen.append(rows.shape[0]))
        return dot(rows, other, contract)

    monkeypatch.setattr(gm, "_dot", counted)
    x = jax.random.normal(jax.random.key(0), (128, 64), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (6, 64, 96), jnp.float32)
    jax.clear_caches()       # the calls are jitted: trace them counted,
    try:
        for sizes in GROUPS.values():
            gs = jnp.asarray(sizes, jnp.int32)
            want = int(gm.matmul_rows(gs, 128))
            for kernel in (lambda: gm._gmm(x, w, gs, transpose_rhs=False),
                           lambda: gm._tgmm(x, x @ w[0], gs)):
                seen.clear()
                jax.block_until_ready(kernel())
                jax.effects_barrier()
                assert sum(seen) == want * 2 * 3, sizes
            assert 128 <= want <= gm.worst_matmul_rows(6, 128) == 128 + 5 * 4
    finally:
        jax.clear_caches()   # and leave no counted trace behind.
    assert int(gm.matmul_rows(jnp.asarray(GROUPS["tile_aligned"]), 128)) == 128
    assert int(gm.matmul_rows(
        jnp.asarray(GROUPS["boundary_in_every_sub_tile"]), 128)) == 128 + 16


def test_matmul_rows_at_the_olmoe_cells_shape(monkeypatch):
    """65,536 rows in 64 groups: 512 sub-tiles of 128 and one more for
    each of 63 boundaries off an edge, where whole-tile visits (a
    sub-tile as high as the tile) cost 128 + 63 tiles of 512."""
    aligned = jnp.full(64, 1024, jnp.int32)
    ragged = aligned.at[0].add(1).at[-1].add(-1)  # boundaries at 1024 g + 1
    assert int(gm.matmul_rows(ragged, 65536)) == 575 * 128
    assert int(jax.jit(gm.matmul_rows, static_argnums=1)(
        aligned, 65536)) == 65536
    assert gm.worst_matmul_rows(64, 65536) == 575 * 128
    monkeypatch.setattr(gm, "SUB_M", gm.TILE_M)
    assert int(gm.matmul_rows(ragged, 65536)) == 764 * 128
    assert int(gm.matmul_rows(aligned, 65536)) == 65536


@pytest.mark.parametrize("transpose_rhs", (False, True),
                         ids=("moe_gmm", "moe_gmm_nt"))
def test_sub_tiles_leave_every_output_bit_alone(small_tiles, monkeypatch,
                                                transpose_rhs):
    """Rows are independent and the contraction is cut the same way, so
    four sub-tiles a tile give the bits of one sub-tile as high as the
    tile (every visit multiplies its whole tile: the rule before)."""
    x = jax.random.normal(jax.random.key(0), (128, 64), jnp.float32)
    w = jax.random.normal(jax.random.key(1), (6, 96, 64) if transpose_rhs
                          else (6, 64, 96), jnp.float32)
    gs = jnp.asarray(GROUPS["ragged"], jnp.int32)
    got = gm._gmm(x, w, gs, transpose_rhs)
    monkeypatch.setattr(gm, "SUB_M", gm.TILE_M)
    np.testing.assert_array_equal(got, gm._gmm(x, w, gs, transpose_rhs))


def test_grouped_matmul_refuses_a_dimension_its_tile_does_not_divide():
    with pytest.raises(ValueError, match="rows=600"):
        gm.grouped_matmul(jnp.zeros((600, 64)), jnp.zeros((2, 64, 64)),
                          jnp.asarray([300, 300], jnp.int32))


# --- weights in the dtype they are stored in -------------------------------

def _bf16_rows_f32_weights(transpose_rhs=False):
    x = jax.random.normal(jax.random.key(0), (128, 64)).astype(jnp.bfloat16)
    w = jax.random.normal(jax.random.key(1), (6, 96, 64) if transpose_rhs
                          else (6, 64, 96), jnp.float32)
    return x, w


@pytest.mark.parametrize("tile_k", (32, 2048),
                         ids=("k_tiled", "k_one_tile"))
@pytest.mark.parametrize("transpose_rhs", (False, True),
                         ids=("moe_gmm", "moe_gmm_nt"))
@pytest.mark.parametrize("sizes", GROUPS.values(), ids=GROUPS.keys())
def test_float32_weights_are_rounded_in_the_kernel_bit_for_bit(
        small_tiles, monkeypatch, sizes, transpose_rhs, tile_k):
    """bf16 rows against float32 weights: the kernel rounds each block to
    bf16 as a cast before the call would, so every output bit is that
    call's.  With K in two tiles the grid's pipeline fetches the blocks;
    with K as one tile the kernel does, a group ahead, three N tiles in
    turn."""
    monkeypatch.setattr(gm, "TILE_K", tile_k)
    x, w = _bf16_rows_f32_weights(transpose_rhs)
    gs = jnp.asarray(sizes, jnp.int32)
    got = gm._gmm(x, w, gs, transpose_rhs)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        got, gm._gmm(x, w.astype(jnp.bfloat16), gs, transpose_rhs))


@pytest.mark.parametrize("sizes", GROUPS.values(), ids=GROUPS.keys())
def test_float32_weights_keep_every_gradient_bit(small_tiles, monkeypatch,
                                                 sizes):
    """Output and the rows' gradient bit for bit, the weights' gradient
    to the last bit of bf16 and in the weights' own dtype.  K and N as
    one tile, as at the OLMoE shapes: a group's block is fetched a group
    ahead and stays over its visits."""
    monkeypatch.setattr(gm, "TILE_K", 2048)
    monkeypatch.setattr(gm, "TILE_N", 2048)
    x, w = _bf16_rows_f32_weights()
    gs = jnp.asarray(sizes, jnp.int32)

    def run(cast):
        def loss(x, w):
            out = gm.grouped_matmul(x, cast(w), gs)
            return jnp.sum(jnp.sin(out.astype(jnp.float32))), out
        (_, out), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(
            x, w)
        return (out, *grads)

    got = run(lambda w: w)
    want = run(lambda w: w.astype(jnp.bfloat16))
    assert [a.dtype for a in got] == [jnp.bfloat16, jnp.bfloat16,
                                      jnp.float32]
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert np.asarray(got[2]).any()
    np.testing.assert_array_equal(got[2], got[2].astype(jnp.bfloat16))


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it (calls,
    branches of a ``cond``, loop bodies, kernels)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for inner in value if isinstance(value, (tuple, list)) else (
                    value,):
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


@pytest.mark.parametrize("weights_dtype,tile_k,scratch,rounded", [
    (jnp.bfloat16, 64, ["float32[16,32]"], 0),
    # K in two tiles: the grid's pipeline fetches the float32 blocks.
    (jnp.float32, 32, ["float32[16,32]"], 2),
    # K one tile: the kernel fetches them itself, a group ahead.
    (jnp.float32, 64, ["float32[16,32]", "float32[2,64,32]",
                       "dma_sem[2]", "int32[1]"], 2)],
    ids=("equal_dtypes_are_todays_kernel", "wider_weights_k_tiled",
         "wider_weights_k_one_tile"))
def test_gmm_rounds_only_weights_stored_wider(small_tiles, monkeypatch,
                                              weights_dtype, tile_k,
                                              scratch, rounded):
    """Equal dtypes trace the kernel as it was: one scratch (the
    accumulator), the weights through the grid's pipeline, no convert of
    a weight block.  Wider weights are rounded where a piece is
    multiplied (the whole tile, and the sub-tile loop's body)."""
    monkeypatch.setattr(gm, "TILE_K", tile_k)
    x, w = _bf16_rows_f32_weights()
    gs = jnp.asarray(GROUPS["ragged"], jnp.int32)
    (call,) = [e for e in _eqns(jax.make_jaxpr(
        lambda x, w: gm._gmm(x, w, gs, False))(x, w.astype(weights_dtype))
        .jaxpr) if e.primitive.name == "pallas_call"]
    kernel = call.params["jaxpr"]
    n = call.params["grid_mapping"].num_scratch_operands
    assert [v.aval.inner_aval.str_short() for v in kernel.invars[-n:]] == \
        scratch
    converts = [e for e in _eqns(kernel)
                if e.primitive.name == "convert_element_type"
                and e.outvars[0].aval.shape == (tile_k, 32)]
    assert len(converts) == rounded


# --- the expert layer -------------------------------------------------------

def _loop_oracle(h, top_p, top_i, layer):
    """Every expert in a Python loop over the tokens that chose it."""
    out = jnp.zeros_like(h)
    for e in range(layer["w_gate"].shape[0]):
        weight = jnp.sum(jnp.where(top_i == e, top_p, 0.0), axis=-1)
        gate = h @ layer["w_gate"][e]
        y = (jax.nn.silu(gate) * (h @ layer["w_up"][e])) @ layer["w_down"][e]
        out = out + weight[:, None] * y
    return out


def _layer(experts=8, d=32, f=16, seed=0):
    k = jax.random.split(jax.random.key(seed), 4)
    return {"router": jax.random.normal(k[0], (d, experts)) * d ** -0.5,
            "w_gate": jax.random.normal(k[1], (experts, d, f)) * d ** -0.5,
            "w_up": jax.random.normal(k[2], (experts, d, f)) * d ** -0.5,
            "w_down": jax.random.normal(k[3], (experts, f, d)) * f ** -0.5}


def _choices(kind, n=64, experts=8, k=2):
    """[n, k] distinct experts per token."""
    rng = np.random.default_rng(0)
    if kind == "uniform":
        pick = [rng.choice(experts, k, replace=False) for _ in range(n)]
    elif kind == "skewed":       # 90% of the tokens take experts 0 and 1
        pick = [[0, 1] if rng.random() < 0.9
                else rng.choice(experts, k, replace=False) for _ in range(n)]
    else:                        # nobody takes expert 3 or expert 7
        allowed = [e for e in range(experts) if e not in (3, 7)]
        pick = [rng.choice(allowed, k, replace=False) for _ in range(n)]
    return jnp.asarray(np.asarray(pick), jnp.int32)


@pytest.mark.parametrize("kind", ("uniform", "skewed", "empty_experts"))
def test_expert_layer_matches_a_per_expert_loop(small_tiles, kind):
    """Forward and every gradient (tokens, routing weights, the three
    expert matrices), whatever the load: nothing dropped, nothing assumed
    balanced, an expert with no token legal."""
    layer = _layer()
    h = jax.random.normal(jax.random.key(5), (64, 32))
    top_i = _choices(kind)
    top_p = jax.random.uniform(jax.random.key(6), top_i.shape, minval=0.05)

    def loss(fn):
        return lambda h, p, l: jnp.sum(jnp.sin(fn(h, p, top_i, l)))

    counts = jnp.bincount(top_i.reshape(-1), length=8).astype(jnp.int32)
    run = lambda h, p, i, l: moe.experts_ffn(h, p, i, counts, l,
                                             jnp.float32)
    got, got_g = jax.value_and_grad(loss(run), (0, 1, 2))(h, top_p, layer)
    want, want_g = jax.value_and_grad(loss(_loop_oracle), (0, 1, 2))(
        h, top_p, layer)
    assert abs(got - want) <= F32_RTOL * abs(want) + 1e-5
    flat_got, _ = jax.tree_util.tree_flatten(got_g)
    flat_want, _ = jax.tree_util.tree_flatten(want_g)
    for g, w in zip(flat_got, flat_want):
        np.testing.assert_allclose(g, w, atol=5e-5)
    if kind == "empty_experts":
        assert not np.asarray(got_g[2]["w_down"])[[3, 7]].any()


def test_route_is_float32_softmax_then_topk_and_ties_take_the_lower_index():
    layer = _layer()
    # Experts 2 and 5 share a router column: their scores tie everywhere.
    router = layer["router"].at[:, 5].set(layer["router"][:, 2]) * 1.5
    h = jax.random.normal(jax.random.key(7), (64, 32)).astype(jnp.bfloat16)
    top_p, top_i, stats = moe.route(h, router, 3, False)
    logits = np.asarray(h, np.float64) @ np.asarray(router, np.float64)
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    order = np.argsort(-probs, axis=-1, kind="stable")[:, :3]
    np.testing.assert_array_equal(top_i, order)
    both = (order == 2).any(-1) & (order == 5).any(-1)
    assert both.any()            # the tie was among the chosen somewhere
    want = np.take_along_axis(probs, order, axis=-1)
    assert top_p.dtype == jnp.float32
    np.testing.assert_allclose(top_p, want, rtol=1e-5)
    # Not renormalised: the kept probabilities sum to less than one.
    assert np.asarray(top_p).sum(-1).max() < 0.999
    # The same softmax in bfloat16 fails that tolerance by far.
    low = jax.nn.softmax(jnp.asarray(logits, jnp.bfloat16), axis=-1)
    low = np.take_along_axis(np.asarray(low, np.float64), order, axis=-1)
    assert np.abs(low / want - 1.0).max() > 1e-3
    np.testing.assert_allclose(stats.counts.sum(), 64 * 3)
    np.testing.assert_allclose(stats.prob_sum, probs.sum(0), rtol=1e-5)
    renorm, _, _ = moe.route(h, router, 3, True)
    np.testing.assert_allclose(renorm.sum(-1), 1.0, rtol=1e-6)


def test_whole_layer_gradient_reaches_the_router(small_tiles):
    """Through the combine's weights: router, softmax, top-k, experts,
    against the same mathematics written densely."""
    layer = _layer()
    cfg = dataclasses.replace(OLMOE_TINY, d_model=32, d_expert=16)
    h = jax.random.normal(jax.random.key(8), (64, 32))

    def dense(h, layer):
        probs = jax.nn.softmax(h @ layer["router"], axis=-1)
        kth = jnp.sort(probs, axis=-1)[:, -cfg.experts_per_token]
        weights = jnp.where(probs >= kth[:, None], probs, 0.0)
        ys = jnp.einsum(
            "nef,efd->ned",
            jax.nn.silu(jnp.einsum("nd,edf->nef", h, layer["w_gate"]))
            * jnp.einsum("nd,edf->nef", h, layer["w_up"]), layer["w_down"])
        return jnp.einsum("ne,ned->nd", weights, ys)

    f = lambda h, l: jnp.sum(jnp.sin(moe.moe_ffn(h, l, cfg)[0]))
    g = lambda h, l: jnp.sum(jnp.sin(dense(h, l)))
    with jax.default_matmul_precision("highest"):
        got = jax.grad(f, (0, 1))(h, layer)
        want = jax.grad(g, (0, 1))(h, layer)
    assert np.abs(np.asarray(want[1]["router"])).max() > 1e-3
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_router_losses_against_hand_values():
    """E = 4, k = 2, three tokens.  Token logits chosen so that softmax
    is exactly (1/2, 1/4, 1/8, 1/8), its permutation, and uniform."""
    ln2 = np.log(2.0)
    logits = jnp.asarray([[3 * ln2, 2 * ln2, ln2, ln2],
                          [ln2, ln2, 2 * ln2, 3 * ln2],
                          [0.0, 0.0, 0.0, 0.0]], jnp.float32)
    _, top_i, stats = moe.route(jnp.eye(3, dtype=jnp.float32), logits, 2,
                                False)
    np.testing.assert_array_equal(top_i, [[0, 1], [3, 2], [0, 1]])
    balance, z = moe.router_losses([stats], 3)
    # f = assignments per token = (2, 2, 1, 1) / 3;
    # P = mean probability = (7/8, 3/4, 5/8, 3/4) / 3.
    f = np.array([2, 2, 1, 1]) / 3
    p = np.array([0.5 + 0.125 + 0.25, 0.25 + 0.125 + 0.25,
                  0.125 + 0.25 + 0.25, 0.125 + 0.5 + 0.25]) / 3
    np.testing.assert_allclose(balance, 4 * np.sum(f * p), rtol=1e-6)
    # logsumexp: ln 16, ln 16, ln 4.
    np.testing.assert_allclose(
        z, (2 * np.log(16.0) ** 2 + np.log(4.0) ** 2) / 3, rtol=1e-6)
    # Two layers together: sums add, tokens add.
    both, z2 = moe.router_losses([stats, stats], 6)
    np.testing.assert_allclose([both, z2], [balance, z], rtol=1e-6)
    # A uniform router over E experts reads k.
    _, _, flat = moe.route(jnp.ones((8, 1)), jnp.zeros((1, 4)), 2, False)
    np.testing.assert_allclose(moe.router_losses([flat], 8)[0], 2.0,
                               rtol=1e-6)


# --- the block, against the plain reference --------------------------------

def test_rotary_matches_the_reference_formula():
    x = jax.random.normal(jax.random.key(3), (2, 16, 2, 32))
    got = attention.rotary(x, jnp.arange(5, 21), 10000.0)
    # The reference rotates one sequence from position 0: take rows 5..20
    # of a longer one.
    longer = jnp.concatenate([jnp.zeros((5, 2, 32)), x[0]], axis=0)
    np.testing.assert_allclose(got[0], reference._rope(longer, 10000.0)[5:],
                               atol=1e-5)


# --- the train step on one and on four devices ------------------------------


def test_bf16_train_step_reads_the_float32_masters_on_four_devices(hvd):
    """bf16 compute over float32 masters through the real step (the
    kernels fetch and round the masters themselves, inside
    ``shard_map``): its loss is the whole batch's bf16 loss, and the
    expert leaves move."""
    from horovod_tpu.topology import build_mesh

    cfg = dataclasses.replace(OLMOE_TINY, dtype=jnp.bfloat16)
    mesh = build_mesh(axes=("data",), devices=jax.devices()[:4])
    optimizer = optax.sgd(0.1)
    step, _, _ = tfm.make_train_step(cfg, optimizer, mesh,
                                     attention="local", donate=False)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens, labels = _batch(cfg, batch=8)
    new, _, loss = step(params, optimizer.init(params), tokens, labels)
    # Per-shard router statistics make the shards' mean another function
    # than the whole batch's loss only in the third digit of the 1% term.
    want = tfm.loss_fn(params, tokens, labels, cfg, attention="local")
    np.testing.assert_allclose(loss, want, rtol=3e-3)
    for name in moe.EXPERT_LEAVES:
        after, before = new["layers"][-1][name], params["layers"][-1][name]
        assert after.dtype == jnp.float32
        assert np.abs(np.asarray(after - before)).max() > 1e-6, name


# sha256 (16 digits) of the tiny every-expert-held step's StableHLO (no
# locations) as the parent of PR 48 lowered it (jax 0.9.0): the kernels of
# a share's sum (ops/moe_rows.py) stand in ``_head_ffn`` alone, and a chip
# that holds every expert runs ``_every_slot_ffn``, which that PR did not
# edit.
EVERY_EXPERT_HELD_STEP = {"float32": "838e0818a4c51936",
                          "bfloat16": "c6dcd4f03937ff4a"}


@pytest.mark.parametrize("dtype", list(EVERY_EXPERT_HELD_STEP))
def test_every_expert_held_lowers_the_step_it_did(hvd, dtype):
    import hashlib

    from horovod_tpu.topology import build_mesh

    cfg = dataclasses.replace(OLMOE_TINY, dtype=jnp.dtype(dtype))
    assert cfg.held_experts == cfg.n_experts
    mesh = build_mesh(axes=("data",), devices=jax.devices()[:4])
    optimizer = optax.sgd(0.1)
    step, _, _ = tfm.make_train_step(cfg, optimizer, mesh,
                                     attention="local", donate=False)
    params = tfm.init_abstract(cfg)
    tokens = jax.ShapeDtypeStruct((8, 16), jnp.int32)
    text = step.lower(params, jax.eval_shape(optimizer.init, params), tokens,
                      tokens).as_text()
    assert "moe_row" not in text
    assert (hashlib.sha256(text.encode()).hexdigest()[:16]
            == EVERY_EXPERT_HELD_STEP[dtype])


def test_sequence_axis_offsets_the_rotary_positions(hvd):
    """Two sequence shards (ring attention) see positions 0..T/2-1 and
    T/2..T-1; the loss is the single-device loss."""
    from horovod_tpu.topology import build_mesh

    cfg = OLMOE_TINY
    mesh = build_mesh(axes=("data", "seq"), shape=(2, 2),
                      devices=jax.devices()[:4])
    optimizer = optax.sgd(0.1)
    step, _, _ = tfm.make_train_step(cfg, optimizer, mesh, seq_axis="seq",
                                     attention="ring", donate=False)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens, labels = _batch(cfg)
    _, _, loss = step(params, optimizer.init(params), tokens, labels)
    want = tfm.loss_fn(params, tokens, labels, cfg, attention="local")
    np.testing.assert_allclose(loss, want, rtol=F32_RTOL)


DENSE_NEW = dataclasses.replace(
    OLMOE_TINY, n_experts=0, experts_per_token=0, d_expert=0,
    router_aux_coef=0.0, router_z_coef=0.0, d_ff=96, qk_norm=False)


def test_model_axis_runs_rotary_swiglu_and_the_untied_head(hvd):
    from jax.sharding import PartitionSpec as P
    from horovod_tpu.topology import build_mesh

    cfg = DENSE_NEW
    mesh = build_mesh(axes=("data", "model"), shape=(2, 2),
                      devices=jax.devices()[:4])
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens, _ = _batch(cfg)
    sharded = jax.jit(jax.shard_map(
        lambda p, t: tfm.forward(p, t, cfg, model_axis="model",
                                 attention="local"),
        mesh=mesh, in_specs=(tfm.param_specs(cfg, "model"), P("data")),
        out_specs=P("data"), check_vma=False))
    want = tfm.forward(params, tokens, cfg, attention="local")
    np.testing.assert_allclose(sharded(params, tokens), want, atol=2e-4)


def test_decode_runs_qk_norm_swiglu_and_the_untied_head(hvd):
    cfg = dataclasses.replace(DENSE_NEW, positions="learned", qk_norm=True)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens, _ = _batch(cfg, batch=2, seq=8)
    want = tfm.forward(params, tokens, cfg, attention="local")
    cache = tfm.init_kv_cache(cfg, 2, 8)
    for pos in range(8):
        logits, cache = tfm.decode_step(params, tokens[:, pos], cache, pos,
                                        cfg)
        np.testing.assert_allclose(logits, want[:, pos], atol=2e-4)


# --- refusals: never a silent fall back to the dense block -------------------


@pytest.mark.parametrize("field,value", [
    ("positions", "rope"), ("qk_norm", True), ("tie_embeddings", False),
    ("mlp", "swiglu")])
def test_pipelined_step_refuses_every_new_field(hvd, field, value):
    from horovod_tpu.topology import build_mesh

    cfg = dataclasses.replace(
        tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                              n_layers=2, d_ff=64, max_seq=16),
        **{field: value})
    mesh = build_mesh(axes=("data", "pipe"), shape=(2, 2),
                      devices=jax.devices()[:4])
    with pytest.raises(NotImplementedError, match=field):
        tfm.make_train_step_pipelined(cfg, optax.sgd(0.1), mesh)


# --- the default is today's GPT-2 block --------------------------------------

def test_default_config_is_todays_gpt2_block(hvd):
    from horovod_tpu.topology import build_mesh

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_seq=16)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    assert sorted(params) == ["embed", "layers", "ln_f_scale", "pos"]
    assert sorted(params["layers"][0]) == [
        "ln1_scale", "ln2_scale", "w1", "w2", "wk", "wo", "wq", "wv"]
    specs = tfm.param_specs(cfg, None)
    assert (jax.tree_util.tree_structure(specs, is_leaf=lambda x: x is None)
            .num_leaves == len(jax.tree_util.tree_leaves(params)))
    mesh = build_mesh(axes=("data",), devices=jax.devices()[:4])
    optimizer = optax.sgd(0.1)
    step, _, _ = tfm.make_train_step(cfg, optimizer, mesh, attention="local")
    tokens = jax.ShapeDtypeStruct((8, 16), jnp.int32)
    text = step.lower(tfm.init_abstract(cfg),
                      jax.eval_shape(optimizer.init, tfm.init_abstract(cfg)),
                      tokens, tokens).as_text()
    # No rotary, no QK-norm, no routing in the lowered step.
    for absent in ("sine", "cosine", "stablehlo.sort", "top_k"):
        assert absent not in text, absent
    moe_text = jax.jit(lambda p, t: tfm.loss_fn(
        p, t, t, OLMOE_TINY, attention="local")).lower(
        tfm.init_abstract(OLMOE_TINY), tokens).as_text()
    for present in ("sine", "cosine", "stablehlo.sort", "top_k"):
        assert present in moe_text, present


def test_rows_computed_over_needed_gauge_is_the_static_worst_case(hvd):
    from horovod_tpu import telemetry

    telemetry.reset_for_tests()
    telemetry.configure(True)
    try:
        tokens = jax.ShapeDtypeStruct((4, 32), jnp.int32)
        jax.eval_shape(lambda p, t: tfm.loss_fn(
            p, t, t, OLMOE_TINY, attention="local"),
            tfm.init_abstract(OLMOE_TINY), tokens)
        # 256 assignments, two sub-tiles of 128, over 8 experts: each but
        # the first may start inside one.  (256 + 7 x 128) / 256.
        assert ('hvd_moe_gmm_rows_computed_over_needed{bound="worst"} 4.5'
                in telemetry.render_prometheus())
        # The olmoe cell: (65536 + 63 x 128) / 65536.
        moe.record_assignments(0, 65536, 64)
        assert ('hvd_moe_gmm_rows_computed_over_needed{bound="worst"} '
                '1.123046875' in telemetry.render_prometheus())
        doc = _metrics_doc()
        assert "`hvd_moe_gmm_rows_computed_over_needed`" in doc
        assert "matmul_rows" in doc
    finally:
        telemetry.reset_for_tests()


def _bf16_copies(layer):
    """What ``experts_ffn`` handed the kernels before: a cast of every
    expert leaf."""
    return tuple(layer[name].astype(jnp.bfloat16)
                 for name in moe.EXPERT_LEAVES)


def _metrics_doc():
    return open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "metrics.md")).read()


def _expert_leaf_converts(jaxpr, shapes, dtype, scope):
    """Converts to ``dtype`` of an array of one of ``shapes`` traced
    under ``scope``, anywhere in ``jaxpr``."""
    for eqn in _eqns(jaxpr):
        if (eqn.primitive.name == "convert_element_type"
                and eqn.outvars[0].aval.shape in shapes
                and eqn.outvars[0].aval.dtype == dtype
                and scope in str(eqn.source_info.name_stack)):
            yield eqn


def test_step_holds_no_compute_dtype_copy_of_an_expert_leaf(hvd,
                                                            monkeypatch):
    """float32 masters, bf16 compute: nothing under ``mlp/moe_experts``
    of the train step converts an [experts, K, N] array to bf16 (the
    kernels round in VMEM), forward or backward."""
    from horovod_tpu.topology import build_mesh

    cfg = dataclasses.replace(OLMOE_TINY, dtype=jnp.bfloat16)
    mesh = build_mesh(axes=("data",), devices=jax.devices()[:1])
    optimizer = optax.sgd(0.1)
    tokens = jax.ShapeDtypeStruct((4, 32), jnp.int32)
    params = tfm.init_abstract(cfg)
    shapes = {params["layers"][0][name].shape
              for name in moe.EXPERT_LEAVES}
    assert shapes == {(8, 64, 32), (8, 32, 64)}

    def copies():
        step, _, _ = tfm.make_train_step(cfg, optimizer, mesh,
                                         attention="local", donate=False)
        jaxpr = jax.make_jaxpr(step)(
            params, jax.eval_shape(optimizer.init, params), tokens, tokens)
        return list(_expert_leaf_converts(jaxpr.jaxpr, shapes, jnp.bfloat16,
                                          "moe_experts"))

    assert not copies()
    # The search finds the copies of a layer that does cast its leaves.
    monkeypatch.setattr(moe, "_expert_operands", _bf16_copies)
    assert len(copies()) >= 3 * cfg.n_layers


@pytest.mark.parametrize("dtype,cast,expected", [
    (jnp.bfloat16, False, 0), (jnp.float32, False, 0),
    # What the layer did before: 3 x 8 x 64 x 32 parameters at 2 bytes.
    (jnp.bfloat16, True, 98304)],
    ids=("f32_masters_bf16_rows", "f32_masters_f32_rows",
         "a_cast_at_the_call_site_is_counted"))
def test_expert_weight_copy_bytes_gauge(hvd, monkeypatch, dtype, cast,
                                        expected):
    from horovod_tpu import telemetry

    if cast:
        monkeypatch.setattr(moe, "_expert_operands", _bf16_copies)
    cfg = dataclasses.replace(OLMOE_TINY, dtype=dtype)
    telemetry.reset_for_tests()
    telemetry.configure(True)
    try:
        tokens = jax.ShapeDtypeStruct((4, 32), jnp.int32)
        jax.eval_shape(lambda p, t: tfm.loss_fn(
            p, t, t, cfg, attention="local"), tfm.init_abstract(cfg), tokens)
        text = telemetry.render_prometheus()
        for layer in (0, 1):
            assert (f'hvd_moe_expert_weight_copy_bytes{{layer="{layer}"}} '
                    f'{expected}' in text), text
        assert "`hvd_moe_expert_weight_copy_bytes`" in _metrics_doc()
    finally:
        telemetry.reset_for_tests()
