"""The router's choice as a membership mask (``ops/router_choice.py``) in
the Pallas interpreter against ``lax.top_k``'s own set, and the dense
form of the sigmoid router that reads it
(``models/moe.route_sigmoid_held``)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.models import moe
from horovod_tpu.ops import router_choice as op

BF16, F32 = jnp.bfloat16, jnp.float32
N = 256


def _kernel(keys, k):
    """The kernel in the interpreter, also at widths Mosaic would not
    take (16 experts: the interpreter turns any block)."""
    return op._choose_call(keys, k=k, interpret=True)


def _top_k_set(keys, k):
    """``lax.top_k``'s set, by a loop over its indices."""
    _, top_i = lax.top_k(keys, k)
    mask = np.zeros(keys.shape, np.float32)
    for row, chosen in enumerate(np.asarray(top_i)):
        mask[row, chosen] = 1.0
    return mask


SHAPES = [(512, 22), (16, 6), (128, 8)]


@pytest.mark.parametrize("experts,k", SHAPES)
def test_the_mask_is_top_ks_set(experts, k):
    keys = jax.random.normal(jax.random.PRNGKey(experts), (N, experts)) * 3
    want = _top_k_set(keys, k)
    np.testing.assert_array_equal(_kernel(keys, k), want)
    np.testing.assert_array_equal(op.chosen_xla(keys, k), want)
    assert (want.sum(axis=1) == k).all()


def _tied(kind: str, experts: int, k: int):
    """Rows built so that the ``k``-th largest key has equals."""
    rng = np.random.default_rng(experts + k)
    keys = rng.uniform(0.0, 1.0, (N, experts)).astype(np.float32)
    if kind == "all_equal":
        keys[:] = 0.625
    elif kind == "kth_and_next_equal":
        # The k-th and the (k+1)-th largest are one value, at random
        # places; on every fourth row so are the four after them.
        order = np.argsort(-keys, axis=1)
        rows = np.arange(N)
        for j in range(k, k + 5):
            some = rows[::4] if j > k else rows
            keys[some, order[some, j]] = keys[some, order[some, k - 1]]
    elif kind == "saturated":
        # A sigmoid of a large logit is exactly 1.0, for more than k.
        keys = np.asarray(jax.nn.sigmoid(jnp.asarray(
            rng.normal(0, 40, (N, experts)).astype(np.float32))))
        assert ((keys == 1.0).sum(axis=1) > k).any()
    elif kind == "negative":
        # Scores under a bias that takes them below zero, in steps that
        # tie; -0.0 is left out (the module's docstring).
        keys = np.round(keys * 8) / 8 - 2.0
    elif kind == "minus_inf":
        # Padding: fewer than k finite keys on some rows, none on one.
        keys[:, k + 3:] = -np.inf
        keys[1::2, k - 2:] = -np.inf
        keys[5] = -np.inf
    return jnp.asarray(keys)


@pytest.mark.parametrize("experts,k", SHAPES)
@pytest.mark.parametrize("kind", ["all_equal", "kth_and_next_equal",
                                  "saturated", "negative", "minus_inf"])
def test_of_equal_keys_the_lowest_index_wins_as_top_ks_does(kind, experts, k):
    keys = _tied(kind, experts, k)
    want = _top_k_set(keys, k)
    got = np.asarray(_kernel(keys, k))
    np.testing.assert_array_equal(got, want)
    np.testing.assert_array_equal(op.chosen_xla(keys, k), want)
    assert (got.sum(axis=1) == k).all()
    if kind == "all_equal":
        assert (got[:, :k] == 1).all()


def test_chosen_runs_the_kernel_where_takes_accepts_and_carries_no_gradient():
    keys = jax.random.normal(jax.random.PRNGKey(3), (N, 128))
    assert op.takes(keys)
    traced = str(jax.make_jaxpr(lambda x: op.chosen(x, 8))(keys))
    assert traced.count("pallas_call") == 1 and "moe_choose" in traced
    np.testing.assert_array_equal(op.chosen(keys, 8), _top_k_set(keys, 8))
    g = jax.grad(lambda x: jnp.sum(op.chosen(x, 8) * x))(keys)
    np.testing.assert_array_equal(g, op.chosen(keys, 8))   # d(m x)/dx = m


@pytest.mark.parametrize("why,keys", [
    ("bf16 keys", jnp.zeros((N, 128), BF16)),
    ("experts that are no whole lane group", jnp.zeros((N, 64), F32)),
    ("tokens that are no whole lane group", jnp.zeros((N - 8, 128), F32)),
])
def test_takes_refuses(why, keys):
    assert op.takes(jnp.zeros((N, 128), F32))
    assert op.takes(jnp.zeros((128, 512), F32))
    assert not op.takes(keys), why


def test_takes_refuses_the_interpreter_under_check_vma(hvd):
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.topology import build_mesh

    mesh = build_mesh(axes=("data",), devices=jax.devices()[:2])
    seen = {}
    for check in (True, False):
        def body(keys):
            seen[check] = op.takes(keys)
            return keys
        jax.eval_shape(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                                     out_specs=P("data"), check_vma=check),
                       jnp.zeros((2 * N, 128), F32))
    assert seen == {True: False, False: True}


# --- the dense form of the router on a narrow share --------------------------

def _router(experts=16, d=32, seed=0):
    k_h, k_w = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(k_h, (N, d)),
            jax.random.normal(k_w, (d, experts)) * d ** -0.5)


@pytest.mark.parametrize("kernel", (False, True), ids=["top_k", "kernel"])
def test_the_bias_moves_the_choice_and_not_the_weights(kernel):
    h, w = _router()
    first, held, k, scale = 4, 4, 6, 5.0
    plain = moe.route_sigmoid_held(h, w, jnp.zeros((16,)), k, scale, first,
                                   held, kernel)
    bias = jnp.zeros((16,)).at[5].set(100.0).at[6].set(-100.0)
    slot_w, slot_e, rows = moe.route_sigmoid_held(h, w, bias, k, scale,
                                                  first, held, kernel)
    # Expert 5 (slot 1) is every token's, expert 6 (slot 2) nobody's.
    assert rows[1] == N and rows[2] == 0
    assert (slot_e[:, 1] == 1).all() and (slot_e[:, 2] == held).all()
    assert (slot_w[:, 2] == 0).all()
    # The weights are the scores' over the sum of the chosen scores: the
    # bias is in neither.
    scores = jax.nn.sigmoid(jnp.dot(h, w, precision="highest"))
    _, top_i = lax.top_k(scores + bias, k)
    total = jnp.take_along_axis(scores, top_i, axis=1).sum(axis=1)
    np.testing.assert_allclose(slot_w[:, 1], scale * scores[:, 5] / total,
                               rtol=1e-5)
    assert not np.array_equal(plain[1], slot_e)
    g = jax.grad(lambda b: jnp.sum(moe.route_sigmoid_held(
        h, w, b, k, scale, first, held, kernel)[0] ** 2))(bias)
    assert float(jnp.abs(g).max()) == 0.0
