"""The short-convolution Pallas kernels (``ops/short_conv.py``) in the
Pallas interpreter on the CPU: the same code Mosaic compiles for the chip
(``tests/test_flash_compile.py`` holds that it does).

The oracle is what the mixers ran before the kernels and run where the
kernels cannot: ``silu(causal_conv(...))`` of
``models/linear_attention.py`` with the bias, the per-head L2 norm and
the one rounding after it as ``jax.numpy``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import linear_attention as la
from horovod_tpu.models import mamba2
from horovod_tpu.ops import gated_delta_rule as gdn
from horovod_tpu.ops import short_conv as op
from horovod_tpu.telemetry import scopes
from tests.test_hybrid_lm import HYBRID_TINY
from tests.test_ssm_moe_lm import NEMOTRON_TINY

# Float32 against float32: the kernels' sigmoid is the reciprocal unit's
# estimate with a Newton step (the interpreter models the estimate at
# bfloat16's precision, squared by the step), everything else the
# oracle's operations in the oracle's order.
F32_REL = 2e-5
NAMES = ("x", "taps", "bias")


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _oracle(x, w, bias=None, *, widths=None, head_dim=None, norm_scale=None):
    """The mixers' ``jax.numpy`` form, in ``short_conv``'s signature."""
    bsz, t, channels = x.shape
    y = la.causal_conv(x, w)
    y = jax.nn.silu(y if bias is None else y + bias)
    if head_dim:
        y = y.reshape(bsz, t, channels // head_dim, head_dim)
        if norm_scale is not None:
            y = la._l2norm(y) * norm_scale
        return gdn.head_major(y.astype(x.dtype))
    y = y.astype(x.dtype)
    if widths is None:
        return y
    cuts = np.cumsum((0,) + tuple(widths))
    return tuple(y[..., a:b] for a, b in zip(cuts[:-1], cuts[1:]))


def _inputs(t, channels, taps, bias, batch=2, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.key(seed + t + channels), 3)
    bound = taps ** -0.5
    x = jax.random.normal(ks[0], (batch, t, channels)).astype(dtype)
    w = jax.random.uniform(ks[1], (taps, channels), jnp.float32, -bound,
                           bound)
    b = (jax.random.uniform(ks[2], (channels,), jnp.float32, -bound, bound)
         if bias else None)
    return x, w, b


def _with_grads(f, x, w, b, **kw):
    out, pull = jax.vjp(lambda *a: f(*a, **kw), x, w, b)
    dy = jax.tree.map(
        lambda o: jax.random.normal(jax.random.key(9), o.shape).astype(
            o.dtype), out)
    return jax.tree.leaves(out), pull(dy)


def _assert_matches(got, want, rel=F32_REL):
    for a, b in zip(got[0], want[0]):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert _rel(a, b) <= rel
    for name, a, b in zip(NAMES, got[1], want[1]):
        if b is None:
            assert a is None, name
            continue
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a, b) <= rel, name


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 32 tokens in chunks of 16: a test's few hundred tokens are
    several tiles of several chunks, as the benchmark's thousands are."""
    monkeypatch.setattr(op, "TILE", 32)
    monkeypatch.setattr(op, "ROWS", 16)
    monkeypatch.setattr(op, "HEAD_ROWS", 16)


# (head width, heads): a head inside a register, heads that straddle
# registers (96: Olmo-Hybrid's keys; 192: its values), a last slab that is
# not a whole one (5 x 96 = 384 + 96).
HEADS = {"d96_h5": (96, 5), "d128_h3": (128, 3), "d192_h3": (192, 3),
         "d24_h2": (24, 2)}
LENGTHS = {"one_tile": 32, "five_tiles": 160}


@pytest.mark.parametrize("t", LENGTHS.values(), ids=LENGTHS.keys())
@pytest.mark.parametrize("norm", [None, 1.0, 0.25],
                         ids=["no_norm", "norm", "norm_scaled"])
@pytest.mark.parametrize("head", HEADS.values(), ids=HEADS.keys())
def test_head_major_matches_the_jax_numpy_form(small_tiles, head, norm, t):
    """Forward and the gradients of ``x`` and the taps, written heads
    apart, with and without a head's L2 norm; the halo across tile and
    chunk boundaries, the first rows against the zero start."""
    d, h = head
    x, w, _ = _inputs(t, d * h, 4, False)
    kw = dict(head_dim=d, norm_scale=norm)
    got = jax.jit(lambda *a: _with_grads(op.short_conv, *a, **kw))(x, w, None)
    want = jax.jit(lambda *a: _with_grads(_oracle, *a, **kw))(x, w, None)
    assert got[0][0].shape == (2 * h, t, d)
    _assert_matches(got, want)


@pytest.mark.parametrize("t", LENGTHS.values(), ids=LENGTHS.keys())
@pytest.mark.parametrize("taps,bias,widths", [
    (4, True, (256, 128, 128)), (4, False, None), (2, True, None),
    (7, False, (384, 128)), (1, True, (128,))],
    ids=["k4_bias_xBC", "k4", "k2_bias", "k7_two", "k1_bias"])
def test_token_major_matches_the_jax_numpy_form(small_tiles, taps, bias,
                                                widths, t):
    """One output or several through as many output specs, with the bias
    and without, at other numbers of taps than four."""
    x, w, b = _inputs(t, sum(widths) if widths else 256, taps, bias)
    kw = dict(widths=widths)
    got = jax.jit(lambda *a: _with_grads(op.short_conv, *a, **kw))(x, w, b)
    want = jax.jit(lambda *a: _with_grads(_oracle, *a, **kw))(x, w, b)
    assert len(got[0]) == (len(widths) if widths else 1)
    _assert_matches(got, want)


def test_the_first_rows_see_zeros_and_a_tile_sees_the_one_before(small_tiles):
    """Token 0's output is ``silu(w_last x_0)``; a tile's first row reads
    the three rows before it, whatever tile they are in."""
    x, w, _ = _inputs(96, 128, 4, False, batch=1)
    y = op.short_conv(x, w)
    np.testing.assert_allclose(y[0, 0], jax.nn.silu(w[3] * x[0, 0]),
                               rtol=1e-5, atol=1e-6)
    for row in (32, 64):                 # a tile's first row
        want = jax.nn.silu(sum(w[j] * x[0, row - 3 + j] for j in range(4)))
        np.testing.assert_allclose(y[0, row], want, rtol=1e-5, atol=1e-6)
    # Rows before a tile do not leak into the one before it.
    x2 = x.at[0, 64:].set(0.0)
    np.testing.assert_array_equal(op.short_conv(x2, w)[0, :64], y[0, :64])


@pytest.mark.parametrize("kw", [
    dict(head_dim=96, norm_scale=96 ** -0.5), dict(head_dim=192),
    dict(widths=(256, 128, 128))], ids=["q", "v", "xBC"])
def test_bfloat16_operands_are_no_further_from_float32_than_the_jax_numpy_form(
        small_tiles, kw):
    """The kernels round where ``causal_conv``'s docstring says, once, at
    the end: against the float32 oracle they read no more than the
    ``jax.numpy`` form on the same bfloat16 operands."""
    channels = 512 if "widths" in kw else 2 * kw["head_dim"]
    x, w, b = _inputs(96, channels, 4, "widths" in kw, dtype=jnp.bfloat16)
    want = _with_grads(_oracle, x.astype(jnp.float32), w, b, **kw)
    got = _with_grads(op.short_conv, x, w, b, **kw)
    xla = _with_grads(_oracle, x, w, b, **kw)
    for a, c, r in zip(got[0] + list(got[1]), xla[0] + list(xla[1]),
                       want[0] + list(want[1])):
        if r is None:
            continue
        assert a.dtype == c.dtype
        assert _rel(a.astype(jnp.float32), r) <= 1.05 * _rel(
            c.astype(jnp.float32), r) + 1e-5


@pytest.mark.parametrize("t,channels,taps,kw,tile", [
    (16384, 2880, 4, dict(head_dim=96), 512),
    (16384, 5760, 4, dict(head_dim=192), 512),
    (8192, 10240, 4, dict(widths=(8192, 1024, 1024)), 256),
    (96, 256, 4, {}, 32), (16, 128, 1, {}, 16), (8, 128, 4, {}, None),
    (100, 128, 4, {}, None),                       # not whole halos
    (64, 128, 10, {}, None), (64, 128, 9, {}, 64),  # taps past the carry
    (64, 192, 4, {}, None), (64, 192, 4, dict(head_dim=96), 64),
    (64, 256, 4, dict(widths=(128, 64, 64)), None),
    (64, 256, 4, dict(widths=(128, 256)), None),    # do not add up
    (64, 200, 4, dict(head_dim=96), None),          # not whole heads
    (64, 2 * 136, 4, dict(head_dim=136), None)],    # lcm(136, 128) > a slab
    ids=lambda v: str(v).replace(" ", "") if not isinstance(v, dict)
    else "_".join(f"{k}{x}" for k, x in v.items()) or "plain")
def test_tiles(t, channels, taps, kw, tile):
    assert op.tiles(t, channels, taps, **kw) == tile


def test_a_tile_is_what_the_vmem_estimate_holds(monkeypatch):
    """A width four times Nemotron's takes a smaller tile before it is
    refused; float32 operands take twice bfloat16's room."""
    assert op.tiles(8192, 40960, 4) == 64
    assert op.tiles(8192, 40960, 4, itemsize=4) == 32
    monkeypatch.setattr(op, "VMEM_LIMIT", 2 ** 20)
    assert op.tiles(8192, 40960, 4) is None


def test_the_path_is_read_from_the_operand(hvd):
    """The kernels wherever they can run; ``causal_conv`` for sizes they
    do not take and, on the CPU, inside ``shard_map(check_vma=True)``,
    where the interpreter's loops do not type."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.topology import build_mesh

    x = jnp.zeros((2, 64, 256))
    assert op.takes(x, 4)
    assert op.takes(x, 4, head_dim=128)
    assert op.takes(x, 4, widths=(128, 128))
    assert op.takes(x, 4, head_dim=96, channels=2880)
    assert not op.takes(x, 4, head_dim=96)
    assert not op.takes(x[:, :60], 4)
    assert not op.takes(x[0], 4)
    mesh = build_mesh(axes=("data",), devices=jax.devices()[:2])
    seen = {}

    def inside(x, check):
        seen[check] = (op.takes(x, 4), la.conv_path(x, HYBRID_TINY),
                       mamba2.conv_path(x, _WIDE_SSM))
        return x

    for check in (True, False):
        jax.eval_shape(jax.shard_map(
            lambda x: inside(x, check), mesh=mesh, in_specs=P("data"),
            out_specs=P("data"), check_vma=check), x)
    assert seen == {True: (False, "xla", "xla"),
                    False: (True, "kernel", "kernel")}
    with pytest.raises(ValueError, match="do not take"):
        op.short_conv(x[:, :60], jnp.zeros((4, 256)))
    with pytest.raises(ValueError, match="head width"):
        op.short_conv(x, jnp.zeros((4, 256)), norm_scale=1.0)


# A Mamba-2 layer whose convolution's three outputs are whole lanes (the
# tiny configuration's are 64 wide: it runs causal_conv).
_WIDE_SSM = dataclasses.replace(NEMOTRON_TINY, ssm_heads=8, ssm_head_dim=16,
                                ssm_state=128, ssm_groups=1, ssm_chunk=32)


def _mixer_grads(module, cfg, t, path, monkeypatch):
    ks = jax.random.split(jax.random.key(0), 3)
    layer = module.init_layer(
        ks[0], cfg, lambda k, shape: jax.random.normal(k, shape)
        * shape[0] ** -0.5)
    u = jax.random.normal(ks[1], (2, t, cfg.d_model))
    dy = jax.random.normal(ks[2], u.shape)
    if path == "xla":
        monkeypatch.setattr(module, "conv_path", lambda x, cfg: "xla")
    assert module.conv_path(u, cfg) == path
    traced = str(jax.make_jaxpr(lambda l, u: module.mixer(u, l, cfg))(
        layer, u))
    assert (scopes.SHORT_CONV_FWD in traced) is (path == "kernel")
    with jax.default_matmul_precision("highest"):
        return jax.grad(lambda l, u: jnp.sum(module.mixer(u, l, cfg) * dy),
                        (0, 1))(layer, u)


@pytest.mark.parametrize("module,cfg,t", [
    (la, HYBRID_TINY, 128), (la, HYBRID_TINY, 64), (mamba2, _WIDE_SSM, 64)],
    ids=["linear_attention", "linear_attention_one_block", "mamba2"])
def test_each_mixer_calls_the_kernel_where_it_runs(module, cfg, t):
    """The whole mixer through the kernels and through ``causal_conv``:
    one layer's output and the gradients of all of its leaves (64 tokens
    are one block of the delta rule's recurrence, which its kernels do not
    take: the head-major operands go back to token-major there)."""
    with pytest.MonkeyPatch.context() as patch:
        got = _mixer_grads(module, cfg, t, "kernel", patch)
    with pytest.MonkeyPatch.context() as patch:
        want = _mixer_grads(module, cfg, t, "xla", patch)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert _rel(a, b) <= 5e-5


def test_a_mixer_runs_causal_conv_where_the_kernels_do_not():
    """The tiny Mamba-2 configuration's outputs are 64 lanes wide, and 40
    tokens are not whole halos: both mixers trace no kernel of this
    module."""
    layer = jax.eval_shape(lambda: mamba2.init_layer(
        jax.random.key(0), NEMOTRON_TINY, lambda k, shape: jnp.zeros(shape)))
    u = jax.ShapeDtypeStruct((1, 64, NEMOTRON_TINY.d_model), jnp.float32)
    assert mamba2.conv_path(u, NEMOTRON_TINY) == "xla"
    traced = jax.make_jaxpr(
        lambda l, u: mamba2.mixer(u, l, NEMOTRON_TINY))(layer, u)
    assert scopes.SHORT_CONV_FWD not in str(traced)
    x = jax.ShapeDtypeStruct((1, 40, HYBRID_TINY.d_model), jnp.float32)
    assert la.conv_path(x, HYBRID_TINY) == "xla"


@pytest.mark.parametrize("kind", ["linear_attention", "mamba2"])
def test_layers_share_one_traced_kernel_a_kind(monkeypatch, kind):
    """Forward, recomputed forward and backward of every layer go through
    the same jitted calls: a mixer's kernel bodies are traced once a kind,
    a set of static arguments (q and k differ in the norm's scale, v has
    none) and a tracing context (the forward as ``jax.checkpoint``'s
    primal and under the differentiation rule), whatever the depth."""
    traced = {"fwd": 0, "bwd": 0}

    def counting(which, kernel):
        def body(*refs, **kw):
            traced[which] += 1
            return kernel(*refs, **kw)
        return body

    monkeypatch.setattr(op, "_fwd_kernel", counting("fwd", op._fwd_kernel))
    monkeypatch.setattr(op, "_bwd_kernel", counting("bwd", op._bwd_kernel))
    # Shapes no other test has: nothing of this is in the jit caches.
    if kind == "mamba2":
        x, w, b = _inputs(48, 384, 4, True, batch=1)

        def layer(x, w, b):
            out = op.short_conv(x, w, b, widths=(128, 128, 128))
            return jnp.concatenate(out, axis=-1)
        calls = 1
    else:
        x, w, b = _inputs(48, 144, 4, True, batch=1)

        def layer(x, w, b):
            q = op.short_conv(x, w, head_dim=48, norm_scale=48 ** -0.5)
            k = op.short_conv(x, w, head_dim=48, norm_scale=1.0)
            v = op.short_conv(x, w, head_dim=48)
            return x + b + gdn.token_major(q + k + v, 1).reshape(x.shape)
        calls = 3

    def three_layers(x, w, b):
        for _ in range(3):
            x = jax.checkpoint(layer)(x, w, b)
        return jnp.sum(x)

    jaxpr = str(jax.make_jaxpr(jax.grad(three_layers, (0, 1, 2)))(x, w, b))
    assert traced == {"fwd": 2 * calls, "bwd": calls}
    assert scopes.SHORT_CONV_FWD in jaxpr and scopes.SHORT_CONV_BWD in jaxpr


def test_the_counter_says_which_path_was_traced(hvd):
    """``hvd_short_conv_rows_total``: batch x T a convolution (three in a
    linear-attention layer, one in a Mamba-2 layer), labelled where the
    path is chosen."""
    from horovod_tpu import telemetry

    telemetry.reset_for_tests()
    telemetry.configure(True)
    try:
        x = jnp.zeros((2, 64, 64))
        la.record_blocks(0, x, HYBRID_TINY)
        la.record_blocks(1, x[:, :40], HYBRID_TINY)
        mamba2.record_chunks(2, x, _WIDE_SSM)
        mamba2.record_chunks(3, x, NEMOTRON_TINY)
        text = telemetry.render_prometheus()
        for line in ('hvd_short_conv_rows_total{layer="0",path="kernel"} 384',
                     'hvd_short_conv_rows_total{layer="1",path="xla"} 240',
                     'hvd_short_conv_rows_total{layer="2",path="kernel"} 128',
                     'hvd_short_conv_rows_total{layer="3",path="xla"} 128'):
            assert line in text, text
    finally:
        telemetry.reset_for_tests()
