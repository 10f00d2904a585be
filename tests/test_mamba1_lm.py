"""The Mamba-1 mixer (``models/mamba1.py``), its selective-scan kernels
(``ops/selective_scan.py``) and its gate's (``ops/mamba_gate.py``), at tiny
sizes on the virtual CPU mesh.

Oracles: the benchmark's plain float32 reference
(``perfbench/reference/mamba1_lm.py``), which shares no code with the
program and walks the scan one token at a time, and the program's own
``jax.numpy`` form (``mamba1.scan_xla``).  The kernels run in the Pallas
interpreter (the same code the chip compiles).  Tolerances, float32
everywhere: 2e-5 relative L2 for the scan alone (the kernels take ``exp``
as a power of two of a rescaled ``A``, one rounding apart), 5e-5 / 2e-4
through the stack.  The gate's kernels against the ``jax.numpy`` line
(``mamba1.gate_xla``): one bfloat16 rounding where bfloat16 leaves, 1e-6
relative L2 where float32 does.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import mamba1
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import mamba_gate, selective_scan
from horovod_tpu.telemetry import scopes
from perfbench.reference import mamba1_lm as reference

# The families every configuration shares, and the table of configurations
# (tests/test_lm_configs.py); those that compile this row's program run
# here, in the row's own file: a file is one worker's chain.
from test_lm_configs import *  # noqa: E402,F401,F403
from test_lm_configs import JAMBA_TINY, rel  # noqa: E402

COSTLY_ROWS = ("jamba",)

SCAN_REL = 2e-5
OPERANDS = ("x", "dt", "a", "b_in", "c_in", "d")


def _operands(t, channels, n, batch=2, seed=0):
    """``(x, dt, a, b_in, c_in, d)`` and a weight for the output; the
    step ``softplus(dt)`` lies around 0.13."""
    k = jax.random.split(jax.random.key(seed), 7)
    x = jax.random.normal(k[0], (batch, t, channels))
    dt = jax.random.normal(k[1], (batch, t, channels)) - 2.0
    a = -jnp.exp(jax.random.normal(k[2], (channels, n)) * 0.5)
    b_in = jax.random.normal(k[3], (batch, t, n))
    c_in = jax.random.normal(k[4], (batch, t, n))
    d = jax.random.normal(k[5], (channels,))
    weight = jax.random.normal(k[6], (batch, t, channels))
    return (x, dt, a, b_in, c_in, d), weight


def _token_by_token(x, dt, a, b_in, c_in, d):
    """The reference's scan, a sequence at a time, with the skip."""
    return jax.vmap(lambda xs, dl, b, c: reference._selective_scan(
        xs, b, c, dl, a, None, None) + d * xs)(
            x, jax.nn.softplus(dt), b_in, c_in)


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 16 tokens: a sequence of 48 is three of them."""
    monkeypatch.setattr(selective_scan, "TILE", 16)


@pytest.mark.parametrize("form", ("kernel", "xla"))
def test_scan_matches_token_by_token_on_every_operand(small_tiles, form):
    """Forward and all six gradients over three tiles of two slabs: the
    state is carried from tile to tile, and the backward recomputes each
    tile from its saved state."""
    operands, weight = _operands(48, 2048, 16)
    assert selective_scan.tiles(48, 2048, 16) == 16
    scan = selective_scan.mamba_scan if form == "kernel" else mamba1.scan_xla
    want = _token_by_token(*operands)
    assert rel(scan(*operands), want) <= SCAN_REL
    loss = lambda f: (lambda *p: jnp.sum(f(*p) * weight))
    got_g = jax.grad(loss(scan), argnums=range(6))(*operands)
    want_g = jax.grad(loss(_token_by_token), argnums=range(6))(*operands)
    for name, got, g in zip(OPERANDS, got_g, want_g):
        assert rel(got, g) <= SCAN_REL, name


def test_kernel_takes_the_model_dtype(small_tiles):
    """``x`` in bfloat16: the wrapper casts it on the way in, and its
    cotangent comes back in bfloat16."""
    (x, *rest), weight = _operands(32, 1024, 8)
    x16 = x.astype(jnp.bfloat16)
    want = mamba1.scan_xla(x16, *rest)
    got = selective_scan.mamba_scan(x16, *rest)
    assert got.dtype == jnp.float32 and rel(got, want) <= SCAN_REL
    dx = jax.grad(lambda v: jnp.sum(selective_scan.mamba_scan(v, *rest)
                                    * weight))(x16)
    assert dx.dtype == jnp.bfloat16


@pytest.mark.parametrize("form", ("kernel", "xla"))
def test_a_large_step_underflows_to_zero_and_not_to_nan(small_tiles, form):
    """A step of 50 to 200 (``softplus`` is the identity there) under
    ``A`` down to -16: ``exp(delta A)`` underflows to an exact 0, the
    state forgets everything, and every value and gradient stays
    finite."""
    (x, dt, a, b_in, c_in, d), weight = _operands(32, 1024, 16)
    dt = 50.0 + 150.0 * jax.nn.sigmoid(dt)
    a = a * 16.0 / jnp.abs(a).max()
    assert float(jnp.exp(dt[..., None] * a).min()) == 0.0
    operands = (x, dt, a, b_in, c_in, d)
    scan = selective_scan.mamba_scan if form == "kernel" else mamba1.scan_xla
    y, grads = jax.value_and_grad(
        lambda *p: jnp.sum(scan(*p) * weight), argnums=range(6))(*operands)
    assert np.isfinite(y)
    for name, g in zip(OPERANDS, grads):
        assert bool(jnp.isfinite(g).all()), name
    assert rel(scan(*operands), _token_by_token(*operands)) <= SCAN_REL


def test_tiles_and_takes():
    """Whole slabs of 1024 channels and whole tiles of whole sublanes; the
    published widths in tiles of 256 inside the VMEM a kernel may use."""
    assert selective_scan.tiles(16384, 5120, 16) == 256
    assert selective_scan.vmem_bytes(256, 16) <= selective_scan.VMEM_LIMIT
    assert selective_scan.tiles(16384, 5000, 16) is None
    assert selective_scan.tiles(20, 1024, 16) is None
    assert selective_scan.tiles(24, 1024, 16) == 24
    published = dataclasses.replace(JAMBA_TINY, mamba_inner=5120,
                                    mamba_state=16)
    assert mamba1.saved_state_bytes(1, 16384, published) == (
        64 * 16 * 5120 * 4)
    x = jnp.zeros((1, 64, 8))
    assert selective_scan.takes(x, 1024, 16)
    assert not selective_scan.takes(x, 128, 16)
    with pytest.raises(ValueError, match="do not take"):
        selective_scan.mamba_scan(*_operands(32, 128, 4)[0])


def test_the_mixer_runs_the_kernels_where_they_take_the_operand():
    """A layer of 1024 inner channels outside ``shard_map``: the scan and
    the convolution are the kernels (interpreted here), named in the
    lowered text, and agree with the ``jax.numpy`` forms of a layer of
    the same leaves that they do not take."""
    cfg = dataclasses.replace(JAMBA_TINY, mamba_inner=1024, mamba_state=8)
    layer = mamba1.PART.init(jax.random.split(jax.random.key(0), 6), cfg)
    u = jax.random.normal(jax.random.key(1), (2, 32, cfg.d_model))
    assert mamba1.scan_path(u, cfg) == mamba1.conv_path(u, cfg) == "kernel"
    text = jax.jit(lambda u: mamba1.mixer(u, layer, cfg)).lower(u).as_text(
        debug_info=True)
    for name in (scopes.MAMBA_SCAN_FWD, scopes.SHORT_CONV_FWD):
        assert name in text
    got = mamba1.mixer(u, layer, cfg)

    def plain(u):
        # The same leaves through the ``jax.numpy`` forms.
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(mamba1, "scan_path", lambda *a: "xla")
            patch.setattr(mamba1, "conv_path", lambda *a: "xla")
            return mamba1.mixer(u, layer, cfg)

    assert rel(got, plain(u)) <= SCAN_REL
    got_g = jax.grad(lambda u: jnp.sum(mamba1.mixer(u, layer, cfg) ** 2))(u)
    assert rel(got_g, jax.grad(lambda u: jnp.sum(plain(u) ** 2))(u)) <= 1e-4


@pytest.fixture
def small_gate_tiles(monkeypatch):
    """The gate's grid steps of 16 tokens: a sequence of 48 is three."""
    monkeypatch.setattr(mamba_gate, "TILE", 16)


def _within_one_bf16_rounding(got, want):
    """``got`` and ``want`` (bfloat16) no further apart than one unit in
    bfloat16's last place of the larger."""
    assert got.dtype == want.dtype == jnp.bfloat16
    got, want = (np.asarray(v, np.float32) for v in (got, want))
    return bool((np.abs(got - want)
                 <= 2.0 ** -7 * np.maximum(np.abs(got), np.abs(want))).all())


@pytest.mark.parametrize("channels", (1024, 2048))
def test_gate_kernels_match_the_jax_numpy_line(small_gate_tiles, channels):
    """Value and both gradients over three tiles with ``z`` in bfloat16:
    ``y`` goes in and ``dy`` comes out float32 in the scan's layout (a
    token's 128-channel groups as rows), the rest token-major."""
    k = jax.random.split(jax.random.key(channels), 3)
    shape = (2, 48, channels)
    y = jax.random.normal(k[0], shape) * 3.0
    z = (jax.random.normal(k[1], shape) * 4.0).astype(jnp.bfloat16)
    weight = jax.random.normal(k[2], shape).astype(jnp.bfloat16)
    assert mamba_gate.tiles(48, channels) == 16
    want = mamba1.gate_xla(y, z)
    got = mamba_gate.mamba_gate(y, z)
    assert _within_one_bf16_rounding(got, want)
    assert rel(got, want) <= 2e-3

    def loss(gate):
        return lambda y, z: jnp.sum(
            (gate(y, z) * weight).astype(jnp.float32))

    dy, dz = jax.grad(loss(mamba_gate.mamba_gate), (0, 1))(y, z)
    want_dy, want_dz = jax.grad(loss(mamba1.gate_xla), (0, 1))(y, z)
    assert dy.dtype == jnp.float32 and rel(dy, want_dy) <= 1e-6
    assert _within_one_bf16_rounding(dz, want_dz)
    assert rel(dz, want_dz) <= 2e-3


def test_gate_kernels_in_float32_and_at_the_extremes(small_gate_tiles):
    """A float32 model dtype (the tiny configurations'), and gates of
    -200 to 200: ``exp`` is held where it stays finite, nothing is NaN,
    and ``silu`` is 0 or the identity there."""
    k = jax.random.split(jax.random.key(7), 2)
    y = jax.random.normal(k[0], (1, 32, 1024))
    z = jax.random.normal(k[1], (1, 32, 1024)) * 2.0
    assert rel(mamba_gate.mamba_gate(y, z), mamba1.gate_xla(y, z)) <= 1e-6
    grads = jax.grad(lambda y, z: jnp.sum(mamba_gate.mamba_gate(y, z) ** 2),
                     (0, 1))(y, z)
    wants = jax.grad(lambda y, z: jnp.sum(mamba1.gate_xla(y, z) ** 2),
                     (0, 1))(y, z)
    for got, want in zip(grads, wants):
        assert rel(got, want) <= 1e-6
    far = jnp.where(z > 0, 200.0, -200.0)
    out, (dy, dz) = jax.value_and_grad(
        lambda y, z: jnp.sum(mamba_gate.mamba_gate(y, z)), (0, 1))(y, far)
    assert np.isfinite(out)
    assert bool(jnp.isfinite(dy).all()) and bool(jnp.isfinite(dz).all())
    assert rel(mamba_gate.mamba_gate(y, far), jnp.where(far > 0, y * far,
                                                        0.0)) <= 1e-6


def test_gate_tiles_takes_and_path():
    """Whole slabs of 1024 channels (``y`` is then whole registers a
    token) and whole sublane tiles of a 16-bit dtype; the published width
    in tiles of 256 inside the VMEM a kernel may use; the tiny
    configuration's 128 channels run the ``jax.numpy`` line; and the gate
    follows the scan to it, whose layout it reads."""
    assert mamba_gate.tiles(16384, 5120) == 256
    assert mamba_gate.vmem_bytes(256, 5120) <= mamba_gate.VMEM_LIMIT
    assert mamba_gate.tiles(16384, 5000) is None
    assert mamba_gate.tiles(24, 1024) is None
    assert mamba_gate.tiles(48, 1024) == 16
    # float32 ``z`` and 16384 channels: a smaller tile fits.
    assert mamba_gate.tiles(16384, 16384, 4, 4) == 64
    u = jnp.zeros((1, 64, 8), jnp.bfloat16)
    assert mamba_gate.takes(u, 1024) and not mamba_gate.takes(u, 128)
    assert not mamba_gate.takes(u[:, :40], 1024)
    assert mamba1.gate_path(u, JAMBA_TINY) == "xla"
    wide = dataclasses.replace(JAMBA_TINY, mamba_inner=1024, mamba_state=8)
    assert mamba1.gate_path(u, wide) == "kernel"
    # 40 tokens: whole sublanes for the scan, no tile of the gate's.
    assert mamba1.scan_path(u[:, :40], wide) == "kernel"
    assert mamba1.gate_path(u[:, :40], wide) == "xla"
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(mamba1, "scan_path", lambda *a: "xla")
        assert mamba1.gate_path(u, wide) == "xla"
    with pytest.raises(ValueError, match="do not take"):
        mamba_gate.mamba_gate(jnp.zeros((1, 32, 128)), jnp.zeros((1, 32, 128)))
    with pytest.raises(ValueError, match="do not take"):
        mamba_gate.mamba_gate(jnp.zeros((1, 32, 8, 128)),
                              jnp.zeros((1, 32, 1024)))


@pytest.mark.parametrize("dtype", (jnp.float32, jnp.bfloat16),
                         ids=("float32", "bfloat16"))
def test_the_mixer_on_the_gates_kernels_against_the_xla_path(dtype):
    """One layer of 1024 inner channels with everything else equal: the
    gate as kernels (named in the lowered text and in the backward's
    trace) against the gate as the ``jax.numpy`` line, value and the
    gradients of the input and of both projections' leaves."""
    cfg = dataclasses.replace(JAMBA_TINY, mamba_inner=1024, mamba_state=8,
                              dtype=dtype)
    layer = mamba1.PART.init(jax.random.split(jax.random.key(0), 6), cfg)
    u = jax.random.normal(jax.random.key(1), (2, 32, cfg.d_model)).astype(
        dtype)
    assert mamba1.gate_path(u, cfg) == "kernel"

    def loss(u, layer):
        return jnp.sum(mamba1.mixer(u, layer, cfg).astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(loss)).lower(u, layer).as_text(debug_info=True)
    for name in (scopes.MAMBA_GATE_FWD, scopes.MAMBA_GATE_BWD):
        assert name in text
    tolerance = 1e-5 if dtype == jnp.float32 else 2e-2
    got, (got_u, got_layer) = jax.value_and_grad(loss, (0, 1))(u, layer)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(mamba1, "gate_path", lambda *a: "xla")
        assert scopes.MAMBA_GATE_FWD not in jax.jit(loss).lower(
            u, layer).as_text(debug_info=True)
        want, (want_u, want_layer) = jax.value_and_grad(loss, (0, 1))(
            u, layer)
    assert rel(got, want) <= tolerance
    assert rel(got_u, want_u) <= tolerance
    for name in ("mamba_w_in", "mamba_w_out", "mamba_d"):
        assert rel(got_layer[name], want_layer[name]) <= tolerance, name


def test_the_gates_counter_says_which_path_was_traced(hvd):
    """``hvd_mamba_gate_rows_total``: batch x T a Mamba layer, labelled
    where the path is chosen; from ``record_tokens`` and from a trace of
    the tiny configuration's loss (128 channels: the ``jax.numpy``
    line)."""
    from horovod_tpu import telemetry

    telemetry.reset_for_tests()
    telemetry.configure(True)
    try:
        wide = dataclasses.replace(JAMBA_TINY, mamba_inner=1024,
                                   mamba_state=8)
        x = jnp.zeros((2, 64, 64))
        mamba1.record_tokens(7, x, wide)
        mamba1.record_tokens(8, x[:, :40], wide)
        tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32)
        jax.eval_shape(
            lambda p, t: tfm.loss_fn(p, t, t, JAMBA_TINY, attention="local"),
            tfm.init_abstract(JAMBA_TINY), tokens)
        text = telemetry.render_prometheus()
        for line in ('hvd_mamba_gate_rows_total{layer="7",path="kernel"} 128',
                     'hvd_mamba_gate_rows_total{layer="8",path="xla"} 80',
                     'hvd_mamba_gate_rows_total{layer="0",path="xla"} 256',
                     'hvd_mamba_gate_rows_total{layer="3",path="xla"} 256'):
            assert line in text, text
        # Layer 2 is the attention layer.
        assert 'hvd_mamba_gate_rows_total{layer="2"' not in text
    finally:
        telemetry.reset_for_tests()


def test_initialisation_is_mambas():
    """``A = 1..N`` for every channel, ``D = 1``, the step log-uniform in
    [1e-3, 0.1] stored as its inverse softplus, the inner norms at 1."""
    layer = mamba1.PART.init(jax.random.split(jax.random.key(0), 6),
                             JAMBA_TINY)
    assert set(layer) == set(mamba1.LEAVES) | {"ln1_scale"}
    np.testing.assert_allclose(jnp.exp(layer["mamba_a_log"]),
                               np.tile(np.arange(1.0, 5.0), (128, 1)),
                               rtol=1e-6)
    step = jax.nn.softplus(layer["mamba_dt_bias"])
    assert 1e-3 <= float(step.min()) <= float(step.max()) <= 0.1 + 1e-6
    for name in ("mamba_d", "mamba_dt_norm_scale", "mamba_b_norm_scale",
                 "mamba_c_norm_scale"):
        assert float(jnp.abs(layer[name] - 1.0).max()) == 0.0
    assert float(jnp.abs(layer["mamba_conv"]).max()) <= 0.5
