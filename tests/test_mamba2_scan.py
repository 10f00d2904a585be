"""The Mamba-2 scan Pallas kernels (``ops/mamba2_scan.py``) in the Pallas
interpreter on the CPU: the same code Mosaic compiles for the chip
(``tests/test_flash_compile.py`` holds that it does).

Oracles: the benchmark's token-by-token recurrence
(``perfbench/reference/ssm_moe_lm.py``), which shares no code with the
program, and the ``jax.numpy`` chunked form the kernels took the place of
(``models/mamba2.py::ssd``).  Tolerances are those of
``tests/test_ssm_moe_lm.py``: float32 rounding.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import mamba2
from horovod_tpu.ops import mamba2_scan as op
from horovod_tpu.telemetry import scopes
from perfbench.reference import ssm_moe_lm as reference
from tests.test_ssm_moe_lm import DECAYS, F32_REL, NEMOTRON_TINY, _rel

CHUNK = 128
TILE = op.TILE_CHUNKS * CHUNK
LENGTHS = {"one_chunk": CHUNK, "one_tile": TILE, "three_tiles": 3 * TILE}
# (groups, heads a group, channels a head): one group; groups of several
# heads whose lanes are one and two registers wide.
GROUPS = {"g1_r8_p16": (1, 8, 16), "g2_r8_p16": (2, 8, 16),
          "g2_r8_p32": (2, 8, 32)}
NAMES = "x B C delta log_a D".split()


def _gates(key, shape, decay):
    """``delta`` and ``log a``: drawn from ``decay``'s range, or Mamba-2's
    initialisation (``A`` ~ U(1, 16), ``delta`` log-uniform in [0.001,
    0.1]) where ``decay`` is None."""
    k = jax.random.split(key, 3)
    if decay is None:
        delta = jnp.exp(jax.random.uniform(
            k[0], shape, minval=np.log(mamba2.DT_INIT_RANGE[0]),
            maxval=np.log(mamba2.DT_INIT_RANGE[1])))
        a = jax.random.uniform(k[1], shape[-1:], minval=1.0, maxval=16.0)
        return delta, -delta * a
    return (jax.nn.softplus(jax.random.normal(k[0], shape)),
            jax.random.uniform(k[1], shape, minval=decay[0], maxval=decay[1]))


def _inputs(t, decay, groups=(2, 8, 16), n=128, batch=2, dtype=jnp.float32):
    g, r, p = groups
    h = g * r
    ks = jax.random.split(jax.random.key(t), 6)
    x = jax.random.normal(ks[0], (batch, t, h, p))
    b_in = jax.random.normal(ks[1], (batch, t, g, n)) * n ** -0.5
    c_in = jax.random.normal(ks[2], (batch, t, g, n))
    delta, log_a = _gates(ks[3], (batch, t, h), decay)
    d = jax.random.normal(ks[4], (h,))
    dy = jax.random.normal(ks[5], (batch, t, h, p))
    return (x.astype(dtype), b_in.astype(dtype), c_in.astype(dtype), delta,
            log_a, d, dy)


def _token_major(scan, x, b_in, c_in, delta, log_a, d):
    """``scan``, one of the two forms ``mamba2.mixer`` calls, on head-major
    operands."""
    (bsz, t), g = x.shape[:2], b_in.shape[2]
    return scan(x.reshape(bsz, t, -1), b_in.reshape(bsz, t, -1),
                c_in.reshape(bsz, t, -1), delta, log_a, d, CHUNK,
                g).reshape(x.shape)


def _kernels(*operands):
    return _token_major(op.mamba2_scan, *operands)


def _chunked(*operands):
    return _token_major(mamba2.ssd_scan, *operands)


def _by_token(x, b_in, c_in, delta, log_a, d):
    r = x.shape[2] // b_in.shape[2]
    return jax.vmap(lambda x, b, c, dl, la: reference._state_space(
        x, jnp.repeat(b, r, axis=1), jnp.repeat(c, r, axis=1), dl,
        jnp.exp(la), None, None) + d[:, None] * x)(
            x, b_in, c_in, delta, log_a)


def _with_grads(f, *inputs):
    *operands, dy = inputs
    out, pull = jax.vjp(f, *operands)
    return (out,) + pull(dy.astype(out.dtype))


def _assert_matches(got, want):
    assert _rel(got[0], want[0]) <= F32_REL
    for name, a, b in zip(NAMES, got[1:], want[1:]):
        # The floor of test_ssm_moe_lm's token-by-token test: near a = 0
        # the gradient of log a is what is left of sums that cancel.
        bound = 2e-5 * np.linalg.norm(b) + 1e-6 * np.linalg.norm(want[1])
        assert np.linalg.norm(np.asarray(a - b)) <= bound, name


@pytest.mark.parametrize("t", LENGTHS.values(), ids=LENGTHS.keys())
@pytest.mark.parametrize("decay", [*DECAYS.values(), None],
                         ids=[*DECAYS.keys(), "published_init"])
def test_kernels_match_both_oracles(decay, t):
    """``y`` and all six gradients, batch and groups above one, ``a`` near
    1, near 0 (where the decays underflow to exact zeros) and as Mamba-2
    initialises it."""
    inputs = _inputs(t, decay)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: _with_grads(_kernels, *a))(*inputs)
        for oracle in (_by_token, _chunked):
            _assert_matches(got, jax.jit(
                lambda *a: _with_grads(oracle, *a))(*inputs))
    if decay == DECAYS["a_near_0"]:
        one = _inputs(CHUNK, decay)
        assert float(jnp.exp(jnp.sum(one[4], axis=1)).max()) == 0.0


@pytest.mark.parametrize("groups", GROUPS.values(), ids=GROUPS.keys())
def test_groups_and_head_widths(groups):
    inputs = _inputs(2 * CHUNK, DECAYS["a_mid"], groups=groups, batch=1)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda *a: _with_grads(_kernels, *a))(*inputs)
        _assert_matches(got, jax.jit(
            lambda *a: _with_grads(_by_token, *a))(*inputs))


def test_bfloat16_operands_are_no_further_from_float32_than_the_jax_numpy_form():
    """The kernels round where the module's docstring says and nowhere
    else: against the float32 token-by-token recurrence they read no more
    than the ``jax.numpy`` form with the same operands."""
    inputs = _inputs(4 * CHUNK, DECAYS["a_near_1"], dtype=jnp.bfloat16)
    f32 = tuple(v.astype(jnp.float32) for v in inputs)
    with jax.default_matmul_precision("highest"):
        want = _with_grads(_by_token, *f32)
    got = _with_grads(_kernels, *inputs)
    xla = _with_grads(_chunked, *inputs)
    assert got[0].dtype == jnp.float32
    for name, a, b, w in zip(["y"] + NAMES, got, xla, want):
        assert a.dtype == b.dtype, name
        # Roundings fall differently: a fifth is their noise at this size
        # (and D's gradient, a float32 sum either way, in another order).
        assert _rel(a.astype(jnp.float32), w) <= 1.2 * _rel(
            b.astype(jnp.float32), w) + 2e-6, name
        assert _rel(a.astype(jnp.float32), w) <= 2e-2, name


def test_the_decay_parameters_gradient_survives_bfloat16_operands():
    """The gradient of ``log a`` is what is left of sums over a chunk that
    cancel, and ``A_log``'s is its sum over every token: the backward
    kernel makes both sides of the cancellation of the same rounded
    products (sides rounded apart read 3% here where the ``jax.numpy``
    form reads 0.3%, and failed the benchmark's check on the chip)."""
    t, (g, r, p) = 4 * CHUNK, (1, 8, 16)
    x, b_in, c_in, delta, _, d, dy = _inputs(t, None, groups=(g, r, p),
                                             batch=1)
    a_log = jnp.log(jax.random.uniform(jax.random.key(1), (g * r,),
                                       minval=1.0, maxval=16.0))

    def grads(f, dtype):
        def loss(a_log, delta):
            return jnp.sum(dy * f(
                x.astype(dtype), b_in.astype(dtype), c_in.astype(dtype),
                delta, -delta * jnp.exp(a_log), d))
        return jax.grad(loss, (0, 1))(a_log, delta)

    with jax.default_matmul_precision("highest"):
        want = grads(_by_token, jnp.float32)
    got = grads(_kernels, jnp.bfloat16)
    xla = grads(_chunked, jnp.bfloat16)
    for name, a, b, w in zip(("A_log", "delta"), got, xla, want):
        assert _rel(a, w) <= 2.0 * _rel(b, w), name
        assert _rel(a, w) <= 1e-2, name


SIZES = dict(chunk=CHUNK, heads=8, head_dim=16, state=128)
# The published mamba2-2.7b: one group of 80 heads of 64 channels in
# chunks of 256, whose backward kernel asks Mosaic for 81 MiB a chunk.
MAMBA2_2P7B = dict(chunk=256, heads=80, head_dim=64, state=128)


@pytest.mark.parametrize("t,sizes,chunks", [
    (128, {}, 1), (512, {}, 4), (8192, {}, 4), (768, {}, 3), (640, {}, 1),
    (136, {}, None), (512, {"chunk": 256}, 2), (128, {"chunk": 32}, None),
    (384, {"chunk": 192}, None),
    # What VMEM holds: Nemotron's group four chunks a tile, a group three
    # times as wide two, four times one, mamba2-2.7b's none.
    (8192, {"heads": 16, "head_dim": 64}, 4),
    (8192, {"heads": 48, "head_dim": 64}, 2),
    (8192, {"heads": 64, "head_dim": 64}, 1), (2048, MAMBA2_2P7B, None)])
def test_tiles(monkeypatch, t, sizes, chunks):
    monkeypatch.setattr(op, "TILE_CHUNKS", 4)
    assert op.tiles(t, **dict(SIZES, **sizes)) == chunks


def test_a_tile_is_what_the_vmem_estimate_holds():
    """The estimate is from above: at the sizes Mosaic was asked (the
    smallest ``vmem_limit_bytes`` the backward kernel compiled under for
    a described v5e, docs/kernels.md) it reads more, and no more than a
    half more."""
    mib = 2 ** 20
    for (chunks, chunk, heads, head_dim, state), asked in {
            (1, 128, 16, 64, 128): 10, (2, 128, 16, 64, 128): 14,
            (1, 128, 32, 64, 128): 19, (2, 128, 32, 64, 128): 28,
            (1, 256, 16, 64, 128): 18, (1, 128, 16, 64, 256): 13,
            (1, 256, 80, 64, 128): 81}.items():
        got = op.vmem_bytes(chunks, chunk, heads, heads * head_dim, state)
        assert asked * mib <= got <= 1.5 * asked * mib


@pytest.mark.parametrize("change,path", [
    ({}, True), ({"chunk": 64}, False), ({"heads": 4, "head_dim": 32}, False),
    ({"head_dim": 8}, False), ({"head_dim": 24}, False),
    ({"state": 64}, False), ({"state": 192}, False),
    ({"heads": 16, "head_dim": 64}, True),
    # A head a column of the transposed running sums: no more heads than a
    # chunk has tokens.
    ({"heads": 128, "head_dim": 8}, True),
    ({"heads": 136, "head_dim": 16}, False),
    # A grid step of one chunk that VMEM does not hold.
    (MAMBA2_2P7B, False), (dict(MAMBA2_2P7B, chunk=128), True),
    ({"chunk": 512, "heads": 32, "head_dim": 64}, False)])
def test_takes_by_widths(change, path):
    """A chunk, a group's width and a state that are whole lanes, a
    group's heads whole sublanes and no more than a chunk, a grid step
    that fits VMEM."""
    x = jnp.zeros((2, 8 * CHUNK, 8))
    assert op.takes(x, **dict(SIZES, **change)) is path


def test_the_path_is_read_from_the_operand(hvd):
    """The kernels wherever they can run; the ``jax.numpy`` form for a
    length that is not whole chunks, for widths the kernels do not take
    and, on the CPU, inside ``shard_map(check_vma=True)``, where the
    interpreter's loop does not type."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.topology import build_mesh

    cfg = dataclasses.replace(NEMOTRON_TINY, ssm_heads=16, ssm_groups=2,
                              ssm_head_dim=16, ssm_state=128,
                              ssm_chunk=CHUNK)
    x = jnp.zeros((2, 4 * CHUNK, 8))
    assert mamba2.recurrence_path(x, cfg) == "kernel"
    assert mamba2.recurrence_path(x[:, :CHUNK], cfg) == "kernel"
    assert mamba2.recurrence_path(x[:, :CHUNK + 8], cfg) == "xla"
    assert mamba2.recurrence_path(x, NEMOTRON_TINY) == "xla"
    mesh = build_mesh(axes=("data",), devices=jax.devices()[:2])
    seen = {}

    def inside(x, check):
        seen[check] = mamba2.recurrence_path(x, cfg)
        return x

    for check in (True, False):
        jax.eval_shape(jax.shard_map(
            lambda x: inside(x, check), mesh=mesh, in_specs=P("data"),
            out_specs=P("data"), check_vma=check), x)
    assert seen == {True: "xla", False: "kernel"}
    with pytest.raises(ValueError, match="do not take"):
        _kernels(*_inputs(CHUNK + 8, DECAYS["a_mid"])[:6])


def test_the_mixer_calls_the_kernels_where_they_run():
    """The whole mixer, through the kernels and through the ``jax.numpy``
    form: one layer's output and the gradients of all of its leaves."""
    cfg = dataclasses.replace(NEMOTRON_TINY, ssm_heads=16, ssm_groups=2,
                              ssm_head_dim=16, ssm_state=128,
                              ssm_chunk=CHUNK)
    ks = jax.random.split(jax.random.key(0), 3)
    layer = mamba2.init_layer(
        ks[0], cfg, lambda k, shape: jax.random.normal(k, shape)
        * shape[0] ** -0.5)
    u = jax.random.normal(ks[1], (2, 2 * CHUNK, cfg.d_model))
    dy = jax.random.normal(ks[2], u.shape)
    assert mamba2.recurrence_path(u, cfg) == "kernel"

    def loss(layer, u, path):
        traced = jax.make_jaxpr(lambda l, u: mamba2.mixer(u, l, cfg))(
            layer, u)
        assert (scopes.SSM_SCAN_FWD in str(traced)) is (path == "kernel")
        return jnp.sum(mamba2.mixer(u, layer, cfg) * dy)

    with jax.default_matmul_precision("highest"):
        got = jax.grad(loss, (0, 1))(layer, u, "kernel")
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(mamba2, "recurrence_path", lambda x, cfg: "xla")
            want = jax.grad(loss, (0, 1))(layer, u, "xla")
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert _rel(a, b) <= F32_REL


def test_a_group_too_wide_for_vmem_runs_the_jax_numpy_form():
    """The published mamba2-2.7b's layer (d 2560, one group of 80 heads
    of 64 channels, chunks of 256): Mosaic refuses the backward kernel at
    these widths (``tests/test_flash_compile.py``'s recipe reads 81 MiB
    asked of the 64 a kernel may use), so ``takes`` does, and the mixer
    traces what the parent of the kernels ran."""
    cfg = dataclasses.replace(
        NEMOTRON_TINY, d_model=2560, dtype=jnp.bfloat16,
        ssm_groups=1, **{f"ssm_{k}": v for k, v in MAMBA2_2P7B.items()})
    u = jax.ShapeDtypeStruct((1, 2048, cfg.d_model), cfg.dtype)
    layer = jax.eval_shape(lambda: mamba2.init_layer(
        jax.random.key(0), cfg, lambda k, shape: jnp.zeros(shape)))
    assert mamba2.recurrence_path(u, cfg) == "xla"
    traced = jax.make_jaxpr(lambda l, u: mamba2.mixer(u, l, cfg))(layer, u)
    # The short convolution's kernels take these widths; the scan's do not.
    assert "pallas_call" in str(traced)
    assert scopes.SSM_SCAN_FWD not in str(traced)
    assert traced.out_avals[0].shape == u.shape


def test_mamba2_layers_share_one_traced_kernel_a_kind(monkeypatch):
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
    inputs = _inputs(2 * CHUNK, DECAYS["a_mid"], groups=(1, 16, 8), batch=1)

    def three_layers(x, *rest):
        for _ in range(3):
            x = jax.checkpoint(_kernels)(x, *rest)
        return jnp.sum(x)

    jax.jit(jax.grad(three_layers, range(6))).lower(*inputs[:6])
    assert traced == {"fwd": 2, "bwd": 1}
