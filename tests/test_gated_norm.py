"""The gated-norm Pallas kernels (``ops/gated_norm.py``) in the Pallas
interpreter on the CPU: the same code Mosaic compiles for the chip
(``tests/test_flash_compile.py`` holds that it does).

The oracles are what the mixers ran before the kernels and run where the
kernels cannot: ``mamba2.gated_norm`` (gate, then a group's RMS norm) and
``linear_attention.gated_norm`` (a head's RMS norm, then the gate), the
``jax.numpy`` lines with their one rounding.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import linear_attention as la
from horovod_tpu.models import mamba2
from horovod_tpu.ops import gated_delta_rule as gdn
from horovod_tpu.ops import gated_norm as op
from horovod_tpu.telemetry import scopes

# Float32 against float32, as ``tests/test_short_conv.py`` holds its
# kernels: the sigmoid is the reciprocal unit's estimate with Newton
# steps, everything else the oracle's operations in the oracle's order.
F32_REL = 2e-5
NAMES = ("x", "z", "scale")
EPS = 1e-5


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _oracle(x, z, scale, *, group, gate_first, head_major=False):
    """The mixers' ``jax.numpy`` forms, in ``gated_norm``'s signature."""
    bsz, t, width = z.shape
    o = gdn.token_major(x, bsz) if head_major else x.reshape(
        bsz, t, width // group, group)
    if gate_first:
        return mamba2.gated_norm(o.reshape(z.shape), z, scale,
                                 width // group, EPS)
    return la.gated_norm(o, z, scale, EPS)


def _kernel(x, z, scale, **kw):
    return op.gated_norm(x, z, scale, eps=EPS, **kw)


def _inputs(t, group, groups, gate_first, head_major=False, batch=2,
            dtype=jnp.float32, x_dtype=None, seed=0):
    """``x`` in the layout and dtype the form's recurrence leaves (float32
    from the Mamba-2 scan, the model dtype from the delta rule), ``z`` in
    the model dtype, a scale a channel (gate first) or a head's."""
    ks = jax.random.split(jax.random.key(seed + t + group * groups), 3)
    width = group * groups
    x = jax.random.normal(ks[0], (batch, t, width)).astype(
        x_dtype or (jnp.float32 if gate_first else dtype))
    if head_major:
        x = gdn.head_major(x.reshape(batch, t, groups, group))
    z = jax.random.normal(ks[1], (batch, t, width)).astype(dtype)
    scale = 1.0 + 0.1 * jax.random.normal(
        ks[2], (width if gate_first else group,), jnp.float32)
    return x, z, scale


def _with_grads(f, x, z, scale, **kw):
    out, pull = jax.vjp(lambda *a: f(*a, **kw), x, z, scale)
    dout = jax.random.normal(jax.random.key(9), out.shape).astype(out.dtype)
    return out, pull(dout)


def _assert_matches(got, want, rel=F32_REL):
    assert got[0].shape == want[0].shape and got[0].dtype == want[0].dtype
    assert _rel(got[0], want[0]) <= rel
    for name, a, b in zip(NAMES, got[1], want[1]):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert _rel(a, b) <= rel, name


@pytest.fixture
def small_tiles(monkeypatch):
    """Tiles of 32 tokens in chunks of 16: a test's few dozen tokens are
    several tiles of several chunks, as the benchmark's thousands are."""
    monkeypatch.setattr(op, "TILE", 32)
    monkeypatch.setattr(op, "CHUNK_REGISTERS", 2)


# (group width, groups, gate first, x head-major): Nemotron's groups of
# 1024 and a group of one register; Olmo-Hybrid's heads of 192 as the
# delta rule's kernels leave them, an even number (slabs of a pair) and an
# odd one (a last slab of one head), and token-major; the tiny hybrid's
# heads of 48 (two of the eight a slab would hold).
FORMS = {
    "ssm_1024x2": (1024, 2, True, False),
    "ssm_128x3": (128, 3, True, False),
    "gdn_192x4_head_major": (192, 4, False, True),
    "gdn_192x3_head_major": (192, 3, False, True),
    "gdn_192x3_token_major": (192, 3, False, False),
    "gdn_48x2_head_major": (48, 2, False, True),
    "norm_first_1024x1": (1024, 1, False, False),
    "gate_first_96x5_head_major": (96, 5, True, True),
}
# One tile, five tiles, and a length the tile of 32 does not divide (three
# tiles of 16); the forms no cell has at one length.
LENGTHS = {"one_tile": 32, "five_tiles": 160, "ragged": 48}
CASES = [(form, length) for form in FORMS for length in LENGTHS
         if length == "one_tile" or form.startswith(("ssm", "gdn_192"))]


@pytest.mark.parametrize(
    "form,t", [(FORMS[form], LENGTHS[length]) for form, length in CASES],
    ids=[f"{form}-{length}" for form, length in CASES])
def test_matches_the_jax_numpy_form(small_tiles, form, t):
    """Forward and the gradients of ``x``, ``z`` and the scale, float32
    against float32, both forms, ``x`` in either layout."""
    group, groups, gate_first, head_major = form
    batch = 2 if t == 32 else 1
    args = _inputs(t, group, groups, gate_first, head_major, batch=batch)
    kw = dict(group=group, gate_first=gate_first, head_major=head_major)
    assert op.tiles(t, group * groups, group, head_major) == (
        16 if t == 48 else 32)
    got = jax.jit(lambda *a: _with_grads(_kernel, *a, **kw))(*args)
    want = jax.jit(lambda *a: _with_grads(_oracle, *a, **kw))(*args)
    assert got[0].shape == (batch, t, group * groups)
    _assert_matches(got, want)


@pytest.mark.parametrize("form", [
    FORMS["ssm_1024x2"], FORMS["gdn_192x4_head_major"],
    FORMS["gdn_192x3_head_major"], FORMS["gdn_192x3_token_major"]],
    ids=["ssm", "gdn_even", "gdn_odd", "gdn_token_major"])
def test_bfloat16_operands_are_no_further_from_float32_than_the_jax_numpy_form(
        small_tiles, form):
    """The kernels round where the ``jax.numpy`` lines do, once, at the
    end: against the float32 oracle on the same values they read no more
    than the ``jax.numpy`` form on the bfloat16 operands.  ``y`` of the
    Mamba-2 scan stays float32, ``o`` of the delta rule is the model's
    dtype; the scale's gradient is float32 either way."""
    group, groups, gate_first, head_major = form
    x, z, scale = _inputs(96, group, groups, gate_first, head_major,
                          dtype=jnp.bfloat16)
    kw = dict(group=group, gate_first=gate_first, head_major=head_major)
    assert x.dtype == (jnp.float32 if gate_first else jnp.bfloat16)
    want = jax.jit(lambda *a: _with_grads(_oracle, *a, **kw))(
        x.astype(jnp.float32), z.astype(jnp.float32), scale)
    got = jax.jit(lambda *a: _with_grads(_kernel, *a, **kw))(x, z, scale)
    xla = jax.jit(lambda *a: _with_grads(_oracle, *a, **kw))(x, z, scale)
    assert got[0].dtype == jnp.bfloat16 and got[1][2].dtype == jnp.float32
    for a, c, r in zip((got[0],) + got[1], (xla[0],) + xla[1],
                       (want[0],) + want[1]):
        assert a.dtype == c.dtype and a.shape == c.shape
        assert _rel(a.astype(jnp.float32), r) <= 1.05 * _rel(
            c.astype(jnp.float32), r) + 1e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("form", [FORMS["ssm_128x3"],
                                  FORMS["gdn_192x3_head_major"]],
                         ids=["ssm", "gdn"])
def test_rows_of_zeros_and_large_gates_stay_finite(small_tiles, form, dtype):
    """A token whose ``x`` is all zeros is normed by ``rsqrt(eps)`` and
    comes out zero with a finite gradient; a gate of +-100 (``silu`` 100
    and -0, ``exp`` far past float32's range unclamped) makes no ``inf *
    0``.  Both as the ``jax.numpy`` form has them."""
    group, groups, gate_first, head_major = form
    x, z, scale = _inputs(64, group, groups, gate_first, head_major,
                          dtype=dtype)
    x = x.at[..., 3:7, :].set(0.0)
    z = z.at[:, 8:12].set(100.0).at[:, 12:16].set(-100.0)
    z = z.at[:, 3].set(100.0).at[:, 4].set(-100.0)
    kw = dict(group=group, gate_first=gate_first, head_major=head_major)
    got = jax.jit(lambda *a: _with_grads(_kernel, *a, **kw))(x, z, scale)
    want = jax.jit(lambda *a: _with_grads(_oracle, *a, **kw))(x, z, scale)
    for a in (got[0],) + got[1]:
        assert bool(jnp.all(jnp.isfinite(a.astype(jnp.float32))))
    np.testing.assert_array_equal(np.asarray(got[0][:, 3:7], np.float32), 0.0)
    rel = F32_REL if dtype == jnp.float32 else 2e-2
    for a, b in zip((got[0],) + got[1], (want[0],) + want[1]):
        assert _rel(a.astype(jnp.float32), b.astype(jnp.float32)) <= rel


@pytest.mark.parametrize("t,width,group,kw,tile", [
    (8192, 8192, 1024, {}, 128),                   # nemotron3s_t8192
    (16384, 5760, 192, dict(head_major=True, x_itemsize=2), 256),
    (16384, 5760, 192, dict(x_itemsize=2), 256),   # olmohybrid_t16k
    (96, 256, 128, {}, 32), (16, 128, 128, {}, 16), (48, 96, 48, {}, 16),
    (8, 128, 128, {}, None), (100, 128, 128, {}, None),  # not whole tiles
    (64, 200, 96, {}, None),                       # not whole groups
    (64, 200, 100, {}, None),                      # lcm(100, 128) > a slab
    (64, 272, 136, dict(head_major=True), None),
    (64, 128, 0, {}, None)],
    ids=lambda v: str(v).replace(" ", "") if not isinstance(v, dict)
    else "_".join(f"{k}{x}" for k, x in v.items()) or "plain")
def test_tiles(t, width, group, kw, tile):
    assert op.tiles(t, width, group, **kw) == tile


def test_a_tile_is_what_the_vmem_estimate_holds(monkeypatch):
    """A width four times Nemotron's takes a smaller tile before it is
    refused; a float32 ``x`` takes more room than a bfloat16 one."""
    assert op.tiles(8192, 32768, 1024) == 32
    assert op.tiles(8192, 32768, 1024, x_itemsize=2) == 64
    monkeypatch.setattr(op, "VMEM_LIMIT", 2 ** 20)
    assert op.tiles(8192, 32768, 1024) is None


def test_the_path_is_read_from_the_operand(hvd):
    """The kernels wherever they can run; the ``jax.numpy`` lines for
    sizes they do not take and, on the CPU, inside
    ``shard_map(check_vma=True)``, where the interpreter's loops do not
    type."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.topology import build_mesh
    from tests.test_hybrid_lm import HYBRID_TINY
    from tests.test_ssm_moe_lm import NEMOTRON_TINY

    u = jnp.zeros((2, 64, 256))
    assert op.takes(u, 128)
    assert op.takes(u, 192, width=5760, head_major=True)
    assert op.takes(u, 1024, width=8192, x_dtype=jnp.float32)
    assert not op.takes(u, 100, width=200)
    assert not op.takes(u, 96)
    assert not op.takes(u[:, :60], 128)
    assert not op.takes(u[0], 128)
    mesh = build_mesh(axes=("data",), devices=jax.devices()[:2])
    seen = {}

    def inside(u, check):
        seen[check] = (op.takes(u, 128), la.norm_path(u, HYBRID_TINY),
                       mamba2.norm_path(u, NEMOTRON_TINY))
        return u

    for check in (True, False):
        jax.eval_shape(jax.shard_map(
            lambda u: inside(u, check), mesh=mesh, in_specs=P("data"),
            out_specs=P("data"), check_vma=check), u)
    assert seen == {True: (False, "xla", "xla"),
                    False: (True, "kernel", "kernel")}
    x, z, scale = _inputs(64, 128, 2, True)
    with pytest.raises(ValueError, match="do not take"):
        op.gated_norm(x[:, :60], z[:, :60], scale, group=128,
                      gate_first=True)
    with pytest.raises(ValueError, match="do not take"):
        op.gated_norm(x, z, scale, group=128, gate_first=True,
                      head_major=True)       # x is not [B * H, T, group]
    with pytest.raises(ValueError, match="do not take"):
        op.gated_norm(x[..., :200], z[..., :200], scale[:200], group=100,
                      gate_first=True)


@pytest.mark.parametrize("kind", ["linear_attention", "mamba2"])
def test_layers_share_one_traced_kernel_a_kind(monkeypatch, kind):
    """Forward, recomputed forward and backward of every layer go through
    the same jitted calls: a mixer's kernel bodies are traced once a kind
    and a tracing context (the forward as ``jax.checkpoint``'s primal and
    under the differentiation rule), whatever the depth."""
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
        x, z, scale = _inputs(80, 128, 2, True, batch=1)
        kw = dict(group=128, gate_first=True)
    else:
        x, z, scale = _inputs(80, 48, 3, False, True, batch=1)
        kw = dict(group=48, gate_first=False, head_major=True)

    def layer(x, z, scale):
        out = _kernel(x, z, scale, **kw)
        return z + out, out

    def three_layers(x, z, scale):
        total = 0.0
        for _ in range(3):
            z, out = jax.checkpoint(layer)(x, z, scale)
            total = total + jnp.sum(out)
        return total

    jaxpr = str(jax.make_jaxpr(jax.grad(three_layers, (0, 1, 2)))(
        x, z, scale))
    assert traced == {"fwd": 2, "bwd": 1}
    assert scopes.GATED_NORM_FWD in jaxpr and scopes.GATED_NORM_BWD in jaxpr


def test_the_counter_says_which_path_was_traced(hvd):
    """``hvd_gated_norm_rows_total``: batch x T a mixer layer, labelled
    where the path is chosen."""
    import dataclasses

    from horovod_tpu import telemetry
    from tests.test_hybrid_lm import HYBRID_TINY
    from tests.test_ssm_moe_lm import NEMOTRON_TINY

    telemetry.reset_for_tests()
    telemetry.configure(True)
    try:
        x = jnp.zeros((2, 64, 64))
        la.record_blocks(0, x, HYBRID_TINY)
        la.record_blocks(1, x[:, :40], HYBRID_TINY)
        mamba2.record_chunks(2, x, NEMOTRON_TINY)
        mamba2.record_chunks(3, x[:, :40], NEMOTRON_TINY)
        # Groups of 100 channels: no slab of whole groups and whole lanes.
        mamba2.record_chunks(4, x, dataclasses.replace(
            NEMOTRON_TINY, ssm_heads=4, ssm_head_dim=50, ssm_groups=2))
        text = telemetry.render_prometheus()
        for line in ('hvd_gated_norm_rows_total{layer="0",path="kernel"} 128',
                     'hvd_gated_norm_rows_total{layer="1",path="xla"} 80',
                     'hvd_gated_norm_rows_total{layer="2",path="kernel"} 128',
                     'hvd_gated_norm_rows_total{layer="3",path="xla"} 80',
                     'hvd_gated_norm_rows_total{layer="4",path="xla"} 128'):
            assert line in text, text
    finally:
        telemetry.reset_for_tests()
