"""Latent attention's assembly kernels (``horovod_tpu/ops/mla_assemble.py``)
against the ``jax.numpy`` lines they replace (``attention.assemble_xla``),
the flash kernels' entry for operands born in their layout
(``flash_attention_folded``), and the choice between the two paths
(``attention.assemble_path``).  The kernels run in the Pallas interpreter
here; what they cost is a chip run's to say (docs/kernels.md).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import attention, parts
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import mla_assemble
from horovod_tpu.ops.flash_attention import (flash_attention,
                                             flash_attention_folded)
from horovod_tpu.telemetry import scopes

BF16, F32 = jnp.bfloat16, jnp.float32
THETA = 1e6
# (heads, head width, rotary width, T): GLM-4.7-Flash's widths (a head of
# ``up`` 448 lanes wide, so odd heads start mid-register), and the least.
SIZES = [(20, 256, 64, 32), (4, 128, 64, 48)]
IDS = ["h20_w256_r64", "h4_w128_r64"]
# One layer and the prediction module, heads of 128 with 64 rotary: the
# least widths the kernels take.
GLM_WIDE = tfm.TransformerConfig(
    vocab_size=128, d_model=64, n_heads=2, n_layers=2, d_ff=96, max_seq=64,
    dtype=F32, positions="rope", rope_theta=THETA, norm_eps=1e-5,
    tie_embeddings=False, head_width=128, q_latent_rank=24,
    kv_latent_rank=16, rope_dim=64, mlp="swiglu", n_experts=4,
    experts_per_token=2, d_expert=48, d_shared=48, routed_scale=1.8,
    dense_layers=1, mtp_layer_types=("full_attention",), mtp_loss_coef=0.1)


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _fold(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _operands(heads, hd, rope, t, dtype=BF16, batch=2):
    keys = jax.random.split(jax.random.key(heads + t), 6)
    shapes = ((batch, t, heads * hd), (batch, t, heads * (2 * hd - rope)),
              (batch, t, rope)) + 3 * ((batch * heads, t, hd),)
    arrays = [jax.random.normal(k, s, F32).astype(dtype)
              for k, s in zip(keys, shapes)]
    return arrays[:3], tuple(arrays[3:]), jnp.arange(t, dtype=jnp.int32) + 5


def _oracle(positions, heads):
    def run(q_proj, up, k_r):
        return tuple(_fold(a) for a in attention.assemble_xla(
            q_proj, up, k_r, positions, heads, THETA))
    return run


def _kernels(positions, heads):
    def run(q_proj, up, k_r):
        return mla_assemble.mla_assemble(q_proj, up, k_r, positions, heads,
                                         THETA)
    return run


@pytest.mark.parametrize("size", SIZES, ids=IDS)
def test_forward_is_the_xla_lines_bit_for_bit(size):
    """q, k, v in bf16: rotated in float32 as ``rotary`` does it, rounded
    once, every head's key ending in the one rotated key."""
    heads, hd, rope, t = size
    operands, _, positions = _operands(*size)
    want = jax.jit(_oracle(positions, heads))(*operands)
    got = jax.jit(_kernels(positions, heads))(*operands)
    for name, a, b in zip("qkv", got, want):
        assert a.shape == b.shape == (2 * heads, t, hd) and a.dtype == BF16
        assert bool(jnp.all(a.view(jnp.uint16) == b.view(jnp.uint16))), name


@pytest.mark.parametrize("size", SIZES, ids=IDS)
def test_gradients_are_the_xla_lines(size):
    """Of a random cotangent: ``d q_proj`` (the tails rotated back) and
    ``d up`` within one bf16 rounding of the ``jax.numpy`` lines' on the
    same bf16 operands, and ``d k_r``, the sum over heads, against the
    lines run in float32 (in bf16 they round the sum before they rotate it
    back, the kernel after): within a bf16 rounding of it."""
    heads = size[0]
    operands, cotangents, positions = _operands(*size)
    got = jax.vjp(_kernels(positions, heads), *operands)[1](cotangents)
    want = jax.vjp(_oracle(positions, heads), *operands)[1](cotangents)
    exact = jax.vjp(_oracle(positions, heads),
                    *(a.astype(F32) for a in operands))[1](
                        tuple(c.astype(F32) for c in cotangents))
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype == BF16
    assert rel(got[0], want[0]) <= 1e-4
    assert bool(jnp.all(got[1] == want[1]))
    assert rel(got[2], exact[2]) <= 2.5e-3      # 2^-9 a value
    assert rel(want[2], exact[2]) > rel(got[2], exact[2])


@pytest.mark.parametrize("size", SIZES, ids=IDS)
def test_float32_operands_to_float32_rounding(size):
    """The same in float32, where nothing is rounded on the way: values
    and gradients to float32 rounding (the compiler may contract a
    multiply and an add on one side and not the other; the head sum is
    reordered)."""
    heads = size[0]
    operands, cotangents, positions = _operands(*size, dtype=F32)
    got, pull = jax.vjp(_kernels(positions, heads), *operands)
    want, pull_want = jax.vjp(_oracle(positions, heads), *operands)
    for a, b in zip(got, want):
        assert rel(a, b) <= 1e-6
    for a, b in zip(pull(cotangents), pull_want(cotangents)):
        assert rel(a, b) <= 1e-6


@pytest.mark.parametrize("why,heads,hd,rope,t", [
    ("odd rope", 4, 128, 63, 64),
    ("a width that is no multiple of 128", 4, 192, 64, 64),
    ("an untiled T", 4, 128, 64, 40),
    ("the rotary part wider than a register", 4, 256, 160, 64),
    ("a head that is all rotary", 4, 128, 128, 64),
])
def test_takes_refuses(why, heads, hd, rope, t):
    h = jnp.zeros((1, t, 64), BF16)
    assert mla_assemble.tiles(t, heads, hd, rope) is None, why
    assert not mla_assemble.takes(h, heads, hd, rope), why
    with pytest.raises(ValueError, match="do not take"):
        mla_assemble.mla_assemble(
            jnp.zeros((1, t, heads * hd), BF16),
            jnp.zeros((1, t, heads * (2 * hd - rope)), BF16),
            jnp.zeros((1, t, rope), BF16), jnp.arange(t), heads, THETA)


def test_tiles_and_vmem_at_the_published_widths():
    """GLM-4.7-Flash at 8192 tokens: tiles of 128, 15.4 MiB of the VMEM a
    kernel may use; a head of 1024 takes a smaller tile."""
    assert mla_assemble.tiles(8192, 20, 256, 64) == mla_assemble.TILE == 128
    assert (15 * 2 ** 20 < mla_assemble.vmem_bytes(128, 20, 256, 64)
            <= 16 * 2 ** 20)
    assert mla_assemble.tiles(8192, 64, 1024, 64, 4) == 16
    assert mla_assemble.tiles(48, 4, 128, 64) == 16
    assert mla_assemble.takes(jnp.zeros((2, 64, 8), BF16), 4, 128, 64)
    assert not mla_assemble.takes(jnp.zeros((64, 8), BF16), 4, 128, 64)


@pytest.mark.parametrize("segments", (False, True), ids=("whole", "packed"))
def test_flash_attention_folded_is_flash_attention(segments):
    """The same kernels on the same operands, without the moves around
    them: the value and the three gradients, equal."""
    b, t, h, d = 2, 64, 3, 128
    q, k, v, do = (jax.random.normal(key, (b, t, h, d), F32).astype(BF16)
                   for key in jax.random.split(jax.random.key(3), 4))
    seg = (jnp.repeat(jnp.arange(4), t // 4)[None].repeat(b, 0)
           if segments else None)
    want, pull = jax.vjp(
        lambda q, k, v: flash_attention(q, k, v, True, segment_ids=seg),
        q, k, v)
    got, pull_folded = jax.vjp(
        lambda q, k, v: flash_attention_folded(q, k, v, h, True,
                                               segment_ids=seg),
        _fold(q), _fold(k), _fold(v))
    assert bool(jnp.all(got == _fold(want)))
    for a, b_ in zip(pull_folded(_fold(do)), pull(do)):
        assert bool(jnp.all(a == _fold(b_)))
    with pytest.raises(ValueError, match="must match"):
        flash_attention_folded(_fold(q), _fold(k), _fold(v), 4)


def _ctx(attention_route, seq_axis=None, t=32):
    return parts.Ctx(None, seq_axis, attention_route,
                     jnp.arange(t, dtype=jnp.int32), 2 * t)


def test_assemble_path_reads_the_route_and_the_widths():
    """The kernels where the heads go to the flash kernels and
    ``mla_assemble.takes`` accepts them; the ``jax.numpy`` lines on every
    other route, under a sequence axis, and at widths or lengths the
    kernels refuse."""
    h = jnp.zeros((2, 32, 64), F32)
    assert attention.assemble_path(h, GLM_WIDE, _ctx("flash")) == "kernel"
    assert attention.assemble_path(h, GLM_WIDE, _ctx("ring_flash")) == "kernel"
    assert attention.assemble_path(h, GLM_WIDE, _ctx("local")) == "xla"
    assert attention.assemble_path(h, GLM_WIDE, _ctx("auto")) == "xla"
    assert attention.assemble_path(
        h, GLM_WIDE, _ctx("ring_flash", seq_axis="seq")) == "xla"
    assert attention.assemble_path(h[:, :24], GLM_WIDE,
                                   _ctx("flash")) == "xla"
    narrow = dataclasses.replace(GLM_WIDE, head_width=64, rope_dim=16)
    assert attention.assemble_path(h, narrow, _ctx("flash")) == "xla"


@pytest.mark.parametrize("dtype,tolerance", [(F32, 1e-5), (BF16, 3e-2)],
                         ids=("float32", "bfloat16"))
def test_a_tiny_glm_step_on_either_path(dtype, tolerance):
    """The loss and the gradients of the latent projections' leaves with
    the heads assembled by the kernels (the flash route: named in the
    lowered text, forward and backward) and by the ``jax.numpy`` lines (the
    local route, the same exact attention)."""
    cfg = dataclasses.replace(GLM_WIDE, dtype=dtype)
    params = tfm.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)

    def loss(route):
        return lambda p: tfm.loss_fn(p, tokens, labels, cfg, attention=route)

    text = jax.jit(jax.grad(loss("flash"))).lower(params).as_text(
        debug_info=True)
    for name in (scopes.MLA_ASSEMBLE_FWD, scopes.MLA_ASSEMBLE_BWD,
                 scopes.FLASH_FWD, scopes.FLASH_BWD_DQ, scopes.FLASH_BWD_DKV):
        assert name in text, name
    assert scopes.MLA_ASSEMBLE_FWD not in jax.jit(loss("local")).lower(
        params).as_text(debug_info=True)
    got, got_grads = jax.value_and_grad(loss("flash"))(params)
    want, want_grads = jax.value_and_grad(loss("local"))(params)
    assert rel(got, want) <= tolerance
    for name in ("w_kvb", "w_qb", "w_kva", "w_qa", "wo"):
        assert rel(got_grads["layers"][0][name],
                   want_grads["layers"][0][name]) <= tolerance, name


def test_the_counter_says_which_path_was_traced(hvd):
    """``hvd_mla_assemble_rows_total``: batch x T once a latent-attention
    layer (the prediction module's among them), labelled where the path is
    chosen."""
    from horovod_tpu import telemetry

    telemetry.reset_for_tests()
    telemetry.configure(True)
    try:
        tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
        for route in ("flash", "local"):
            jax.eval_shape(
                lambda p, t: tfm.loss_fn(p, t, t, GLM_WIDE, attention=route),
                tfm.init_abstract(GLM_WIDE), tokens)
        text = telemetry.render_prometheus()
        lines = [line for line in text.splitlines()
                 if line.startswith("hvd_mla_assemble_rows_total{")]
        assert len(lines) == 6, text
        for layer in ("0", "1", "mtp_0"):
            for path in ("kernel", "xla"):
                assert any(f'path="{path}"' in line and line.endswith(" 64")
                           and f'layer="{layer}"' in line
                           for line in lines), (layer, path, lines)
    finally:
        telemetry.reset_for_tests()
