"""Plain attention's assembly kernels (``horovod_tpu/ops/qk_assemble.py``)
against the ``jax.numpy`` lines they replace (``attention.qkv_proj``'s
per-head norm and rotation, then the fold to the attention kernels'
layout), and the choice between the two paths (``attention.qk_path``).  The
kernels run in the Pallas interpreter here; what they cost is a chip run's
to say (docs/kernels.md).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import attention, parts
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import head_major, qk_assemble
from horovod_tpu.ops import sparse_attention as sa

BF16, F32 = jnp.bfloat16, jnp.float32
THETA, EPS = 1e6, 1e-6
# (heads, key-value heads, head width, T): Qwen3's grouping of 8 at the
# least width, no grouping, and a head of two registers.
SIZES = [(8, 1, 128, 48), (4, 4, 128, 32), (2, 1, 256, 32)]
IDS = ["h8_kv1_w128", "h4_kv4_w128", "h2_kv1_w256"]
# Plain attention with the per-head norm at the least widths the kernels
# take, in the dtype they take.
QWEN_WIDE = tfm.TransformerConfig(
    vocab_size=128, d_model=64, n_heads=4, n_kv_heads=2, head_width=128,
    n_layers=2, d_ff=96, max_seq=64, dtype=BF16, positions="rope",
    rope_theta=THETA, norm_eps=EPS, tie_embeddings=False,
    qk_norm_per_head=True, mlp="swiglu")


def rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _fold(x):
    b, t, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, t, d)


def _positions(t):
    """Each position twice, as block diffusion sends them: the clean
    sequence and its noised copy."""
    return jnp.tile(jnp.arange(t // 2, dtype=jnp.int32) + 5, 2)


def _operands(heads, kv_heads, hd, t, repeat, dtype=BF16, batch=2):
    keys = jax.random.split(jax.random.key(heads + t), 8)
    wide = (heads, kv_heads, kv_heads)
    projections = [
        (3.0 * jax.random.normal(k, (batch, t, n * hd), F32)).astype(dtype)
        for k, n in zip(keys, wide)]
    scales = [1.0 + 0.3 * jax.random.normal(k, (hd,), F32)
              for k in keys[3:5]]
    out = (heads,) + 2 * (heads if repeat else kv_heads,)
    cotangents = tuple(
        jax.random.normal(k, (batch * n, t, hd), F32).astype(dtype)
        for k, n in zip(keys[5:], out))
    return projections + scales, cotangents


def _oracle(positions, heads, hd, repeat):
    """``qkv_proj``'s lines from the projections on, and the fold."""
    def run(q_proj, k_proj, v_proj, q_scale, k_scale):
        q, k, v = (a.reshape(a.shape[:-1] + (-1, hd))
                   for a in (q_proj, k_proj, v_proj))
        q = attention.rotary(parts.rmsnorm(q, q_scale, EPS), positions, THETA)
        k = attention.rotary(parts.rmsnorm(k, k_scale, EPS), positions, THETA)
        if repeat:
            k, v = attention._share_kv_heads(k, v, heads)
        return _fold(q), _fold(k), _fold(v)
    return run


def _kernels(positions, heads, repeat):
    def run(*operands):
        return qk_assemble.qk_assemble(*operands, positions, heads, THETA,
                                       EPS, repeat=repeat)
    return run


@pytest.mark.parametrize("repeat", (False, True), ids=("once", "a_head"))
@pytest.mark.parametrize("size", SIZES, ids=IDS)
def test_forward_is_the_xla_lines_bit_for_bit(size, repeat):
    """q, k, v in bf16: the statistics in float32, rounded after the
    normalisation, after the scale and after the rotation as
    ``parts.rmsnorm`` and ``rotary`` round, at positions that repeat."""
    heads, kv_heads, hd, t = size
    operands, _ = _operands(*size, repeat)
    positions = _positions(t)
    want = jax.jit(_oracle(positions, heads, hd, repeat))(*operands)
    got = jax.jit(_kernels(positions, heads, repeat))(*operands)
    for name, a, b, n in zip("qkv", got, want, (heads,) + 2 * (
            heads if repeat else kv_heads,)):
        assert a.shape == b.shape == (2 * n, t, hd) and a.dtype == BF16
        assert bool(jnp.all(a.view(jnp.uint16) == b.view(jnp.uint16))), name


@pytest.mark.parametrize("repeat", (False, True), ids=("once", "a_head"))
@pytest.mark.parametrize("size", SIZES, ids=IDS)
def test_gradients_are_the_xla_lines(size, repeat):
    """Of a random cotangent: the three projections' gradients and both
    scales' against ``jax.grad`` of the ``jax.numpy`` lines run in float32
    on the same values, within a bf16 rounding of it (2^-9 a value), and
    nearer to it than the lines run in bf16, which round between their
    steps where the kernel rounds once."""
    heads, _, hd, t = size
    operands, cotangents = _operands(*size, repeat)
    positions = _positions(t)
    oracle = _oracle(positions, heads, hd, repeat)
    got = jax.vjp(_kernels(positions, heads, repeat), *operands)[1](
        cotangents)
    want = jax.vjp(oracle, *operands)[1](cotangents)
    exact = jax.vjp(oracle, *(a.astype(F32) for a in operands))[1](
        tuple(c.astype(F32) for c in cotangents))
    names = ("d q_proj", "d k_proj", "d v_proj", "d q_scale", "d k_scale")
    for name, a, b, e in zip(names, got, want, exact):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        assert rel(a, e) <= 3e-3, name
        assert rel(a, e) <= rel(b, e) + 1e-6, name
        assert rel(a, b) <= 3e-2, name


@pytest.mark.parametrize("why,heads,kv_heads,hd,t,dtype", [
    ("a width that is no multiple of 128", 4, 2, 192, 64, BF16),
    ("heads narrower than a register", 4, 2, 32, 64, BF16),
    ("an untiled T", 4, 2, 128, 40, BF16),
    ("an odd T", 4, 2, 128, 33, BF16),
    ("float32 operands", 4, 2, 128, 64, F32),
    ("key-value heads that do not divide the heads", 4, 3, 128, 64, BF16),
])
def test_takes_refuses(why, heads, kv_heads, hd, t, dtype):
    h = jnp.zeros((1, t, 64), dtype)
    assert qk_assemble.tiles(t, heads, kv_heads, hd,
                             jnp.dtype(dtype).itemsize) is None, why
    assert not qk_assemble.takes(h, heads, kv_heads, hd), why
    with pytest.raises(ValueError, match="do not take"):
        qk_assemble.qk_assemble(
            jnp.zeros((1, t, heads * hd), dtype),
            jnp.zeros((1, t, kv_heads * hd), dtype),
            jnp.zeros((1, t, kv_heads * hd), dtype), jnp.ones((hd,)),
            jnp.ones((hd,)), jnp.arange(t), heads, THETA, EPS)


def test_takes_refuses_the_interpreter_under_check_vma(hvd):
    """Inside ``shard_map(check_vma=True)`` off a chip the operand varies
    over the mesh's axes and the interpreter cannot run there; outside it
    the same operand is taken."""
    from jax.sharding import PartitionSpec as P

    taken = []

    def body(h):
        taken.append(qk_assemble.takes(h, 4, 2, 128))
        return h

    h = jnp.zeros((hvd.mesh().size, 32, 64), BF16)
    jax.eval_shape(jax.shard_map(body, mesh=hvd.mesh(), in_specs=P("data"),
                                 out_specs=P("data"), check_vma=True), h)
    assert taken == [False]
    assert qk_assemble.takes(h, 4, 2, 128)


def test_tiles_and_vmem_at_the_published_widths():
    """Qwen3-30B-A3B's heads (32 over 4 of 128) at 16384 positions: tiles
    of 512, 44 MiB of the 64 a kernel may use; wider models take a smaller
    tile; the tile rule is latent attention's, from a larger most."""
    assert qk_assemble.tiles(16384, 32, 4, 128) == qk_assemble.TILE == 512
    assert (43 * 2 ** 20 < qk_assemble.vmem_bytes(512, 32, 4, 128)
            <= 45 * 2 ** 20 < head_major.VMEM_LIMIT)
    assert qk_assemble.tiles(16384, 128, 128, 256) == 32
    assert qk_assemble.tiles(8192 + 128, 32, 4, 128) == head_major.TILE == 128
    assert qk_assemble.tiles(48, 8, 1, 128) == head_major.ROWS == 16
    assert qk_assemble.takes(jnp.zeros((2, 64, 8), BF16), 4, 2, 128)
    assert not qk_assemble.takes(jnp.zeros((64, 8), BF16), 4, 2, 128)


def _ctx(route, seq_axis=None, model_axis=None, t=32):
    return parts.Ctx(model_axis, seq_axis, route,
                     jnp.arange(t, dtype=jnp.int32), 2 * t)


def test_qk_path_reads_the_part_the_route_and_the_widths(monkeypatch):
    """The kernels where the part norms a head at a time under rotary
    positions, the heads go to the flash kernels (or to the sparse route's)
    and ``qk_assemble.takes`` accepts them; ``qkv_proj``'s lines on every
    other route, under a sequence or a model axis, without the per-head
    norm, and at widths, lengths or dtypes the kernels refuse."""
    h = jnp.zeros((2, 32, 64), BF16)
    path = attention.qk_path
    assert path(h, QWEN_WIDE, _ctx("flash")) == "kernel"
    assert path(h, QWEN_WIDE, _ctx("ring_flash")) == "kernel"
    assert path(h, QWEN_WIDE, _ctx("local")) == "xla"
    assert path(h, QWEN_WIDE, _ctx("auto")) == "xla"
    assert path(h, QWEN_WIDE, _ctx("ring_flash", seq_axis="seq")) == "xla"
    assert path(h, QWEN_WIDE, _ctx("flash", model_axis="model")) == "xla"
    assert path(h[:, :24], QWEN_WIDE, _ctx("flash")) == "xla"
    assert path(h.astype(F32), QWEN_WIDE, _ctx("flash")) == "xla"
    for other in (dict(head_width=64), dict(qk_norm_per_head=False),
                  dict(qk_norm_per_head=False, qk_norm=True),
                  dict(positions="learned")):
        cfg = dataclasses.replace(QWEN_WIDE, **other)
        assert path(h, cfg, _ctx("flash")) == "xla", other
    # The sparse route asks its own kernels, whatever the route's name.
    assert path(h, QWEN_WIDE, _ctx("flash"), sparse=True) == "xla"
    monkeypatch.setattr(sa, "path", lambda x: "kernel")
    assert path(h, QWEN_WIDE, _ctx("local"), sparse=True) == "kernel"


def test_a_tiny_qwen_step_on_either_path():
    """The loss and the gradients of the attention's leaves with the heads
    made by the kernels (the flash route: named in the lowered text,
    forward and backward, and no repeat of K and V beside them) and by
    ``qkv_proj``'s lines (the local route, the same exact attention)."""
    from horovod_tpu.telemetry import scopes

    cfg = QWEN_WIDE
    params = tfm.init_params(jax.random.key(0), cfg)
    tokens = jax.random.randint(jax.random.key(1), (2, 32), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)

    def loss(route):
        return lambda p: tfm.loss_fn(p, tokens, labels, cfg, attention=route)

    text = jax.jit(jax.grad(loss("flash"))).lower(params).as_text(
        debug_info=True)
    for name in (scopes.FLASH_FWD, scopes.FLASH_BWD_DQ, scopes.FLASH_BWD_DKV):
        assert name in text, name
    # Booked where the per-head norm and the rotation were.
    for name in (scopes.QK_ASSEMBLE_FWD, scopes.QK_ASSEMBLE_BWD):
        assert (f"{scopes.ATTN_QKV}/{scopes.QK_HEAD_NORM_ROPE}/{name}"
                in text), name
    assert scopes.QK_ASSEMBLE_FWD not in jax.jit(loss("local")).lower(
        params).as_text(debug_info=True)
    got, got_grads = jax.value_and_grad(loss("flash"))(params)
    want, want_grads = jax.value_and_grad(loss("local"))(params)
    assert rel(got, want) <= 1e-2
    for name in ("wq", "wk", "wv", "wo", "q_norm_scale", "k_norm_scale"):
        assert rel(got_grads["layers"][0][name],
                   want_grads["layers"][0][name]) <= 3e-2, name


def test_the_counter_says_which_path_was_traced(hvd):
    """``hvd_qk_assemble_rows_total``: batch x T once an attention layer
    that norms a head at a time, labelled where the path is chosen, and no
    series for a part without the per-head norm."""
    from horovod_tpu import telemetry

    telemetry.reset_for_tests()
    telemetry.configure(True)
    try:
        tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
        plain = dataclasses.replace(QWEN_WIDE, qk_norm_per_head=False)
        for cfg, route in ((QWEN_WIDE, "flash"), (QWEN_WIDE, "local"),
                           (plain, "flash")):
            jax.eval_shape(
                lambda p, t: tfm.loss_fn(p, t, t, cfg, attention=route),
                tfm.init_abstract(cfg), tokens)
        text = telemetry.render_prometheus()
        lines = [line for line in text.splitlines()
                 if line.startswith("hvd_qk_assemble_rows_total{")]
        assert len(lines) == 4, text
        for layer in ("0", "1"):
            for path in ("kernel", "xla"):
                assert any(f'path="{path}"' in line and line.endswith(" 64")
                           and f'layer="{layer}"' in line
                           for line in lines), (layer, path, lines)
    finally:
        telemetry.reset_for_tests()
