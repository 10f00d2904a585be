"""Pallas flash attention vs the lax oracle (interpret mode on CPU).

The kernel (`ops/flash_attention.py`) runs here through the Pallas
interpreter — same kernel code, CPU-executable — against
`parallel/sequence.local_attention`, the straightforward lax softmax
attention the SP tests already use as their numerical oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel.sequence import local_attention


def _make_qkv(rs, b=2, t=256, h=3, d=32, dtype=jnp.float32):
    q = jnp.asarray(rs.standard_normal((b, t, h, d)), dtype)
    k = jnp.asarray(rs.standard_normal((b, t, h, d)), dtype)
    v = jnp.asarray(rs.standard_normal((b, t, h, d)), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches_oracle(causal):
    rs = np.random.default_rng(0)
    q, k, v = _make_qkv(rs)
    out = flash_attention(q, k, v, causal, None, 64, 64, True)
    ref = local_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_gradients_match_oracle(causal):
    rs = np.random.default_rng(1)
    q, k, v = _make_qkv(rs, t=128, d=16)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal, None, 32, 32, True)
        return jnp.sum(o * (o + 1.0))

    def loss_ref(q, k, v):
        o = local_attention(q, k, v, causal=causal)
        return jnp.sum(o * (o + 1.0))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{nm} mismatch")


def test_uneven_blocks():
    """block_q != block_k and blocks not dividing each other's multiples."""
    rs = np.random.default_rng(2)
    q, k, v = _make_qkv(rs, t=192, d=16)
    out = flash_attention(q, k, v, True, None, 64, 32, True)
    ref = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_bf16_inputs():
    rs = np.random.default_rng(3)
    q, k, v = _make_qkv(rs, t=128, d=32, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, True, None, 64, 64, True)
    ref = local_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(out, dtype=np.float32),
                               np.asarray(ref), rtol=3e-2, atol=3e-2)


def test_custom_scale():
    rs = np.random.default_rng(4)
    q, k, v = _make_qkv(rs, t=128, d=16)
    out = flash_attention(q, k, v, False, 0.5, 64, 64, True)
    ref = local_attention(q, k, v, causal=False, scale=0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_rejects_ragged_sequence():
    rs = np.random.default_rng(5)
    q, k, v = _make_qkv(rs, t=100, d=16)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, v, True, None, 64, 64, True)


def test_short_sequence_block_clamp():
    """T smaller than the default blocks clamps instead of failing."""
    rs = np.random.default_rng(6)
    q, k, v = _make_qkv(rs, t=64, d=16)
    out = flash_attention(q, k, v, True, None, 128, 128, True)
    ref = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_transformer_flash_path():
    """The transformer's attention="flash" route matches the lax route."""
    from horovod_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                d_ff=64, n_layers=1, max_seq=64,
                                dtype=jnp.float32)
    rs = np.random.default_rng(7)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(rs.integers(0, 64, (2, 64)), jnp.int32)
    a = tfm.forward(params, tokens, cfg, attention="flash")
    b = tfm.forward(params, tokens, cfg, attention="local")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-2,
                               atol=2e-2)


def test_flash_rejected_under_sequence_axis():
    """flash + seq_axis must error, never silently run a different
    algorithm."""
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.topology import build_mesh
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                d_ff=64, n_layers=1, max_seq=64,
                                dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((1, 64), jnp.int32)
    mesh = build_mesh(axes=("seq",), shape=(2,))
    with pytest.raises(ValueError, match="ring.*ulysses|not available"):
        jax.shard_map(
            lambda p, t: tfm.forward(p, t, cfg, seq_axis="seq",
                                     attention="flash"),
            mesh=mesh,
            in_specs=(jax.sharding.PartitionSpec(),
                      jax.sharding.PartitionSpec(None, "seq")),
            out_specs=jax.sharding.PartitionSpec(None, "seq"),
            check_vma=False)(params, tokens)


def _masked_oracle(q, k, v, seg, causal):
    """Dense attention with explicit segment (+causal) masking."""
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (d ** -0.5)
    mask = seg[:, None, :, None] == seg[:, None, None, :]
    if causal:
        t = q.shape[1]
        mask = mask & jnp.tril(jnp.ones((t, t), bool))[None, None]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    # Fully-masked rows (impossible here: diagonal always valid) guard:
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [True, False])
def test_segment_ids_forward(causal):
    """Sequence packing: tokens attend only within their own segment."""
    rs = np.random.default_rng(10)
    q, k, v = _make_qkv(rs, b=2, t=128, h=2, d=16)
    # 3 packed segments of uneven lengths per batch row.
    seg = jnp.asarray(
        np.concatenate([np.zeros(40), np.ones(56), np.full(32, 2)]
                       ).astype(np.int32)[None].repeat(2, 0))
    out = flash_attention(q, k, v, causal, None, 32, 32, True,
                          segment_ids=seg)
    ref = _masked_oracle(q, k, v, seg, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # Cross-check: segment isolation means each segment equals attention
    # run on it alone.
    alone = flash_attention(q[:, :40], k[:, :40], v[:, :40], causal,
                            None, 8, 8, True)
    np.testing.assert_allclose(np.asarray(out[:, :40]), np.asarray(alone),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [True, False])
def test_segment_ids_gradients(causal):
    """Backward with segment masking matches the masked oracle's grads."""
    rs = np.random.default_rng(11)
    q, k, v = _make_qkv(rs, b=1, t=64, h=2, d=16)
    seg = jnp.asarray(np.concatenate(
        [np.zeros(24), np.ones(40)]).astype(np.int32)[None])

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal, None, 32, 32, True,
                            segment_ids=seg)
        return jnp.sum(o * (o + 1.0))

    def loss_ref(q, k, v):
        o = _masked_oracle(q, k, v, seg, causal)
        return jnp.sum(o * (o + 1.0))

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, nm in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{nm} mismatch")


def test_segment_ids_validation():
    rs = np.random.default_rng(12)
    q, k, v = _make_qkv(rs, b=2, t=64, h=2, d=16)
    with pytest.raises(ValueError, match="segment_ids must be \\[B, T\\]"):
        flash_attention(q, k, v, True, None, 32, 32, True,
                        segment_ids=jnp.zeros((2, 32), jnp.int32))
    with pytest.raises(ValueError, match="integer"):
        flash_attention(q, k, v, True, None, 32, 32, True,
                        segment_ids=jnp.zeros((2, 64), jnp.float32))


def test_transformer_packed_sequences():
    """forward(segment_ids=...) masks cross-segment attention on both the
    local and flash routes, and the two agree; the packed forward equals
    running each segment separately."""
    from horovod_tpu.models import transformer as tfm
    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                d_ff=64, n_layers=1, max_seq=64,
                                dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rs = np.random.default_rng(13)
    tokens = jnp.asarray(rs.integers(0, 64, (1, 64)), jnp.int32)
    seg = jnp.asarray(np.concatenate(
        [np.zeros(24), np.ones(40)]).astype(np.int32)[None])

    a = tfm.forward(params, tokens, cfg, attention="local",
                    segment_ids=seg)
    b = tfm.forward(params, tokens, cfg, attention="flash",
                    segment_ids=seg)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                               atol=2e-4)

    # Positional embeddings differ per absolute position, so compare the
    # FIRST segment (positions align) against a stand-alone run.
    alone = tfm.forward(params, tokens[:, :24], cfg, attention="local")
    np.testing.assert_allclose(np.asarray(a[:, :24]), np.asarray(alone),
                               rtol=2e-4, atol=2e-4)
    # (The SP routes used to reject segment_ids; they are now supported —
    # seq-sharded coverage lives in test_parallel.py and
    # test_packed_train_step_seq_sharded below.)


def test_packed_train_step(hvd, mesh8):
    """make_train_step(packed=True) threads segment_ids into the jitted
    SPMD step (DP over 8 devices, local attention)."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                                d_ff=32, n_layers=1, max_seq=16,
                                dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adam(1e-2)
    step, specs, opt_specs = tfm.make_train_step(
        cfg, opt, mesh8, data_axis="data", attention="local", packed=True)
    params = jax.device_put(params, jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh8, s), specs))
    opt_state = jax.device_put(opt.init(params), jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh8, s), opt_specs,
        is_leaf=lambda x: isinstance(x, P)))

    rng = np.random.default_rng(3)
    sh = NamedSharding(mesh8, P("data"))
    seg = jax.device_put(jnp.asarray(np.concatenate(
        [np.zeros(8), np.ones(8)]).astype(np.int32)[None].repeat(8, 0)),
        sh)
    losses = []
    for _ in range(5):
        toks = jax.device_put(
            jnp.asarray(rng.integers(0, 32, (8, 16)), jnp.int32), sh)
        labs = jax.device_put(
            jnp.asarray(np.roll(np.asarray(toks), -1, 1), jnp.int32), sh)
        params, opt_state, loss = step(params, opt_state, toks, labs, seg)
        losses.append(float(np.asarray(loss)))
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < losses[0], losses


def test_packed_train_step_seq_sharded(hvd):
    """The two-packed-languages train step on a SEQ-SHARDED mesh
    (ring attention): segment_ids reach the SP route and the step learns
    both packed languages — previously rejected with ValueError."""
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.topology import build_mesh

    cfg = tfm.TransformerConfig(vocab_size=32, d_model=32, n_heads=2,
                                d_ff=64, n_layers=1, max_seq=16,
                                dtype=jnp.float32)
    mesh = build_mesh(axes=("data", "seq"), shape=(2, 4))
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    opt = optax.adam(1e-2)
    step, specs, opt_specs = tfm.make_train_step(
        cfg, opt, mesh, data_axis="data", seq_axis="seq",
        attention="ring", packed=True)
    params = jax.device_put(params, jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs))
    opt_state = jax.device_put(opt.init(params), jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), opt_specs,
        is_leaf=lambda x: isinstance(x, P)))

    # Two "languages" packed per row: segment 0 counts +1, segment 1
    # counts +2 (mod 32).  Boundary at 8 (not on every 4-wide shard edge).
    rng = np.random.default_rng(5)
    sh = NamedSharding(mesh, P("data", "seq"))
    seg = jax.device_put(jnp.asarray(np.concatenate(
        [np.zeros(8), np.ones(8)]).astype(np.int32)[None].repeat(4, 0)),
        sh)
    losses = []
    for _ in range(30):
        s0 = rng.integers(0, 32, (4, 1))
        s1 = rng.integers(0, 32, (4, 1))
        a = (s0 + np.arange(9)) % 32          # +1 language, 9 tokens
        b = (s1 + 2 * np.arange(9)) % 32      # +2 language, 9 tokens
        toks = np.concatenate([a[:, :-1], b[:, :-1]], axis=1)
        labs = np.concatenate([a[:, 1:], b[:, 1:]], axis=1)
        toks = jax.device_put(jnp.asarray(toks, jnp.int32), sh)
        labs = jax.device_put(jnp.asarray(labs, jnp.int32), sh)
        params, opt_state, loss = step(params, opt_state, toks, labs, seg)
        losses.append(float(np.asarray(loss)))
    assert np.isfinite(losses).all(), losses
    assert losses[-1] < 0.7 * losses[0], (losses[0], losses[-1])


def test_attention_auto_dispatch(hvd, monkeypatch):
    """attention='auto' picks local below the crossover (exactly equals
    the local route) and the flash kernel above it (still equals local —
    same math — proving the flash route was viable where chosen)."""
    from horovod_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=32, d_model=32, n_heads=2,
                                d_ff=64, n_layers=1, max_seq=256,
                                dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(17)

    # small T: auto == local (flash would need T%128==0 anyway at 96)
    toks = jnp.asarray(rng.integers(0, 32, (1, 96)), jnp.int32)
    a = tfm.forward(params, toks, cfg, attention="auto")
    b = tfm.forward(params, toks, cfg, attention="local")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)

    # above the (lowered) threshold: auto takes the flash kernel
    monkeypatch.setenv("HOROVOD_FLASH_AUTO_MIN_T", "256")
    toks = jnp.asarray(rng.integers(0, 32, (1, 256)), jnp.int32)
    a = tfm.forward(params, toks, cfg, attention="auto")
    f = tfm.forward(params, toks, cfg, attention="flash")
    b = tfm.forward(params, toks, cfg, attention="local")
    np.testing.assert_allclose(np.asarray(a), np.asarray(f), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                               atol=2e-4)


def test_attention_auto_never_raises_on_shape(hvd):
    """T=1992 is above the auto threshold but not 128-divisible: the
    flash kernel cannot tile it, so ``attention="auto"`` must silently
    take the lax path (no shape may make ``auto`` fail;
    only an explicit ``attention="flash"`` may raise)."""
    from horovod_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=32, d_model=32, n_heads=2,
                                d_ff=64, n_layers=1, max_seq=2048,
                                dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(23)
    toks = jnp.asarray(rng.integers(0, 32, (1, 1992)), jnp.int32)
    a = jax.jit(lambda p, t: tfm.forward(p, t, cfg, attention="auto"))(
        params, toks)
    b = jax.jit(lambda p, t: tfm.forward(p, t, cfg, attention="local"))(
        params, toks)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
    # the explicit kernel request still raises the actionable error
    with pytest.raises(ValueError, match="divisible by 128"):
        tfm.forward(params, toks, cfg, attention="flash")


def test_auto_blocks_default_path():
    """The DEFAULT (auto) block path — the only form the transformer
    uses — matches the oracle, and non-128-divisible lengths fail with
    the actionable pad-the-sequence error instead of a degenerate grid."""
    rs = np.random.default_rng(20)
    q, k, v = _make_qkv(rs, t=256, d=32)
    out = flash_attention(q, k, v, True)          # block_q=block_k=None
    ref = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    # auto floor: T=1992 is 8-divisible but not 128-divisible
    qb, kb, vb = _make_qkv(rs, t=1992, d=16, b=1, h=1)
    with pytest.raises(ValueError, match="divisible by 128"):
        flash_attention(qb, kb, vb, True)
    # short-T clamp path still works through auto
    qs, ks, vs = _make_qkv(rs, t=64, d=16)
    outs = flash_attention(qs, ks, vs, True)
    refs = local_attention(qs, ks, vs, causal=True)
    np.testing.assert_allclose(np.asarray(outs), np.asarray(refs),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Block classes: above the diagonal (skipped), interior (no causal mask),
# diagonal (masked; square blocks >= 256 as 2x2 sub-tiles without the
# upper-right one).  Explicit blocks so one call holds every class.
# ---------------------------------------------------------------------------

def _packed_ids(t, b=1):
    """Three documents; the first boundary (at 0.39 T) falls inside the
    interior block under the diagonal for every blocking used below."""
    cuts = [0, int(0.39 * t), int(0.59 * t), t]
    ids = np.zeros((b, t), np.int32)
    for i in range(3):
        ids[:, cuts[i]:cuts[i + 1]] = i
    return jnp.asarray(ids)


_CLASS_BLOCKINGS = [
    pytest.param(512, 128, 128, id="t512-b128"),           # whole diagonal
    pytest.param(512, 256, 256, id="t512-b256-subtiles"),  # 2x2 sub-tiles
    pytest.param(512, 256, 128, id="t512-bq256-bk128"),    # sub-tiles off
    pytest.param(512, 128, 256, id="t512-bq128-bk256"),
    pytest.param(256, 256, 256, id="one-block-subtiles"),  # all diagonal
    pytest.param(128, 128, 128, id="one-block"),
]


@pytest.mark.parametrize("segments", [False, True],
                         ids=["plain", "segment_ids"])
@pytest.mark.parametrize("causal", [True, False],
                         ids=["causal", "full"])
@pytest.mark.parametrize("t,block_q,block_k", _CLASS_BLOCKINGS)
def test_block_classes_match_oracle(t, block_q, block_k, causal, segments):
    """Forward and dQ, dK, dV of every block class against the lax
    oracle."""
    rs = np.random.default_rng(30)
    q, k, v = _make_qkv(rs, b=1, t=t, h=2, d=32)
    seg = _packed_ids(t) if segments else None

    def loss(attn):
        def f(q, k, v):
            o = attn(q, k, v)
            return jnp.sum(o * (o + 1.0)), o
        return jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)

    (_, out), gf = loss(lambda q, k, v: flash_attention(
        q, k, v, causal, None, block_q, block_k, True, seg))(q, k, v)
    (_, ref), gr = loss(lambda q, k, v: local_attention(
        q, k, v, causal=causal, segment_ids=seg))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    for a, b, nm in zip(gf, gr, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{nm} mismatch")


@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("block", [128, 256])
def test_rotated_k_parts_match_lax_twin(block, causal):
    """The ring route's per-step call: K/V and the K-side segment ids of
    another shard (``causal=False`` off the diagonal step), rows that meet
    no key of their document in this block (m = -inf, l = 0, zero output
    and zero gradients), and the global (m, l) handed to the backward."""
    from horovod_tpu.ops import flash_attention as fa
    from horovod_tpu.parallel import sequence as sp

    rs = np.random.default_rng(31)
    h, t, d = 2, 512, 32
    qf, kf, vf, dof = (jnp.asarray(rs.standard_normal((h, t, d)),
                                   jnp.float32) for _ in range(4))
    # q side: documents 0 | 1 | 2; the arriving K block holds 1 | 2 | 3,
    # cut elsewhere: document 0's rows are fully masked.
    qseg = np.zeros((1, 1, t), np.int32)
    qseg[..., 150:330] = 1
    qseg[..., 330:] = 2
    kseg = np.full((1, 1, t), 1, np.int32)
    kseg[..., 200:420] = 2
    kseg[..., 420:] = 3
    qseg, kseg = jnp.asarray(qseg), jnp.asarray(kseg)
    scale = d ** -0.5

    got = fa._fwd_parts(qf, kf, vf, qseg, kseg, h, causal, scale, block,
                        block, True)
    want = sp._lax_fwd_parts(qf, kf, vf, qseg, kseg, h, causal, scale,
                             block, block, True)
    o, m, l = got
    assert np.isneginf(np.asarray(m)[:, 0, :150]).all()
    assert (np.asarray(l)[:, 0, :150] == 0).all()
    assert (np.asarray(o)[:, :150] == 0).all()
    for a, b, nm in zip(got, want, ("o", "m", "l")):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5, err_msg=nm)

    grads = fa._bwd_parts(qf, kf, vf, o, dof, m, l, qseg, kseg, h, causal,
                          scale, block, block, True)
    twin = sp._lax_bwd_parts(qf, kf, vf, o, dof, m, l, qseg, kseg, h,
                             causal, scale, block, block, True)
    assert (np.asarray(grads[0])[:, :150] == 0).all()
    for a, b, nm in zip(grads, twin, ("dq", "dk", "dv")):
        assert np.isfinite(np.asarray(a)).all(), nm
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=nm)


@pytest.mark.parametrize("t,per_head,ratio", [
    # gpt67_t8192: 28 interior + 8 diagonal of 64, 34 block-equivalents.
    (8192, {"skipped": 28, "interior": 28, "diagonal": 8}, 34 / 32),
    # gpt67_t2048: 1 + 2 of 4, 2.5 block-equivalents for 2.0.
    (2048, {"skipped": 1, "interior": 1, "diagonal": 2}, 2.5 / 2.0),
])
def test_block_class_counts_at_the_cell_shapes(t, per_head, ratio):
    from horovod_tpu.ops import flash_attention as fa

    block = fa._auto_block(t, 128)
    assert block == 1024
    got = fa.block_classes(t, block, block, True)
    assert {k: got[k] for k in per_head} == per_head
    assert got["needed"] == t * (t + 1) // 2
    # The issue's figures take the needed elements as T^2 / 2.
    assert got["computed"] / (t * t / 2) == pytest.approx(ratio)
    assert got["computed"] / got["needed"] == pytest.approx(ratio, rel=1e-3)
    # Without the causal flag nothing is skipped or masked.
    full = fa.block_classes(t, block, block, False)
    assert (full["skipped"], full["diagonal"]) == (0, 0)
    assert full["computed"] == full["needed"] == t * t


def test_block_class_counters_are_recorded_at_trace_time():
    """``hvd_flash_blocks_total{kernel,class}`` and
    ``hvd_flash_computed_over_needed{kernel}`` for one traced
    forward + backward (B*H = 2, T = 512, blocks 256: per head 1 skipped,
    1 interior, 2 diagonal of 3/4 block each)."""
    from horovod_tpu import telemetry
    from horovod_tpu.telemetry import aggregate

    rs = np.random.default_rng(32)
    q, k, v = _make_qkv(rs, b=1, t=512, h=2, d=16)
    telemetry.registry().clear()
    telemetry.configure(enabled_flag=True)
    try:
        jax.grad(lambda q: jnp.sum(flash_attention(
            q, k, v, True, None, 256, 256, True)))(q)
        snap = telemetry.metrics_snapshot()
        for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
            for cls, n in (("skipped", 2), ("interior", 2),
                           ("diagonal", 4)):
                assert aggregate.counter_total(
                    snap, "hvd_flash_blocks_total",
                    {"kernel": kernel, "class": cls}) == n, (kernel, cls)
        ratios = {e["labels"]["kernel"]: e["value"] for e in
                  snap["hvd_flash_computed_over_needed"]["values"]}
        assert ratios == pytest.approx(dict.fromkeys(
            ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"),
            2.5 * 256 * 256 / (512 * 513 // 2)))
    finally:
        telemetry.configure(enabled_flag=False)
        telemetry.registry().clear()


def test_metrics_doc_names_the_flash_series():
    import os
    doc = open(os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "metrics.md")).read()
    assert "`hvd_flash_blocks_total`" in doc
    assert "`hvd_flash_computed_over_needed`" in doc
