"""SDAR's block-diffusion objective through the normal LM step, at tiny
widths that keep the shape of the thing: the Qwen3-MoE layer (grouped heads
whose total width is not the hidden size, QK-norm a head at a time,
softmax-routed SwiGLU experts of which this chip holds a share) run once
over a clean sequence and its noised copy under the block-diffusion mask,
with the weighted masked-token loss over the noised half; against the plain
reference of ``perfbench/reference/bd_moe_lm.py``, which shares no code
with the program and lays the two halves the other way round, and the
flash kernels of ``horovod_tpu/ops/flash_attention.py`` under the mask in
the interpreter against a dense masked softmax.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import moe
from horovod_tpu.models import transformer as tfm
from horovod_tpu.ops import flash_attention as fa
from horovod_tpu.parallel import sequence as seq_mod
from perfbench import kernel_cost_bd
from perfbench.reference import bd_moe_lm as reference

F32_REL = 5e-5

# The families every configuration shares, and the table of configurations
# (tests/test_lm_configs.py); those that compile this row's program run
# here, in the row's own file: a file is one worker's chain.
from test_lm_configs import *  # noqa: E402,F401,F403
from test_lm_configs import SDAR_TINY, sdar_dims as _dims  # noqa: E402

COSTLY_ROWS = ("sdar",)


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _brute_force(length, block):
    """[2 L, 2 L] bool from the four rules, the clean sequence first."""
    shown = np.zeros((2 * length, 2 * length), bool)
    for i in range(2 * length):
        for j in range(2 * length):
            bi, bj = (i % length) // block, (j % length) // block
            if i >= length:
                shown[i, j] = (bj == bi) if j >= length else (bj < bi)
            else:
                shown[i, j] = j < length and bj <= bi
    return shown


# --- the mask's description ---------------------------------------------------

# (length, diffusion block, block_q, block_k): every class occurs; the
# diffusion block smaller than a kernel block's half, equal to it, equal to
# the block; oblong blocks either way; a block that is no power of two; and
# blocks of 256, which the kernels work as sub-tiles.
BLOCKINGS = [(32, 4, 8, 8), (32, 4, 16, 8), (32, 4, 8, 16), (16, 4, 8, 8),
             (16, 4, 4, 4), (32, 4, 32, 32), (24, 6, 12, 6),
             (512, 4, 256, 256), (512, 128, 256, 256)]


@pytest.mark.parametrize("length,block,block_q,block_k", BLOCKINGS)
def test_the_description_counts_classes_and_names_live_blocks(
        length, block, block_q, block_k):
    """``visible`` is the four rules; ``block_classes`` is a brute-force
    count over them; needed is ``L^2 + L b``; a live grid step's index map
    names its own block and a skipped step's a live one of its row."""
    mask, t = fa.BlockDiffusion(length, block), 2 * length
    pos = np.arange(t)
    shown = np.asarray(mask.visible(pos[:, None], pos[None, :]))
    np.testing.assert_array_equal(shown, _brute_force(length, block))
    assert (shown.sum() == mask.needed(t) == length * (length + block)
            == kernel_cost_bd.needed_pairs(length, block))
    tile = lambda qi, kj: shown[qi * block_q:(qi + 1) * block_q,
                                kj * block_k:(kj + 1) * block_k]
    want = {"skipped": 0, "interior": 0, "diagonal": 0}
    for qi in range(t // block_q):
        for kj in range(t // block_k):
            want["skipped" if not tile(qi, kj).any() else
                 "interior" if tile(qi, kj).all() else "diagonal"] += 1
    classes = fa.block_classes(t, block_q, block_k, mask)
    assert {k: classes[k] for k in want} == want
    assert classes["needed"] <= classes["computed"] <= 4 * length * length
    if (block_q, block_k) == (block, block):
        assert classes["computed"] == classes["needed"]
    kv_map, q_map = mask.kv_map(block_q, block_k), mask.q_map(block_q,
                                                              block_k)
    for qi in range(t // block_q):
        for kj in range(t // block_k):
            named_k, named_q = int(kv_map(0, qi, kj)[1]), int(
                q_map(0, kj, qi)[1])
            if tile(qi, kj).any():
                assert (named_k, named_q) == (kj, qi)
            else:
                assert tile(qi, named_k).any(), (qi, kj, named_k)
                assert tile(named_q, kj).any(), (qi, kj, named_q)


def test_the_cells_blocking_computes_a_ninth_more_than_needed():
    """``sdar30b_bd8k``'s kernels: 1024-blocks over 2 x 8192 positions."""
    classes = fa.block_classes(16384, 1024, 1024,
                               fa.BlockDiffusion(8192, 4))
    assert (classes["skipped"], classes["interior"],
            classes["diagonal"]) == (176, 56, 24)
    # 56 whole tiles, 16 of three quarters, 8 of a half.
    assert classes["computed"] == 72 * 1024 * 1024
    assert classes["needed"] == 8192 * 8196
    causal = fa.block_classes(16384, 1024, 1024, True)
    assert causal["computed"] / classes["needed"] > 2.0


# --- the kernels under the mask -----------------------------------------------

def _dense(q, k, v, mask):
    pos = jnp.arange(q.shape[1])
    shown = mask.visible(pos[:, None], pos[None, :])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(shown[None, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("length,block,block_q,block_k", BLOCKINGS)
def test_flash_kernels_under_the_mask_match_the_masked_softmax(
        length, block, block_q, block_k):
    """Forward, dQ, dK and dV in the interpreter, where a query of the
    noised half's first block has met no key before its own tile."""
    mask = fa.BlockDiffusion(length, block)
    q, k, v, do = (jax.random.normal(key, (2, 2 * length, 2, 16))
                   for key in jax.random.split(jax.random.PRNGKey(length), 4))
    with jax.default_matmul_precision("highest"):
        out, pull = jax.vjp(lambda *a: fa.flash_attention(
            *a, mask, None, block_q, block_k, True), q, k, v)
        want, want_pull = jax.vjp(lambda *a: _dense(*a, mask), q, k, v)
        for got, ref in zip((out,) + pull(do), (want,) + want_pull(do)):
            assert np.isfinite(np.asarray(got)).all()
            assert _rel(got, ref) <= 1e-5
        local = seq_mod.local_attention(q, k, v, causal=mask)
    assert _rel(local, want) <= 1e-6


def test_folded_kernels_take_the_same_mask():
    mask = fa.BlockDiffusion(32, 4)
    q, k, v = (jax.random.normal(key, (4, 64, 16))
               for key in jax.random.split(jax.random.PRNGKey(3), 3))
    unfold = lambda x: x.reshape(2, 2, 64, 16).transpose(0, 2, 1, 3)
    got = fa.flash_attention_folded(q, k, v, 2, mask, None, 8, 8, True)
    want = fa.flash_attention(unfold(q), unfold(k), unfold(v), mask, None,
                              8, 8, True)
    np.testing.assert_allclose(unfold(got), want, rtol=1e-6, atol=1e-6)


def test_the_mask_refuses_what_it_cannot_tile_and_segment_ids():
    x = jnp.zeros((1, 64, 1, 16))
    mask = fa.BlockDiffusion(32, 4)
    with pytest.raises(NotImplementedError, match="segment_ids.*BlockDiff"):
        fa.flash_attention(x, x, x, mask, None, 8, 8, True,
                           jnp.zeros((1, 64), jnp.int32))
    with pytest.raises(ValueError, match="2 \\* length = 64 positions"):
        fa.flash_attention(x[:, :32], x[:, :32], x[:, :32], mask, None, 8,
                           8, True)
    with pytest.raises(ValueError, match="block_q=6 must be a multiple"):
        fa.block_classes(64, 6, 8, mask)
    with pytest.raises(ValueError, match="block_k=64 must be"):
        fa.block_classes(64, 8, 64, mask)
    with pytest.raises(ValueError, match="must tile"):
        fa.BlockDiffusion(30, 4)
    assert fa.as_mask(True) is fa.CAUSAL and fa.as_mask(False) is fa.FULL
    assert fa.as_mask(mask) is mask and hash(mask) == hash(
        fa.BlockDiffusion(32, 4))


# --- the objective against its definition -------------------------------------

def _params(cfg, seed=0):
    params = tfm.init_params(jax.random.PRNGKey(seed), cfg)
    # As the benchmark's adapter: at the program's 0.02 every token is the
    # same token to the router.
    params["embed"] = params["embed"] * 50.0
    return params


def _batch(cfg, batch=2, seq=32, seed=1):
    tokens = jax.random.randint(jax.random.PRNGKey(seed), (batch, seq), 0,
                                cfg.mask_token_id)
    return (tokens,) + tfm.diffusion_noise(
        jax.random.PRNGKey(seed + 1), batch, seq, cfg.diffusion_block)


def test_heads_born_in_the_kernels_layout_give_the_same_step(monkeypatch):
    """The tiny step at the least width ``qk_assemble`` takes (heads of a
    register, bfloat16), the flash route: with the heads normed, rotated
    and laid out by the kernels (in the interpreter; named in the lowered
    text beside the three flash kernels, and no repeat of K and V outside
    them) the loss and every checked leaf are ``qkv_proj``'s lines' within
    this table's bfloat16 tolerances (3e-3 the loss; a leaf far inside its
    0.3)."""
    from horovod_tpu.ops import qk_assemble
    from horovod_tpu.telemetry import scopes

    cfg = dataclasses.replace(SDAR_TINY, head_width=128, dtype=jnp.bfloat16)
    params, batch = _params(cfg), _batch(cfg)

    def step():
        return jax.jit(jax.value_and_grad(
            lambda p: tfm.diffusion_loss_fn(p, *batch, cfg, "flash")))

    text = step().lower(params).as_text(debug_info=True)
    for name in (scopes.QK_ASSEMBLE_FWD, scopes.QK_ASSEMBLE_BWD,
                 scopes.FLASH_FWD, scopes.FLASH_BWD_DQ, scopes.FLASH_BWD_DKV):
        assert name in text, name
    got, got_grads = step()(params)
    monkeypatch.setattr(qk_assemble, "takes", lambda *a: False)
    assert scopes.QK_ASSEMBLE_FWD not in step().lower(params).as_text(
        debug_info=True)
    want, want_grads = step()(params)
    assert np.isfinite(float(got)) and _rel(got, want) <= 3e-3
    for name, path in reference.leaf_paths(cfg.n_layers).items():
        assert _rel(reference.leaf(got_grads, path),
                    reference.leaf(want_grads, path)) <= 2e-2, name


def test_the_doubled_stream_is_the_objective_block_by_block():
    """One pass over 2 L positions gives what K passes give, one for each
    block over [the clean blocks before it; the block noised] with no
    doubled stream: the program's loss and the reference's, each its own
    layout, against the definition."""
    cfg = SDAR_TINY
    params, batch = _params(cfg), _batch(cfg, batch=1, seq=24)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, *b: reference.loss_block_by_block(
            p, *b, dims=_dims(cfg)))(params, *batch)
        ours = tfm.diffusion_loss_fn(params, *batch, cfg, "local")
        theirs = reference.loss_and_tail_grads(params, *batch,
                                               dims=_dims(cfg))[0]
    assert abs(ours - want) <= F32_REL * abs(want)
    assert abs(theirs - want) <= F32_REL * abs(want)


def test_the_loss_weighs_masked_tokens_by_their_blocks_rate():
    """Nothing but the masked positions of the noised half counts; the
    clean half's last hidden states and an unmasked token's label feed
    nothing; halving every rate doubles the loss."""
    cfg = SDAR_TINY
    params, (tokens, masked, rates) = _params(cfg), _batch(cfg)
    loss = jax.jit(lambda *batch: tfm.diffusion_loss_fn(
        params, *batch, cfg, "local"))
    with jax.default_matmul_precision("highest"):
        base = loss(tokens, masked, rates)
        assert abs(loss(tokens, masked, rates / 2) - 2 * base) <= 1e-5 * base
        assert float(loss(tokens, jnp.zeros_like(masked), rates)) == 0.0
        # Another token where the last block's copy is not noised.
        last = jnp.arange(tokens.shape[1]) >= tokens.shape[1] - 4
        swapped = jnp.where(last & ~masked, (tokens + 1) % 100, tokens)
        only_own = masked & last
        # (Its own block reads an unmasked token of the copy: an input.)
        assert loss(swapped, only_own, rates) != loss(tokens, only_own,
                                                      rates)
        head = jax.grad(lambda p: tfm.diffusion_loss_fn(
            p, tokens, masked, rates, cfg, "local"))(params)["head"]
    assert float(jnp.abs(head).max()) > 0


def test_the_noise_is_a_rate_a_block_and_a_coin_a_token():
    masked, rates = tfm.diffusion_noise(jax.random.PRNGKey(0), 64, 256, 4,
                                        t_min=0.25)
    assert masked.shape == (64, 256) and masked.dtype == bool
    assert rates.shape == (64, 64) and rates.dtype == jnp.float32
    assert 0.25 <= float(rates.min()) and float(rates.max()) <= 1.0
    # E[masked] = E[t] = 0.625; a block's share follows its own rate.
    assert abs(float(masked.mean()) - 0.625) < 0.02
    share = masked.reshape(64, 64, 4).mean(-1)
    assert np.corrcoef(np.asarray(share).ravel(),
                       np.asarray(rates).ravel())[0, 1] > 0.5


def test_the_shares_add_up():
    """What the eight chips of a deployment compute, 16 of 128 experts
    each (here: the four shares of 2 of 8), adds up to the uncut layer
    **under this model's input**: the doubled stream, a quarter of whose
    rows are the one mask row's."""
    cfg = SDAR_TINY
    params, (tokens, masked, _) = _params(cfg), _batch(cfg, seq=64)
    ids = jax.vmap(lambda t, m: reference.stream_ids(
        t, m, cfg.mask_token_id))(tokens, masked)
    u = params["embed"][ids]                               # [2, 128, 64]
    k = jax.random.split(jax.random.PRNGKey(7), 4)
    layer = {"router": jax.random.normal(k[0], (64, 8)) * 0.3}
    experts = {"w_gate": jax.random.normal(k[1], (8, 64, 48)) * 0.1,
               "w_up": jax.random.normal(k[2], (8, 64, 48)) * 0.1,
               "w_down": jax.random.normal(k[3], (8, 48, 64)) * 0.1}
    whole = dataclasses.replace(cfg, experts_held=0, experts_held_from=0)
    with jax.default_matmul_precision("highest"):
        want, rows = reference._moe_part(
            u.reshape(-1, 64), dict(layer, **experts),
            dict(_dims(cfg), held_from=0), None)
        total = jnp.zeros_like(u)
        for first in range(0, 8, 2):
            share = dataclasses.replace(cfg, experts_held=2,
                                        experts_held_from=first)
            held = {n: w[first:first + 2] for n, w in experts.items()}
            total = total + moe.moe_ffn(u, dict(layer, **held), share)[0]
        uncut = moe.moe_ffn(u, dict(layer, **experts), whole)[0]
    assert _rel(total.reshape(-1, 64), want) <= F32_REL
    assert _rel(uncut.reshape(-1, 64), want) <= F32_REL
    assert int(rows.sum()) == 256 * cfg.experts_per_token
    # The mask row's positions all go the same way.
    assert int(rows.max()) >= int(masked.sum())


# --- the step -------------------------------------------------------------------

def test_forward_and_generate_refuse_the_objective_by_name():
    cfg = SDAR_TINY
    params = tfm.init_abstract(cfg)
    tokens = jnp.zeros((2, 16), jnp.int32)
    with pytest.raises(NotImplementedError,
                       match="diffusion_block=4.*forward\\(\\) runs one"):
        jax.eval_shape(lambda p: tfm.forward(p, tokens, cfg,
                                             attention="local"), params)
    with pytest.raises(NotImplementedError,
                       match="decode_step.*diffusion_block=4"):
        jax.eval_shape(lambda p: tfm.generate(p, tokens, 32, cfg), params)
    with pytest.raises(ValueError, match="one rate for each block of 4"):
        tfm.diffusion_loss_fn(params, tokens, tokens > 0,
                              jnp.ones((2, 8)), cfg, "local")


def test_the_step_books_the_masks_blocks(hvd):
    """``hvd_flash_blocks_total`` and ``hvd_flash_computed_over_needed``
    count under the mask the step was traced with: needed is ``L^2 + L
    b``, not ``T (T + 1) / 2``."""
    from horovod_tpu import telemetry

    telemetry.reset_for_tests()
    telemetry.configure(True)
    try:
        cfg = SDAR_TINY
        batch = _batch(cfg, seq=64)
        jax.eval_shape(jax.grad(lambda p: tfm.diffusion_loss_fn(
            p, *batch, cfg, "flash")), tfm.init_abstract(cfg))
        text = telemetry.render_prometheus()
    finally:
        telemetry.reset_for_tests()
    # Blocks of 64 over 2 x 64 positions: clean x noised skipped, the
    # other three quadrants masked; 2 sequences x 4 heads x 2 layers.
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        for name, steps in (("skipped", 1), ("interior", 0),
                            ("diagonal", 3)):
            assert (f'hvd_flash_blocks_total{{class="{name}",'
                    f'kernel="{kernel}"}} {16 * steps}') in text, text
        assert (f'hvd_flash_computed_over_needed{{kernel="{kernel}"}} '
                f'{3 * 64 * 64 / (64 * 68)}') in text, text
