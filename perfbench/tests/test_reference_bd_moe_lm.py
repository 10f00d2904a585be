"""The plain SDAR reference against the program's model at the rehearsal
size of ``configs/sdar-30b-a3b-chat.json``, both in float32, where they
must agree to rounding (the program through its doubled stream, clean half
first, its sort and its grouped matmuls; the reference noised half first,
its own mask from the four rules and one expert after another), the
objective block by block, and the catalog row the configuration copies."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models import transformer as tfm
from perfbench import run
from perfbench.adapters import bd_moe_lm, moe_lm
from perfbench.reference import bd_moe_lm as reference

CONFIG = os.path.join(run.HERE, "configs", "sdar-30b-a3b-chat.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _setting(seq=64, batch=2):
    config = run._load(CONFIG, rehearse=True)
    cfg = bd_moe_lm.model_config(config, seq)
    cfg = tfm.TransformerConfig(**{**cfg.__dict__, "dtype": jnp.float32})
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    params["embed"] = params["embed"] * 50.0
    tokens = moe_lm.zipf_tokens(jax.random.PRNGKey(1), (batch, seq),
                                cfg.vocab_size - 1, 1.0)
    return cfg, params, (tokens,) + tfm.diffusion_noise(
        jax.random.PRNGKey(2), batch, seq, cfg.diffusion_block)


def test_bd_moe_lm_reference_matches_the_programs_model():
    cfg, params, batch = _setting()
    assert cfg.head_dim == 32 and cfg.kv_heads == 2
    assert cfg.held_experts == 4 and cfg.n_experts == 16
    assert cfg.diffusion_block == 4 and cfg.mask_token_id == 511
    assert int(jnp.max(batch[0])) < cfg.mask_token_id
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(tfm.diffusion_loss_fn)(
            params, *batch, cfg, "local")
    got_loss, got, stats = jax.jit(lambda *a: reference.loss_and_tail_grads(
        *a, dims=bd_moe_lm.reference_dims(cfg),
        names=tuple(reference.LEAVES)))(params, *batch)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    paths = reference.leaf_paths(cfg.n_layers)
    assert paths["wk_last"] == ("layers", 1, "wk")
    assert set(got) == set(paths) and len(paths) == 17
    for name, path in paths.items():
        grad = reference.leaf(want, path)
        assert float(jnp.linalg.norm(grad)) > 0, name
        err = float(jnp.linalg.norm(got[name] - grad)
                    / jnp.linalg.norm(grad))
        assert err < 5e-5, (name, err)
    assert stats["rows"].shape == (2, 4)
    assert 0.2 < float(stats["masked_share"]) < 0.8


def test_the_doubled_stream_is_the_objective_block_by_block():
    cfg, params, batch = _setting(seq=16, batch=1)
    dims = bd_moe_lm.reference_dims(cfg)
    doubled = reference.loss_and_tail_grads(params, *batch, dims=dims)[0]
    by_block = jax.jit(lambda *a: reference.loss_block_by_block(
        *a, dims=dims))(params, *batch)
    np.testing.assert_allclose(doubled, by_block, rtol=2e-5)


def test_visible_is_the_four_rules_and_the_controls_are_not():
    noised = jnp.arange(16) < 8
    pos = jnp.tile(jnp.arange(8), 2)
    shown = lambda rule: np.asarray(reference.visible(
        noised[:, None], pos[:, None], noised[None, :], pos[None, :], 4,
        rule))
    want = shown("block_diffusion")
    # Noised query 5 (block 1): its own block's copies, the clean block 0.
    np.testing.assert_array_equal(
        want[5], [0, 0, 0, 0, 1, 1, 1, 1, 1, 1, 1, 1, 0, 0, 0, 0])
    # Clean query 2 (block 0): its own clean block, both directions.
    np.testing.assert_array_equal(
        want[8 + 2], [0] * 8 + [1, 1, 1, 1, 0, 0, 0, 0])
    assert want.sum() == 8 * 8 + 8 * 4
    for rule in reference.RULES[1:]:
        assert (shown(rule) != want).any(), rule
    assert shown("own_clean_block").sum() == want.sum() + 8 * 4
    assert shown("noised_causal").sum() == want.sum() - 2 * 6
    assert shown("causal").sum() == 16 * 17 // 2


def test_configuration_holds_the_catalog_rows_numbers():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SDAR-30B-A3B-Chat")
    with open(CONFIG) as f:
        config = json.load(f)
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "sdar-30b-a3b-chat")
    assert entry["source"] == row["source_url"] == config["source"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(entry["reduced"]) == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert config["published"] == {k: row["config"][k] for k in differs}
    assert config["num_hidden_layers"] >= 4 and config["num_experts"] == 16
    assert config["vocab_size"] * 8 == row["config"]["vocab_size"]
    for line in ("block_length", "noise_rate", "no_label_shift", "qkv",
                 "mask_id"):
        assert line in config["assumed"], line
