"""The plain Nemotron-3 reference against the program's model at the
rehearsal size of ``configs/nemotron-3-super-120b-a12b.json``, both in
float32, where they must agree to rounding (the program through its
chunked recurrence, its sort and its grouped matmuls, the reference one
token at a time and one expert after another), and the catalog row the
configuration copies."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import transformer as tfm
from perfbench import run
from perfbench.adapters import moe_lm, ssm_moe_lm
from perfbench.reference import ssm_moe_lm as reference

CONFIG = os.path.join(run.HERE, "configs", "nemotron-3-super-120b-a12b.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PERIOD = tuple(ssm_moe_lm.KINDS[c] for c in "MEMEMEM*EME")


def _setting(seq=256):
    config = run._load(CONFIG, rehearse=True)
    cfg = ssm_moe_lm.model_config(config, seq)
    cfg = tfm.TransformerConfig(**{**cfg.__dict__, "dtype": jnp.float32})
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    params["embed"] = params["embed"] * 50.0
    tokens = moe_lm.zipf_tokens(jax.random.PRNGKey(1), (2, seq + 1),
                                cfg.vocab_size, 1.0)
    return cfg, params, tokens[:, :-1], tokens[:, 1:]


def _reference(cfg, *arrays, names=tuple(reference.LEAVES), **kw):
    """Every leaf the reference can differentiate, not only the cell's."""
    return jax.jit(lambda *a: reference.loss_and_tail_grads(
        *a, dims=ssm_moe_lm.reference_dims(cfg), layer_types=cfg.layer_types,
        mtp_layer_types=cfg.mtp_layer_types, mtp_coef=cfg.mtp_loss_coef,
        names=names, **kw))(*arrays)


def test_ssm_moe_lm_reference_matches_the_programs_model():
    cfg, params, tokens, labels = _setting()
    assert cfg.layer_types == PERIOD
    assert cfg.mtp_layer_types == ("attention", "mlp")
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(tfm.loss_fn)(
            params, tokens, labels, cfg, attention="local")
    got_loss, got, stats = _reference(cfg, params, tokens, labels)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    paths = reference.leaf_paths(cfg.layer_types)
    assert paths["ssm_a_log_last"] == ("layers", 9, "ssm_a_log")
    assert paths["w_down_last"] == ("layers", 10, "w_down")
    assert set(got) == set(paths) and len(paths) == 9
    for name, path in paths.items():
        grad = reference.leaf(want, path)
        error = np.linalg.norm(got[name] - grad) / np.linalg.norm(grad)
        assert error < 2e-4, (name, error)
    # The cell's leaves are the default, and the same numbers.
    _, cells, _ = _reference(cfg, params, tokens, labels,
                             names=reference.CHECKED)
    assert set(cells) == set(reference.CHECKED) < set(paths)
    for name, grad in cells.items():
        np.testing.assert_allclose(grad, got[name], rtol=1e-5, atol=1e-9)
    # a_t in (0, 1): one row a Mamba-2 layer; rows of the four held
    # experts in each of the six expert layers, at most one a token each.
    decay, rows = np.asarray(stats["decay"]), np.asarray(stats["rows"])
    assert decay.shape == (5, 3) and (decay > 0).all() and (decay < 1).all()
    assert (np.diff(decay, axis=1) >= 0).all()
    assert rows.shape == (6, 4) and (rows <= tokens.size).all()
    assert 0 < rows.sum() <= 6 * tokens.size * 4


@pytest.mark.parametrize("variant,leaf", [
    (dict(reset_every=32), "ssm_w_out_last"),
    (dict(shared_expert=False), "ln_f_scale"),
    (dict(low_precision=jnp.float8_e4m3fn), "w_down_last")],
    ids=["carried_state_zeroed", "no_shared_expert", "float8_operands"])
def test_the_variants_that_must_not_pass_are_other_functions(variant, leaf):
    cfg, params, tokens, labels = _setting(seq=128)
    _, whole, _ = _reference(cfg, params, tokens, labels)
    _, other, _ = _reference(cfg, params, tokens, labels, **variant)
    error = (np.linalg.norm(other[leaf] - whole[leaf])
             / np.linalg.norm(whole[leaf]))
    assert error > 0.05, error


def test_configuration_holds_the_catalog_rows_numbers():
    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
    with open(CONFIG) as f:
        config = json.load(f)
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if config[k] != v)
    assert differs == sorted(config["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert config["published"] == {k: row["config"][k] for k in differs}
    # One whole period of the published pattern, the router's width and
    # the choice as published, 1/64 of the experts, 1/8 of the rows.
    assert ssm_moe_lm.layer_types(config) == PERIOD
    pattern = row["config"]["hybrid_override_pattern"]
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) \
        == (40, 40, 8)
    assert config["router_width"] == row["config"]["n_routed_experts"]
    assert config["n_routed_experts"] * 64 == config["router_width"]
    assert config["vocab_size"] * 8 == row["config"]["vocab_size"]
    held = range(config["experts_held_from"],
                 config["experts_held_from"] + config["n_routed_experts"])
    assert held.start % 8 == 0 and held.stop <= config["router_width"]


def test_the_compiled_widths_are_the_published_ones():
    with open(CONFIG) as f:
        config = json.load(f)
    cfg = ssm_moe_lm.model_config(config, 8192)
    shapes = tfm.init_abstract(cfg)
    mamba, experts, attention = (shapes["layers"][i] for i in (0, 1, 7))
    assert mamba["ssm_w_in"].shape == (4096, 18560)
    assert mamba["ssm_w_out"].shape == (8192, 4096)
    assert mamba["ssm_conv"].shape == (4, 10240)
    assert mamba["ssm_a_log"].shape == (128,)
    assert (cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups,
            cfg.ssm_chunk) == (128, 64, 128, 8, 128)
    assert attention["wq"].shape == (4096, 4096)
    assert attention["wk"].shape == attention["wv"].shape == (4096, 256)
    assert experts["router"].shape == (4096, 512)
    assert experts["w_latent_in"].shape == (4096, 1024)
    assert experts["w_up"].shape == (8, 1024, 2688)
    assert experts["w_down"].shape == (8, 2688, 1024)
    assert experts["w_shared_up"].shape == (4096, 5376)
    assert (cfg.experts_per_token, cfg.routed_scale) == (22, 5.0)
    assert shapes["mtp"]["w_eh"].shape == (8192, 4096)
    total = sum(leaf.size for leaf in jax.tree_util.tree_leaves(shapes))
    assert total == 1_378_724_736


def test_adapter_draws_the_decay_inside_the_configurations_ranges():
    with open(CONFIG) as f:
        config = json.load(f)
    a_log, dt_bias = ssm_moe_lm.draw_decay(jax.random.PRNGKey(2), 4096,
                                           config)
    a, dt = np.exp(a_log), np.log1p(np.exp(np.asarray(dt_bias)))
    lo, hi = config["a_init_range"]
    assert lo <= a.min() < lo * 1.05 and hi * 0.95 < a.max() <= hi
    lo, hi = config["time_step_min"], config["time_step_max"]
    assert lo * 0.999 <= dt.min() < lo * 1.1
    assert hi * 0.9 < dt.max() <= hi * 1.001
