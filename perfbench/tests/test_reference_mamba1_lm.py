"""The plain Jamba reference against the program's model at the rehearsal
size of ``configs/ai21-jamba2-3b.json``, both in float32, where they must
agree to rounding (the program through its blocked scan, the reference one
token at a time), and the catalog row the configuration copies."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import transformer as tfm
from perfbench import run
from perfbench.adapters import mamba1_lm, moe_lm
from perfbench.reference import mamba1_lm as reference

CONFIG = os.path.join(run.HERE, "configs", "ai21-jamba2-3b.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PERIOD = ("mamba",) * 7 + ("full_attention",) + ("mamba",) * 6


def _setting(seq=256):
    config = run._load(CONFIG, rehearse=True)
    cfg = mamba1_lm.model_config(config, seq)
    cfg = tfm.TransformerConfig(**{**cfg.__dict__, "dtype": jnp.float32})
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    params["embed"] = params["embed"] * 5.0
    tokens = moe_lm.zipf_tokens(jax.random.PRNGKey(1), (2, seq + 1),
                                cfg.vocab_size, 1.0)
    return cfg, params, tokens[:, :-1], tokens[:, 1:]


def _reference(cfg, *arrays, names=tuple(reference.LEAVES), **kw):
    """Every leaf the reference can differentiate, not only the cell's."""
    return jax.jit(lambda *a: reference.loss_and_tail_grads(
        *a, dims=mamba1_lm.reference_dims(cfg), layer_types=cfg.layer_types,
        names=names, stats=True, **kw))(*arrays)


def test_mamba1_lm_reference_matches_the_programs_model():
    cfg, params, tokens, labels = _setting()
    assert cfg.layer_types == ("mamba", "mamba", "full_attention", "mamba")
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(tfm.loss_fn)(
            params, tokens, labels, cfg, attention="local")
    got_loss, got, stats = _reference(cfg, params, tokens, labels)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    paths = reference.leaf_paths(cfg.layer_types)
    assert paths["mamba_a_log_last"] == ("layers", 3, "mamba_a_log")
    assert paths["wk_attn"] == ("layers", 2, "wk")
    assert set(got) == set(paths) and len(paths) == 19
    for name, path in paths.items():
        grad = reference.leaf(want, path)
        error = np.linalg.norm(got[name] - grad) / np.linalg.norm(grad)
        assert error < 2e-4, (name, error)
    # The cell's leaves are the default, and the same numbers.
    _, cells, _ = _reference(cfg, params, tokens, labels,
                             names=reference.CHECKED)
    assert set(cells) == set(reference.CHECKED) < set(paths)
    for name, grad in cells.items():
        assert (np.linalg.norm(grad - got[name])
                <= 1e-5 * np.linalg.norm(got[name])), name
    # exp(delta A) in (0, 1) and delta > 0: one row a Mamba layer.
    decay, delta = np.asarray(stats["decay"]), np.asarray(stats["delta"])
    assert decay.shape == delta.shape == (3, 3)
    assert (decay > 0).all() and (decay < 1).all() and (delta > 0).all()
    assert (np.diff(decay, axis=1) >= 0).all()


@pytest.mark.parametrize("variant,leaf", [
    (dict(reset_every=32), "mamba_a_log_last"),
    (dict(one_decay=True), "mamba_a_log_last"),
    (dict(inner_norms=False), "mamba_w_dt_last"),
    (dict(skip=False), "mamba_d_last"),
    (dict(independent_kv=True), "wk_attn"),
    (dict(low_precision=jnp.float8_e4m3fn), "w_down_last")],
    ids=["carried_state_zeroed", "one_decay_a_channel", "no_inner_norms",
         "no_skip", "independent_kv_heads", "float8_operands"])
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
                   if r["name"] == "AI21-Jamba2-3B")
    with open(CONFIG) as f:
        config = json.load(f)
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if config[k] != v)
    assert differs == sorted(config["reduced"]) == [
        "num_hidden_layers", "vocab_size"]
    assert config["published"] == {k: row["config"][k] for k in differs}
    # One whole period of the published pattern and a quarter of the rows.
    assert mamba1_lm.layer_types(config) == PERIOD
    assert config["num_hidden_layers"] == config["attn_layer_period"]
    assert config["vocab_size"] * 4 == row["config"]["vocab_size"]


def test_the_compiled_widths_are_the_published_ones():
    with open(CONFIG) as f:
        config = json.load(f)
    cfg = mamba1_lm.model_config(config, 16384)
    shapes = tfm.init_abstract(cfg)
    mamba, attention = shapes["layers"][0], shapes["layers"][7]
    assert mamba["mamba_w_in"].shape == (2560, 10240)
    assert mamba["mamba_conv"].shape == (4, 5120)
    assert mamba["mamba_w_x"].shape == (5120, 160 + 16 + 16)
    assert mamba["mamba_w_dt"].shape == (160, 5120)
    assert mamba["mamba_a_log"].shape == (5120, 16)
    assert mamba["mamba_w_out"].shape == (5120, 2560)
    assert [mamba[f"mamba_{n}_norm_scale"].shape for n in "dt b c".split()
            ] == [(160,), (16,), (16,)]
    assert attention["wq"].shape == attention["wo"].shape == (2560, 2560)
    assert attention["wk"].shape == attention["wv"].shape == (2560, 128)
    for layer in (mamba, attention):
        assert layer["w_gate"].shape == layer["w_up"].shape == (2560, 8192)
        assert layer["w_down"].shape == (8192, 2560)
    assert (cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (20, 1, 128)
    assert "head" not in shapes and shapes["embed"].shape == (16384, 2560)
    total = sum(leaf.size for leaf in jax.tree_util.tree_leaves(shapes))
    assert total == 1_472_726_976
