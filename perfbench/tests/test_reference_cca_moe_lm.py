"""The plain ``cca_moe_lm`` reference against the program's model at the
rehearsal size of ``configs/zaya1-8b.json``, both in float32, where they
must agree to rounding; its controls; the placement it gives the adapter;
and the catalog row the configuration copies."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import transformer as tfm
from perfbench import run
from perfbench.adapters import cca_moe_lm, moe_lm
from perfbench.controls_cca_moe_lm import CONTROLS
from perfbench.reference import cca_moe_lm as reference

CONFIG = os.path.join(run.HERE, "configs", "zaya1-8b.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def setting(seq=256):
    config = run._load(CONFIG, rehearse=True)
    cfg = cca_moe_lm.model_config(config, seq)
    cfg = tfm.TransformerConfig(**{**cfg.__dict__, "dtype": jnp.float32})
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    params["embed"] = params["embed"] * 50.0
    tokens = moe_lm.zipf_tokens(jax.random.PRNGKey(1), (2, seq + 1),
                                cfg.vocab_size, 1.0)
    placed = reference.level_placement(
        params, tokens[0, :-1], dims=cca_moe_lm.reference_dims(cfg))
    params["layers"] = [reference.place(layer, found) for layer, found
                        in zip(params["layers"], placed)]
    return cfg, params, tokens[:, :-1], tokens[:, 1:]


def _reference(cfg, params, *batch, **kw):
    """Every trained leaf by default; the cell's with ``names``."""
    if "names" not in kw:
        kw.setdefault("paths", reference.trained_leaves(params))
        kw["names"] = tuple(kw["paths"])
    return jax.jit(lambda *a: reference.loss_and_grads(
        *a, dims=cca_moe_lm.reference_dims(cfg), **kw))(
            cca_moe_lm.for_reference(params, cfg), *batch)


@pytest.fixture(scope="module")
def plain(setting):
    return _reference(*setting)


def test_cca_moe_lm_reference_matches_the_programs_model(setting, plain):
    cfg, params, tokens, labels = setting
    assert (cfg.n_layers, cfg.cca_taps, cfg.router_width,
            cfg.residual_scaling) == (3, (2, 2), 32, True)
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(tfm.loss_fn)(
            params, tokens, labels, cfg, attention="local")
    got_loss, got, stats = plain
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    paths = reference.trained_leaves(params)
    # 33 leaves a layer but the selection bias, and the first gamma.
    assert len(paths) == 2 + 3 * 32 - 1
    for name, path in paths.items():
        grad = reference.leaf(want, path)
        if name == "ln_f_scale":
            # The reference's leaf is the program's times the constant.
            grad = grad / cfg.logit_scale
        assert float(np.linalg.norm(grad)) > 0, name
        error = np.linalg.norm(got[name] - grad) / np.linalg.norm(grad)
        assert error <= 5e-5, (name, error)
    for layer in want["layers"]:
        assert float(jnp.abs(layer["router_bias"]).max()) == 0.0
    # The placement gives the held range a uniform router's rows, 4 / 9 of
    # the tokens, as near as a subset of four loads comes.
    rows, skips = np.asarray(stats["rows"]), np.asarray(stats["skips"])
    assert rows.shape == (3, 4) and skips.shape == (3,)
    assert (abs(rows.sum(1) - 512 * 4 / 9) < 2 * 512 / 9).all(), rows
    # A layer makes up what the layers below it fell short by.
    assert abs(rows.sum() - 3 * 512 * 4 / 9) < 512 / 9, rows


def test_the_cells_leaves_are_leaves_of_every_trained_one(setting, plain):
    cfg, params, tokens, labels = setting
    _, eight, _ = _reference(cfg, params, tokens, labels,
                             names=reference.CHECKED)
    assert set(eight) == set(reference.CHECKED)
    assert len(reference.CHECKED) == 8
    _, every, _ = plain
    paths = reference.leaf_paths(cfg.n_layers)
    for name, grad in eight.items():
        np.testing.assert_allclose(
            grad, every[".".join(map(str, paths[name]))], rtol=1e-4,
            atol=1e-9)
    assert eight["w_down_last"].shape == params["layers"][-1][
        "w_down"].shape


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_every_control_is_another_function(setting, plain, control):
    """Each moves the loss or the gradient of a checked leaf; the
    gradient cut between the routers moves no loss."""
    cfg, params, tokens, labels = setting
    kw = {key: getattr(jnp, value) if key.endswith("low_precision")
          else value for key, value in CONTROLS[control].items()}
    want_loss, _, _ = plain
    _, want, _ = _reference(cfg, params, tokens, labels,
                            names=reference.CHECKED)
    loss, grads, _ = _reference(cfg, params, tokens, labels,
                                names=reference.CHECKED, **kw)
    moved = max(float(np.linalg.norm(grads[n] - want[n])
                      / np.linalg.norm(want[n])) for n in want)
    assert moved > 0.02, (control, moved)
    if control == "cut_state":
        np.testing.assert_allclose(loss, want_loss, rtol=1e-6)


def test_the_reference_is_handed_the_heads_constant_in_the_final_norm(
        setting, plain):
    """``for_reference``: the program multiplies its logits by the
    configuration's ``logit_scale``, the reference writes ``RMSNorm_f(x)
    E^T`` and is handed the final norm's scale times the constant: one
    function.  Without the folding the two losses part."""
    cfg, params, tokens, labels = setting
    assert cfg.logit_scale == 2.0 ** -15
    folded = cca_moe_lm.for_reference(params, cfg)
    np.testing.assert_array_equal(folded["ln_f_scale"],
                                  params["ln_f_scale"] * cfg.logit_scale)
    assert all(folded[k] is params[k] for k in params if k != "ln_f_scale")
    unfolded, _, _ = jax.jit(lambda *a: reference.loss_and_grads(
        *a, dims=cca_moe_lm.reference_dims(cfg),
        names=("k_temp_last",)))(params, tokens, labels)
    assert abs(float(unfolded) - float(plain[0])) > 0.1 * float(plain[0])


def test_an_unknown_control_is_refused(setting):
    with pytest.raises(TypeError, match="unknown controls"):
        _reference(*setting, no_such_control=True)


def test_configuration_copies_the_catalog_row():
    """Every number of the catalog's ``config`` under the same key, but
    for what ``reduced`` lists; nested groups whole."""
    with open(CONFIG) as f:
        config = json.load(f)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "ZAYA1-8B")
    assert config["source"] == row["source_url"]
    assert sorted(config["reduced"]) == ["num_experts", "num_hidden_layers",
                                         "vocab_size"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config["published"][key] == value, key
        else:
            assert config[key] == value, key
    assert config["num_hidden_layers"] >= 4
    assert config["num_experts"] >= 8
    assert config["vocab_size"] * 8 >= config["published"]["vocab_size"]
