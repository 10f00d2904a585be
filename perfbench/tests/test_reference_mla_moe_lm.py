"""The plain GLM-4.7-Flash reference against the program's model at the
rehearsal size of ``configs/glm-4.7-flash.json``, both in float32, where
they must agree to rounding (the program through its sort, its grouped
matmuls and its kernels' layout, the reference one expert after another),
and the catalog row the configuration copies."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import transformer as tfm
from perfbench import run
from perfbench.adapters import mla_moe_lm, moe_lm
from perfbench.reference import mla_moe_lm as reference

CONFIG = os.path.join(run.HERE, "configs", "glm-4.7-flash.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _setting(seq=256):
    config = run._load(CONFIG, rehearse=True)
    cfg = mla_moe_lm.model_config(config, seq)
    cfg = tfm.TransformerConfig(**{**cfg.__dict__, "dtype": jnp.float32})
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    params["embed"] = params["embed"] * 50.0
    tokens = moe_lm.zipf_tokens(jax.random.PRNGKey(1), (2, seq + 1),
                                cfg.vocab_size, 1.0)
    return cfg, params, tokens[:, :-1], tokens[:, 1:]


def _reference(cfg, *arrays, names=tuple(reference.LEAVES), **kw):
    """Every leaf the reference can differentiate, not only the cell's."""
    return jax.jit(lambda *a: reference.loss_and_tail_grads(
        *a, dims=mla_moe_lm.reference_dims(cfg),
        dense_layers=cfg.dense_layers, mtp_coef=cfg.mtp_loss_coef,
        names=names, **kw))(*arrays)


def test_mla_moe_lm_reference_matches_the_programs_model():
    cfg, params, tokens, labels = _setting()
    assert cfg.head_dim == 64 and cfg.n_heads * cfg.head_dim != cfg.d_model
    assert cfg.dense_layers == 1 and cfg.n_layers == 3
    assert cfg.mtp_layer_types == ("full_attention",)
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(tfm.loss_fn)(
            params, tokens, labels, cfg, attention="local")
    got_loss, got, stats = _reference(cfg, params, tokens, labels)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    paths = reference.leaf_paths(cfg.n_layers)
    assert paths["w_kvb_last"] == ("layers", 2, "w_kvb")
    assert paths["w_down_dense"] == ("layers", 0, "w_down")
    assert set(got) == set(paths) and len(paths) == 12
    for name, path in paths.items():
        grad = reference.leaf(want, path)
        error = np.linalg.norm(got[name] - grad) / np.linalg.norm(grad)
        assert error <= 5e-5, (name, error)
    # Two expert layers and the module's, four held experts each.
    assert stats["rows"].shape == (3, 4)
    assert 0 < int(stats["rows"].sum()) < 3 * 512 * 2


def test_the_checked_leaves_need_the_last_layer_only():
    """The cell's five leaves come from a backward pass through the last
    layer and the module: the same numbers as from the whole stack."""
    cfg, params, tokens, labels = _setting()
    _, all_of, _ = _reference(cfg, params, tokens, labels)
    _, five, _ = _reference(cfg, params, tokens, labels,
                            names=reference.CHECKED)
    assert set(five) == set(reference.CHECKED)
    for name in reference.CHECKED:
        np.testing.assert_allclose(five[name], all_of[name], rtol=2e-5,
                                   atol=1e-9)


@pytest.mark.parametrize("control", [
    dict(shared_expert=False), dict(rotate_shared_key=False),
    dict(low_precision=jnp.float8_e4m3fn)],
    ids=["no_shared_expert", "k_r_unrotated", "float8"])
def test_the_controls_are_other_functions(control):
    cfg, params, tokens, labels = _setting()
    want, want_g, _ = _reference(cfg, params, tokens, labels,
                                 names=reference.CHECKED)
    off, off_g, _ = _reference(cfg, params, tokens, labels,
                               names=reference.CHECKED, **control)
    worst = max(float(np.linalg.norm(off_g[n] - want_g[n])
                      / np.linalg.norm(want_g[n]))
                for n in reference.CHECKED if np.linalg.norm(want_g[n]))
    assert worst > 0.02 or abs(off - want) > 1e-4 * abs(want)


def test_the_held_experts_are_those_loaded_nearest_the_mean():
    """``_nearest_the_mean`` on hand-made loads, and ``level_placement``
    through the stack: a permutation of the router's columns after which
    every held expert receives about the mean."""
    loads = jnp.asarray([5, 900, 140, 7, 300, 112, 60, 3, 200, 9, 80, 1,
                         100, 130, 20, 93], jnp.float32)     # mean 135
    perm = np.asarray(reference._nearest_the_mean(loads, 4, 8))
    assert sorted(perm) == list(range(16))
    # Nearest first; of two as near, the lower index.
    np.testing.assert_array_equal(np.asarray(loads)[perm[8:12]],
                                  [140, 130, 112, 100])
    # The others fill the places around them, nearest first too.
    np.testing.assert_array_equal(np.asarray(loads)[perm[:3]], [93, 80, 200])
    assert np.asarray(loads)[perm[-1]] == 900
    cfg, params, tokens, labels = _setting()
    stack, module = jax.jit(lambda *a: reference.level_placement(
        *a, dims=mla_moe_lm.reference_dims(cfg),
        dense_layers=cfg.dense_layers))(params, tokens[0], labels[0])
    assert all(sorted(np.asarray(p)) == list(range(16))
               for p in stack + module) and len(stack + module) == 3

    def worst(params):
        rows = np.asarray(_reference(cfg, params, tokens[:1], labels[:1],
                                     names=reference.CHECKED)[2]["rows"])
        return np.abs(rows / 32.0 - 1).max()      # 256 tokens x 2 / 16

    def with_routers(layers, perms):
        return [dict(layer, router=layer["router"][:, perm])
                for layer, perm in zip(layers, perms)]

    placed = dict(params, layers=params["layers"][:1] + with_routers(
        params["layers"][1:], stack), mtp=dict(
            params["mtp"], layers=with_routers(params["mtp"]["layers"],
                                               module)))
    # 4 of 16 experts and 32 rows each: 0.41 against 1.19 as drawn.
    assert worst(placed) < 0.5 < 1.0 < worst(params)


def test_the_configuration_is_the_catalog_row():
    """Every published number under its published key; the three reduced
    keys, and nothing else, differ."""
    with open(CONFIG) as f:
        config = json.load(f)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "GLM-4.7-Flash")
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert config["published"] == {k: row["config"][k] for k in differs}
    assert config["router_width"] == row["config"]["n_routed_experts"]
    # The floors: four expert layers, 8 experts, an eighth of the rows.
    assert config["num_hidden_layers"] - config["first_k_dense_replace"] >= 4
    assert config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= row["config"]["vocab_size"]
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == config["name"])
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
