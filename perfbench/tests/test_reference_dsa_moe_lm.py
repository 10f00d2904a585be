"""The plain Keye-VL-2.0 reference against the program's model at the
rehearsal size of ``configs/keye-vl-2.0-30b-a3b.json``, both in float32,
where they must agree to rounding (the program through its selection, its
sort and its grouped matmuls, the reference by ``lax.top_k`` and one
expert after another), and the catalog row the configuration copies."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import transformer as tfm
from perfbench import run
from perfbench.adapters import dsa_moe_lm, moe_lm
from perfbench.reference import dsa_moe_lm as reference

CONFIG = os.path.join(run.HERE, "configs", "keye-vl-2.0-30b-a3b.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def _setting(seq=256):
    config = run._load(CONFIG, rehearse=True)
    cfg = dsa_moe_lm.model_config(config, seq)
    cfg = tfm.TransformerConfig(**{**cfg.__dict__, "dtype": jnp.float32})
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    params["embed"] = params["embed"] * 50.0
    tokens = moe_lm.zipf_tokens(jax.random.PRNGKey(1), (2, seq + 1),
                                cfg.vocab_size, 1.0)
    return cfg, params, tokens[:, :-1], tokens[:, 1:]


def _reference(cfg, *arrays, names=tuple(reference.LEAVES), **kw):
    """Every leaf the reference can differentiate, not only the cell's."""
    kw.setdefault("index_coef", cfg.indexer_loss_coef)
    return jax.jit(lambda *a: reference.loss_and_tail_grads(
        *a, dims=dsa_moe_lm.reference_dims(cfg), names=names, **kw))(*arrays)


def test_dsa_moe_lm_reference_matches_the_programs_model():
    cfg, params, tokens, labels = _setting()
    assert cfg.head_dim == 32 and cfg.attn_width == 128 == cfg.d_model
    assert cfg.kv_heads == 2 and cfg.index_topk == 64 < 256
    assert cfg.held_experts == 4 and cfg.n_experts == 16
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(tfm.loss_fn)(
            params, tokens, labels, cfg)
    got_loss, got, stats = _reference(cfg, params, tokens, labels)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(
        stats["ce"] + cfg.indexer_loss_coef * stats["index_kl"], got_loss,
        rtol=1e-6)
    paths = reference.leaf_paths(cfg.n_layers)
    assert paths["index_wq_last"] == ("layers", 1, "index_wq")
    assert paths["wo_first"] == ("layers", 0, "wo")
    assert set(got) == set(paths) and len(paths) == 18
    for name, path in paths.items():
        grad = reference.leaf(want, path)
        error = np.linalg.norm(got[name] - grad) / np.linalg.norm(grad)
        assert error <= 5e-5, (name, error)
    assert stats["rows"].shape == (2, 4)
    assert 0 < int(stats["rows"].sum()) < 2 * 512 * 2


def test_the_checked_leaves_need_the_last_layer_only():
    """The cell's four leaves come from a backward pass through the last
    layer: the same numbers as from the whole stack."""
    cfg, params, tokens, labels = _setting()
    _, all_of, _ = _reference(cfg, params, tokens, labels)
    _, four, _ = _reference(cfg, params, tokens, labels,
                            names=reference.CHECKED)
    assert set(four) == set(reference.CHECKED) and len(four) == 4
    for name in reference.CHECKED:
        np.testing.assert_allclose(four[name], all_of[name], rtol=2e-5,
                                   atol=1e-9)


@pytest.mark.parametrize("control", [
    dict(low_precision=jnp.float8_e4m3fn), dict(topk=32),
    dict(select=False), dict(index_coef=0.0)],
    ids=["float8", "half_the_keys", "no_selection", "no_indexer_loss"])
def test_the_controls_are_other_functions(control):
    cfg, params, tokens, labels = _setting()
    want, want_g, _ = _reference(cfg, params, tokens, labels,
                                 names=reference.CHECKED)
    off, off_g, _ = _reference(cfg, params, tokens, labels,
                               names=reference.CHECKED, **control)
    worst = max(float(np.linalg.norm(off_g[n] - want_g[n])
                      / np.linalg.norm(want_g[n]))
                for n in reference.CHECKED)
    assert worst > 0.08 and abs(off - want) > 1e-4 * abs(want)


def test_the_references_selection_is_top_k_with_the_short_rows_whole():
    scores = jax.random.normal(jax.random.PRNGKey(3), (64, 256))
    sel = np.asarray(reference.selection(scores, 32, 48))
    # Rows 32 .. 47 keep all their keys, rows 48 .. 95 their best 48.
    np.testing.assert_array_equal(
        sel.sum(-1), np.minimum(np.arange(32, 96) + 1, 48))
    assert not sel[np.triu_indices(64, 33 + 0, 256)[0],
                   np.triu_indices(64, 33, 256)[1]].any()
    row = np.asarray(scores[40, :73])              # query 72
    assert set(np.flatnonzero(sel[40])) == set(np.argsort(-row)[:48])


def test_the_held_experts_are_those_loaded_nearest_the_mean():
    cfg, params, tokens, labels = _setting()
    perms = jax.jit(lambda *a: reference.level_placement(
        *a, dims=dsa_moe_lm.reference_dims(cfg)))(params, tokens[0])
    assert len(perms) == 2 and all(
        sorted(np.asarray(p)) == list(range(16)) for p in perms)

    def worst(params):
        rows = np.asarray(_reference(cfg, params, tokens[:1], labels[:1],
                                     names=reference.CHECKED)[2]["rows"])
        return np.abs(rows / 32.0 - 1).max()      # 256 tokens x 2 / 16

    placed = dict(params, layers=[
        dict(layer, router=layer["router"][:, perm])
        for layer, perm in zip(params["layers"], perms)])
    assert worst(placed) < worst(params)


def test_the_configuration_is_the_catalog_row():
    """Every published number under its published key; the three reduced
    keys, and nothing else, differ; the nested groups are copied whole."""
    with open(CONFIG) as f:
        config = json.load(f)
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Keye-VL-2.0-30B-A3B")
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert config["published"] == {k: row["config"][k] for k in differs}
    assert config["sa_config"] == row["config"]["sa_config"]
    assert config["rope_scaling"] == row["config"]["rope_scaling"]
    # The router scores the published count under its other published key.
    assert config["num_local_experts"] == row["config"]["num_experts"] == 128
    # The floors: four layers, 8 experts, an eighth of the rows.
    assert config["num_hidden_layers"] >= 4
    assert config["num_experts"] == 16 and config["vocab_size"] == 18992
    assert config["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert (config["experts_held_from"] + config["num_experts"]
            <= config["num_local_experts"])
    for reading in ("layer_form", "qkv", "indexer", "selection", "attention",
                    "indexer_loss", "experts"):
        assert reading in config["assumed"], reading
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == config["name"])
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert entry["source"] == config["source"]
