"""The plain looped reference against the program's model at the rehearsal
size of ``configs/ouro-2.6b.json``, both in float32, where they must agree
to rounding; its second statement of the model (an untied stack of four
copies); its controls; and the catalog row the configuration copies."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import transformer as tfm
from perfbench import run
from perfbench.adapters import looped_lm, moe_lm
from perfbench.reference import looped_lm as reference

CONFIG = os.path.join(run.HERE, "configs", "ouro-2.6b.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.fixture(scope="module")
def setting(seq=256):
    config = run._load(CONFIG, rehearse=True)
    cfg = looped_lm.model_config(config, seq)
    cfg = tfm.TransformerConfig(**{**cfg.__dict__, "dtype": jnp.float32})
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = moe_lm.zipf_tokens(jax.random.PRNGKey(1), (2, seq + 1),
                                cfg.vocab_size, 1.0)
    return cfg, params, tokens[:, :-1], tokens[:, 1:]


def _reference(cfg, params, *batch, **kw):
    """Every leaf by default; the cell's five with ``names``."""
    if "names" not in kw:
        kw.setdefault("paths", reference.every_leaf(params))
        kw["names"] = tuple(kw["paths"])
    return jax.jit(lambda *a: reference.loss_and_grads(
        *a, dims=looped_lm.reference_dims(cfg), **kw))(params, *batch)


@pytest.fixture(scope="module")
def plain(setting):
    return _reference(*setting)


def test_looped_lm_reference_matches_the_programs_model(setting, plain):
    cfg, params, tokens, labels = setting
    assert (cfg.loops, cfg.n_layers, cfg.post_norm) == (4, 3, True)
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(tfm.loss_fn)(
            params, tokens, labels, cfg, attention="local")
    got_loss, got, stats = plain
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    paths = reference.every_leaf(params)
    assert len(paths) == 5 + 3 * 11
    for name, path in paths.items():
        grad = reference.leaf(want, path)
        error = np.linalg.norm(got[name] - grad) / np.linalg.norm(grad)
        assert error < 5e-5, (name, error)
    # The cell's leaves are the default, and the same numbers.
    _, cells, _ = _reference(cfg, params, tokens, labels,
                             names=reference.CHECKED)
    checked = reference.leaf_paths(cfg.n_layers)
    assert checked["wk_first"] == ("layers", 0, "wk")
    assert checked["w_down_last"] == ("layers", 2, "w_down")
    for name, grad in cells.items():
        twin = got[".".join(map(str, checked[name]))]
        assert np.linalg.norm(grad - twin) <= 1e-5 * np.linalg.norm(twin)
    # The gate's pre-activation has a standard deviation near 1 and a
    # mean of its own a seed (the normed states share a direction), so the
    # passes' shares wander around (1/2, 1/4, 1/8, 1/8); every pass has
    # weight.
    p = np.asarray(stats["p_mean"])
    assert abs(p.sum() - 1.0) < 1e-6 and p.min() > 0.01
    assert (np.asarray(stats["l_mean"]) > np.log(512) - 1.0).all()


def test_the_tied_stack_is_four_untied_copies(setting, plain):
    cfg, params, tokens, labels = setting
    untied = dict(params, layers=params["layers"] * cfg.loops)
    paths = {f"{t}.{i}.{name}": ("layers", t * cfg.n_layers + i, name)
             for t in range(cfg.loops) for i in range(cfg.n_layers)
             for name in params["layers"][i]}
    loss, copies, _ = _reference(cfg, untied, tokens, labels, paths=paths,
                                 untied=True)
    np.testing.assert_allclose(loss, plain[0], rtol=1e-6)
    for i in range(cfg.n_layers):
        for name in params["layers"][i]:
            summed = sum(copies[f"{t}.{i}.{name}"]
                         for t in range(cfg.loops))
            tied = plain[1][f"layers.{i}.{name}"]
            assert (np.linalg.norm(summed - tied)
                    <= 2e-5 * np.linalg.norm(tied)), (i, name)


@pytest.mark.parametrize("variant,leaf,loss_moves", [
    (dict(loops=3), "layers.0.wk", True),
    (dict(cut_passes=True), "layers.0.wk", False),
    (dict(norm_carried=False), "layers.0.wk", True),
    (dict(post_norms=False), "layers.2.ln2_post_scale", True),
    (dict(uniform_exit=True), "exit_gate_w", True),
    (dict(entropy=False), "exit_gate_w", True),
    (dict(last_takes_rest=False), "exit_gate_w", True),
    (dict(last_pass_only=True), "layers.0.wk", True),
    (dict(low_precision=jnp.float8_e4m3fn), "layers.2.w_down", True)],
    ids=["three_passes", "cut_passes", "norm_at_readouts", "no_post_norms",
         "uniform_exit", "no_entropy", "last_unnormalised",
         "last_pass_only", "float8_operands"])
def test_each_control_is_another_function(setting, plain, variant, leaf,
                                          loss_moves):
    loss, grads, _ = _reference(*setting, **variant)
    want_loss, want, _ = plain
    moved = abs(float(loss - want_loss)) > 1e-4 * abs(float(want_loss))
    # (A cut between the passes leaves the loss alone.)
    assert moved == loss_moves
    assert (np.linalg.norm(grads[leaf] - want[leaf])
            > 0.02 * np.linalg.norm(want[leaf]))


def test_configuration_copies_the_catalog_row():
    with open(CONFIG) as f:
        config = json.load(f)
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f]
    row = next(r for r in rows if r["name"] == "Ouro-2.6B")
    assert config["source"] == row["source_url"]
    changed = {k for k, v in row["config"].items() if config.get(k) != v}
    assert changed == {"num_hidden_layers"}
    assert config["published"] == {"num_hidden_layers": 48}
    assert 4 <= config["num_hidden_layers"] < 48
    bench = json.load(open(os.path.join(run.ROOT, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"] if c["name"] == "ouro-2.6b")
    assert entry["reduced"] == ["num_hidden_layers"] == list(
        config["reduced"])
