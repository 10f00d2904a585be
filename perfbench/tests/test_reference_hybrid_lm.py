"""The plain Olmo-Hybrid reference against the program's model at the
rehearsal size of ``configs/olmo-hybrid-7b.json``, both in float32, where
they must agree to rounding (the program through its chunked recurrence,
the reference one token at a time), and the catalog row the
configuration copies."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models import transformer as tfm
from perfbench import run
from perfbench.adapters import hybrid_lm, moe_lm
from perfbench.reference import hybrid_lm as reference

CONFIG = os.path.join(run.HERE, "configs", "olmo-hybrid-7b.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_hybrid_lm_reference_matches_the_programs_model():
    config = run._load(CONFIG, rehearse=True)
    cfg = hybrid_lm.model_config(config, 256)
    assert cfg.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    cfg = tfm.TransformerConfig(**{**cfg.__dict__, "dtype": jnp.float32})
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = moe_lm.zipf_tokens(jax.random.PRNGKey(1), (2, 257),
                                cfg.vocab_size, 1.0)
    tokens, labels = tokens[:, :-1], tokens[:, 1:]
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(tfm.loss_fn)(
            params, tokens, labels, cfg, attention="local")
    got_loss, got, gates = jax.jit(lambda *a: (
        reference.loss_and_tail_grads(
            *a, n_heads=cfg.n_heads, layer_types=cfg.layer_types,
            linear_heads=cfg.linear_value_heads,
            key_dim=cfg.linear_key_head_dim, eps=cfg.norm_eps,
            neg_eigval=cfg.linear_allow_neg_eigval)))(params, tokens, labels)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    for name, grad in (("ln_f_scale", want["ln_f_scale"]),
                       ("w_down_last", want["layers"][3]["w_down"]),
                       ("lin_wo_last", want["layers"][2]["lin_wo"]),
                       ("lin_wa_last", want["layers"][2]["lin_wa"])):
        error = np.linalg.norm(got[name] - grad) / np.linalg.norm(grad)
        assert error < 2e-4, (name, error)
    # alpha in (0, 1], beta in (0, 2): one row a linear layer.
    gates = np.asarray(gates)
    assert gates.shape == (3, 6)
    assert (gates[:, 0] > 0).all() and (gates[:, 4] <= 1).all()
    assert (gates[:, 5] > 1).all() and (gates[:, 5] < 2).all()


def test_configuration_holds_the_catalog_rows_numbers():
    if not os.path.exists(CATALOG):
        import pytest
        pytest.skip("no catalog beside the model-configs guide here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Olmo-Hybrid-7B")
    with open(CONFIG) as f:
        config = json.load(f)
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if config[k] != v)
    assert differs == sorted(config["reduced"]) == [
        "num_hidden_layers", "vocab_size"]
    # One whole period of the published pattern.
    assert hybrid_lm.layer_types(config) == tuple(
        row["config"]["layer_types"][:4])
    assert config["vocab_size"] * 4 == row["config"]["vocab_size"]


def test_adapter_draws_gates_inside_the_configurations_ranges():
    with open(CONFIG) as f:
        gate_init = json.load(f)["gate_init"]
    a_log, dt_bias = hybrid_lm.draw_gates(jax.random.PRNGKey(2), 4096,
                                          gate_init)
    a, dt = np.exp(a_log), np.log1p(np.exp(np.asarray(dt_bias)))
    lo, hi = gate_init["a_range"]
    assert lo <= a.min() < lo * 1.05 and hi * 0.95 < a.max() <= hi
    lo, hi = gate_init["dt_range"]
    assert lo * 0.999 <= dt.min() < lo * 1.1
    assert hi * 0.9 < dt.max() <= hi * 1.001
