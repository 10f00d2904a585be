"""The plain OLMoE reference against the program's model at the rehearsal
size of ``configs/olmoe-1b-7b-0125.json``, both in float32, where they
must agree to rounding (the program through its own sort, gathers and
grouped-matmul kernels in the interpreter; the reference through a loop
over the experts)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from horovod_tpu.models import transformer as tfm
from perfbench import run
from perfbench.adapters import moe_lm
from perfbench.reference import moe_lm as reference


def _rehearsal_config():
    path = os.path.join(run.HERE, "configs", "olmoe-1b-7b-0125.json")
    return run._load(path, rehearse=True)


def test_moe_lm_reference_matches_the_programs_model():
    config = _rehearsal_config()
    cfg = moe_lm.model_config(config, 256)
    cfg = tfm.TransformerConfig(**{**cfg.__dict__, "dtype": jnp.float32})
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = moe_lm.zipf_tokens(jax.random.PRNGKey(1), (2, 257),
                                cfg.vocab_size, 1.0)
    tokens, labels = tokens[:, :-1], tokens[:, 1:]
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(tfm.loss_fn)(
            params, tokens, labels, cfg, attention="local")
    got_loss, got, assignments = jax.jit(lambda *a: (
        reference.loss_and_tail_grads(
            *a, n_heads=cfg.n_heads, top_k=cfg.experts_per_token,
            eps=cfg.norm_eps, theta=cfg.rope_theta,
            aux_coef=cfg.router_aux_coef, z_coef=cfg.router_z_coef)))(
        params, tokens, labels)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    last = want["layers"][-1]
    for name, grad in (("ln_f_scale", want["ln_f_scale"]),
                       ("w_down_last", last["w_down"]),
                       ("router_last", last["router"])):
        error = (np.linalg.norm(got[name] - grad) / np.linalg.norm(grad))
        assert error < 1e-4, (name, error)
    # Nothing dropped: every layer holds tokens x k assignments.
    np.testing.assert_array_equal(
        np.asarray(assignments).sum(1),
        tokens.size * cfg.experts_per_token)
    # The aux losses are in the total: without them the loss is lower.
    plain = tfm.TransformerConfig(**{**cfg.__dict__, "router_aux_coef": 0.0,
                                     "router_z_coef": 0.0})
    assert want_loss - tfm.loss_fn(params, tokens, labels, plain,
                                   attention="local") > 0.01


def test_zipf_tokens_follow_rank_to_the_minus_exponent():
    tokens = np.asarray(moe_lm.zipf_tokens(jax.random.PRNGKey(3),
                                           (400_000,), 1000, 1.0))
    assert tokens.min() >= 0 and tokens.max() < 1000
    counts = np.sort(np.bincount(tokens, minlength=1000))[::-1]
    harmonic = (1.0 / np.arange(1, 1001)).sum()
    # The three commonest tokens: 1/H, 1/2H, 1/3H of the draws.
    np.testing.assert_allclose(counts[:3] / tokens.size,
                               [1 / harmonic, 1 / 2 / harmonic,
                                1 / 3 / harmonic], rtol=0.03)
    # Which token is commonest is the seed's: a permutation, not rank 0.
    other = np.asarray(moe_lm.zipf_tokens(jax.random.PRNGKey(4),
                                          (10_000,), 1000, 1.0))
    assert np.bincount(tokens).argmax() != np.bincount(other).argmax()
