"""Each plain reference against the program's model at a tiny size, both
in float32, where they must agree to rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from horovod_tpu.models import get_model
from horovod_tpu.models import transformer as tfm
from perfbench.reference import lm as lm_reference
from perfbench.reference import resnet as resnet_reference


def test_lm_reference_matches_the_programs_model():
    cfg = tfm.TransformerConfig(vocab_size=97, d_model=64, n_heads=2,
                                n_layers=3, d_ff=128, max_seq=512,
                                dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (3, 513), 0, 97)
    tokens, labels = tokens[:, :-1], tokens[:, 1:]
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(tfm.loss_fn)(
            params, tokens, labels, cfg, attention="local")
    got_loss, got = lm_reference.loss_and_tail_grads(
        params, tokens, labels, n_heads=2)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-5)
    np.testing.assert_allclose(got["ln_f_scale"], want["ln_f_scale"],
                               rtol=1e-3, atol=1e-6)
    np.testing.assert_allclose(got["w2_last"], want["layers"][-1]["w2"],
                               rtol=1e-3, atol=1e-6)


def test_resnet_reference_matches_the_programs_model():
    model = get_model("resnet50", num_classes=10, stem="s2d",
                      dtype=jnp.float32)
    images = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 16, 12))
    labels = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, 10)
    variables = model.init(jax.random.PRNGKey(0), images[:1], train=False)
    # Zero-initialised last norms would hide every block's body.
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 if p.ndim == 1 else p, variables["params"])

    def program_loss(p, img, lab):
        logits, _ = model.apply(
            {"params": p, "batch_stats": variables["batch_stats"]}, img,
            train=True, mutable=["batch_stats"])
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, lab).mean()

    with jax.default_matmul_precision("highest"):
        halves = [jax.value_and_grad(program_loss)(
            params, images[i:i + 4], labels[i:i + 4]) for i in (0, 4)]
    want_loss = (halves[0][0] + halves[1][0]) / 2
    want_grad = (halves[0][1]["head"]["kernel"]
                 + halves[1][1]["head"]["kernel"]) / 2
    got_loss, got = resnet_reference.loss_and_head_grad(
        params, images, labels, stage_sizes=(3, 4, 6, 3), stem="s2d",
        replicas=2)
    np.testing.assert_allclose(got_loss, want_loss, rtol=1e-4)
    np.testing.assert_allclose(got["head_kernel"], want_grad, rtol=1e-2,
                               atol=1e-5)
