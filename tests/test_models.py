"""Model zoo + SPMD training-step tests (CPU-simulated 8-chip mesh)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest


def test_resnet18_forward_shapes(hvd):
    from horovod_tpu.models import ResNet18

    model = ResNet18(num_classes=10)
    x = jnp.zeros((2, 32, 32, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 10)
    assert out.dtype == jnp.float32


@pytest.mark.slow
@pytest.mark.parametrize("name,size", [("vgg16", 32), ("inception3", 96)])
def test_headline_model_forward(hvd, name, size):
    """VGG-16 and Inception V3 — the reference's other two headline scaling
    models (README.rst:75) — forward with BN state at reduced resolution."""
    from horovod_tpu.models import get_model

    model = get_model(name, num_classes=10)
    x = jnp.zeros((2, size, size, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 10)
    assert out.dtype == jnp.float32
    assert "batch_stats" in variables

    # train=True mutates batch_stats (the harness contract).
    out, mutated = model.apply(variables, x, train=True,
                               mutable=["batch_stats"])
    assert "batch_stats" in mutated


@pytest.mark.slow
def test_headline_models_train_step(hvd, mesh8):
    """The synthetic benchmark harness must drive the new families end-to-end
    (registry -> make_train_step -> finite loss)."""
    from horovod_tpu.benchmark import run_synthetic_benchmark

    for name, size in (("vgg11", 32), ("inception3", 96)):
        res = run_synthetic_benchmark(
            name, batch_size=1, image_size=size, num_classes=4,
            num_warmup_batches=0, num_batches_per_iter=1, num_iters=1,
            verbose=False)
        assert np.isfinite(res["loss"])
        assert res["img_sec_per_chip"] > 0


def test_lm_benchmark_plumbing(hvd):
    """run_lm_benchmark (the bench.py 'lm' key) end-to-end on a tiny
    config: finite loss, throughput, and the analytic FLOP accounting
    present (MFU itself is None on CPU — no known peak)."""
    from horovod_tpu.benchmark import lm_train_flops, run_lm_benchmark

    res = run_lm_benchmark(
        d_model=32, n_layers=2, n_heads=2, vocab_size=64, seq_len=64,
        batch_size=2, attention="local", remat="dots",
        num_warmup_batches=1, num_batches_per_iter=2, num_iters=2,
        verbose=False)
    assert np.isfinite(res["loss"])
    assert res["tok_sec_per_chip"] > 0
    assert res["flops_per_step_analytic"] > 0
    # every result names the devices it ran on: by default, all of them
    assert res["device"] == {"platform": "cpu", "kind": "cpu", "count": 8}
    assert res["mfu"] is None
    # the analytic count matches the hand formula
    from horovod_tpu.models.transformer import TransformerConfig
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                            n_layers=2, d_ff=128, max_seq=64)
    n_matmul = 2 * (4 * 32 * 32 + 2 * 32 * 128) + 32 * 64
    want = 6.0 * n_matmul * 2 * 64 + 6.0 * 2 * 64 * 64 * 32 * 2
    assert lm_train_flops(cfg, 2) == want


def test_decode_benchmark_plumbing_and_bf16(hvd):
    """run_decode_benchmark end-to-end on a tiny config, plus the bf16
    regression: decode_step must accept a bf16 cfg (the rmsnorm f32
    scale used to promote k/v past the cache dtype — r4 fix)."""
    import jax.numpy as jnp

    from horovod_tpu.benchmark import run_decode_benchmark
    from horovod_tpu.models import transformer as tfm

    res = run_decode_benchmark(d_model=32, n_layers=2, n_heads=2,
                               vocab_size=64, batch_size=2,
                               prompt_len=4, total_len=16,
                               num_iters=1, verbose=False)
    assert res["decode_tok_sec"] > 0

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_seq=16,
                                dtype=jnp.bfloat16)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    out = tfm.generate(params, jnp.zeros((1, 2), jnp.int32), 8, cfg)
    assert out.shape == (1, 8)


def test_registry(hvd):
    from horovod_tpu.models import get_model, list_models

    assert "resnet50" in list_models()
    m = get_model("resnet50", num_classes=7)
    assert m.num_classes == 7
    with pytest.raises(ValueError, match="unknown model"):
        get_model("nope")


@pytest.mark.slow
def test_train_step_runs_and_learns(hvd, mesh8):
    """One full distributed step must run and reduce loss over a few steps."""
    from horovod_tpu.benchmark import make_train_step
    from horovod_tpu.models import ResNet18
    from jax.sharding import NamedSharding, PartitionSpec as P

    model = ResNet18(num_classes=4)
    variables = model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, 32, 32, 3)), train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt = optax.sgd(0.05, momentum=0.9)
    opt_state = opt.init(params)

    rng = np.random.default_rng(0)
    images = jax.device_put(
        rng.standard_normal((16, 32, 32, 3), dtype=np.float32),
        NamedSharding(mesh8, P("data")))
    labels = jax.device_put(rng.integers(0, 4, (16,), dtype=np.int32),
                            NamedSharding(mesh8, P("data")))
    repl = NamedSharding(mesh8, P())
    params, batch_stats, opt_state = jax.device_put(
        (params, batch_stats, opt_state), repl)

    step = make_train_step(model, opt, mesh8)
    losses = []
    for _ in range(4):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, images, labels)
        losses.append(float(np.asarray(loss)))
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0]


@pytest.mark.slow
def test_benchmark_reports_flops_and_efficiency(hvd):
    """run_synthetic_benchmark must report FLOPs (XLA cost analysis) and
    run_scaling_efficiency must compute the 1-vs-N ratio — the metric
    BASELINE.md anchors on (reference README.rst:75)."""
    from horovod_tpu.benchmark import (run_scaling_efficiency,
                                       run_synthetic_benchmark)

    res = run_synthetic_benchmark(
        "resnet18", batch_size=2, image_size=32, num_warmup_batches=1,
        num_batches_per_iter=2, num_iters=2, verbose=False)
    assert res["img_sec_per_chip"] > 0
    assert res["flops_per_step"] and res["flops_per_step"] > 1e8
    assert res["tflops_per_chip"] and res["tflops_per_chip"] > 0
    assert res["mfu"] is None  # CPU mesh: no peak -> no MFU claim

    eff = run_scaling_efficiency(
        "resnet18", batch_size=2, image_size=32, n_devices=8,
        num_warmup_batches=1, num_batches_per_iter=2, num_iters=2,
        verbose=False)
    assert eff["n_devices"] == 8
    assert 0 < eff["scaling_efficiency"] <= 1.5  # plumbing, not perf, on CPU


def test_graft_entry_single_chip(hvd):
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (8, 100)


def test_graft_entry_multichip():
    import __graft_entry__ as ge

    ge.dryrun_multichip(8)


def test_transformer_decode_matches_forward(hvd):
    """KV-cache decode_step reproduces the training forward's logits
    position by position (greedy-decode correctness oracle)."""
    from horovod_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=4,
                                d_ff=64, n_layers=2, max_seq=16,
                                dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 64, (2, 10)), jnp.int32)

    oracle = tfm.forward(params, tokens, cfg, attention="local")

    cache = tfm.init_kv_cache(cfg, 2, 10)
    outs = []
    for pos in range(10):
        logits, cache = tfm.decode_step(params, tokens[:, pos], cache,
                                        pos, cfg)
        outs.append(logits)
    dec = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec), np.asarray(oracle),
                               rtol=2e-4, atol=2e-4)


def test_transformer_generate(hvd):
    """generate() teacher-forces the prompt and continues greedily; the
    continuation equals step-by-step argmax decode."""
    from horovod_tpu.models import transformer as tfm

    cfg = tfm.TransformerConfig(vocab_size=32, d_model=16, n_heads=2,
                                d_ff=32, n_layers=1, max_seq=12,
                                dtype=jnp.float32)
    params = tfm.init_params(jax.random.PRNGKey(1), cfg)
    prompt = jnp.asarray([[3, 7, 1]], jnp.int32)
    out = jax.jit(lambda p, t: tfm.generate(p, t, 8, cfg))(params, prompt)
    assert out.shape == (1, 8)
    assert (np.asarray(out[:, :3]) == np.asarray(prompt)).all()

    # Manual argmax continuation oracle.
    cache = tfm.init_kv_cache(cfg, 1, 8)
    tok = prompt[:, 0]
    seq = [int(prompt[0, 0])]
    for pos in range(7):
        logits, cache = tfm.decode_step(params, tok, cache, pos, cfg)
        nxt = int(jnp.argmax(logits, -1)[0])
        tok = (prompt[:, pos + 1] if pos + 1 < 3
               else jnp.asarray([nxt], jnp.int32))
        seq.append(int(tok[0]))
    assert seq == [int(v) for v in np.asarray(out[0])], (seq, out)


def test_s2d_stem_exact_equivalence(hvd):
    """The space-to-depth stem computes the SAME function as the 7x7/s2
    stem under the conv7_to_s2d_weights reparameterization: conv(s2d(x),
    w4) == conv(x, w7) for the stem conv alone, and the full packed model
    equals the canonical model when stem weights are mapped and all other
    weights are shared."""
    from flax.core import unfreeze
    from horovod_tpu.models import ResNet18
    from horovod_tpu.models.resnet import conv7_to_s2d_weights, space_to_depth

    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 64, 64, 3), dtype=np.float32)

    m7 = ResNet18(num_classes=7, dtype=jnp.float32)
    m4 = ResNet18(num_classes=7, dtype=jnp.float32, stem="s2d")
    v7 = m7.init(jax.random.PRNGKey(0), jnp.asarray(x), train=False)
    xp = jnp.asarray(space_to_depth(x))

    v4 = unfreeze(jax.tree.map(lambda a: a, v7))
    w7 = np.asarray(v7["params"]["conv_init"]["kernel"])
    v4["params"]["conv_init"] = {
        "kernel": jnp.asarray(conv7_to_s2d_weights(w7))}

    y7 = m7.apply(v7, jnp.asarray(x), train=False)
    y4 = m4.apply(v4, xp, train=False)
    np.testing.assert_allclose(np.asarray(y4), np.asarray(y7),
                               rtol=1e-5, atol=1e-5)
