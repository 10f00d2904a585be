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
    """The state recipe and the flax step must drive the other families
    end-to-end (registry -> make_bench_state -> make_train_step -> finite
    loss)."""
    from horovod_tpu.benchmark import make_bench_state, make_train_step

    for name, size in (("vgg11", 32), ("inception3", 96)):
        mesh, ax, model, optimizer, _, state, batch = make_bench_state(
            name, batch_size=1, image_size=size, num_classes=4, mesh=mesh8)
        step = make_train_step(model, optimizer, mesh, ax)
        *state, loss = step(*state, *batch)
        assert np.isfinite(float(loss))
        assert batch[0].shape[0] == 8            # batch_size is per chip


@pytest.mark.parametrize("shard_optimizer", [False, True])
def test_lm_bench_state_is_placed_for_its_step(hvd, shard_optimizer):
    """make_lm_bench_state — what chip_smoke.py's lm_dp and lm_zero phases
    stand on: the state goes where the step's specs say (parameters whole
    on every device; the optimizer state whole too, or 1/N of it per
    device under ZeRO), the batch is batch_size per chip sharded over the
    mesh, and the step it returns takes that state and batch as they
    are."""
    from jax.sharding import PartitionSpec as P

    from horovod_tpu.benchmark import make_lm_bench_state

    mesh, cfg, step, state, batch = make_lm_bench_state(
        d_model=32, n_layers=2, n_heads=2, d_ff=128, vocab_size=64,
        seq_len=64, batch_size=2, attention="local", remat="dots",
        shard_optimizer=shard_optimizer)
    n = mesh.devices.size
    assert n == 8 and mesh.axis_names == ("data",)
    assert cfg.max_seq == 64 and cfg.dtype == jnp.bfloat16
    params, opt_state = state
    for leaf in jax.tree_util.tree_leaves(params):
        assert leaf.dtype == jnp.float32          # f32 master weights
        assert leaf.sharding.is_fully_replicated
    for arr in batch:
        assert arr.shape == (2 * n, 64) and arr.sharding.spec == P("data")
    slots = jax.tree_util.tree_leaves(opt_state)
    assert {leaf.dtype for leaf in slots} == {jnp.dtype(jnp.bfloat16)}
    per_device = sum(leaf.addressable_shards[0].data.nbytes
                     for leaf in slots)
    whole = sum(leaf.nbytes for leaf in slots)
    if shard_optimizer:
        # 1/N of what a replica holds whole; bucket padding to a multiple
        # of N is all that may be added.
        assert 1 / n <= per_device / whole < 1.1 / n
    else:
        assert per_device == whole
    *state, loss = step(*state, *batch)
    assert np.isfinite(float(loss))
    for new, old in zip(jax.tree_util.tree_leaves(state),
                        jax.tree_util.tree_leaves((params, opt_state))):
        assert new.sharding.is_equivalent_to(old.sharding, old.ndim)


def test_generate_accepts_bf16_config(hvd):
    """decode_step must accept a bf16 cfg (the rmsnorm f32 scale used to
    promote k/v past the cache dtype — r4 fix)."""
    from horovod_tpu.models import transformer as tfm

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
