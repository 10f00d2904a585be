"""A stack run several times on the same weights (``TransformerConfig.loops``,
``post_norm``), at tiny sizes on the virtual CPU mesh.

Oracles: the benchmark's plain float32 reference
(``perfbench/reference/looped_lm.py``), which shares no code with the
program, writes the loop as a Python loop and the exit distribution as
plain products; that reference's second statement of the model, a stack of
``loops x N`` **untied** layers whose quarters are copies of the ``N``; and
the program's own single-device step for the mesh axes beyond the data
axis.  Float32 everywhere: 5e-5 relative L2 through the stack.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding

from horovod_tpu.models import transformer as tfm
from perfbench.reference import looped_lm as reference

# The families every configuration shares, and the table of configurations
# (tests/test_lm_configs.py); those that compile this row's program run
# here, in the row's own file: a file is one worker's chain.
from test_lm_configs import *  # noqa: E402,F401,F403
from test_lm_configs import (GPT2_TINY, OURO_TINY, built, ouro_dims,  # noqa: E402
                             rel)

COSTLY_ROWS = ("ouro",)

REL = 5e-5


def _program(cfg, params, batch, **kw):
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(lambda p: tfm.loss_fn(
            p, *batch, cfg, attention="local", **kw)))(params)


@pytest.mark.parametrize("loops", (1, 2))
def test_fewer_passes_match_the_reference_on_every_leaf(loops):
    """One and two passes beside the row's four: with one the program
    runs today's path (no gate, the head norms), which is the reference's
    single readout through its final norm."""
    row = built("ouro")
    cfg = dataclasses.replace(
        OURO_TINY, loops=loops,
        exit_entropy_coef=OURO_TINY.exit_entropy_coef * (loops > 1))
    params = dict(row.params)
    if loops == 1:
        del params["exit_gate_w"], params["exit_gate_b"]
    loss, grads = _program(cfg, params, row.batch())
    ref_params = dict(row.params)
    paths = reference.every_leaf(params)
    want, want_g, _ = jax.jit(lambda p, *b: reference.loss_and_grads(
        p, *b, dims=dict(ouro_dims(cfg), loops=loops), names=tuple(paths),
        paths=paths, entropy=loops > 1))(ref_params, *row.batch())
    assert abs(loss - want) <= REL * abs(want)
    for name, path in paths.items():
        assert rel(reference.leaf(grads, path), want_g[name]) <= REL, name


def test_the_tied_stack_is_the_untied_stack_of_four_copies():
    """The loop's definition: ``loops x N`` untied layers whose quarters
    are copies of the ``N``, the final norm between the quarters and a
    readout after each, give the program's loss; and the tied leaf's
    gradient is the sum of its four copies'."""
    row, cfg = built("ouro"), OURO_TINY
    loss, grads = row.program()
    untied = dict(row.params, layers=row.params["layers"] * cfg.loops)
    paths = {f"{t}.{i}.{name}": ("layers", t * cfg.n_layers + i, name)
             for t in range(cfg.loops) for i in range(cfg.n_layers)
             for name in row.params["layers"][i]}
    want, copies, _ = jax.jit(lambda p, *b: reference.loss_and_grads(
        p, *b, dims=ouro_dims(cfg), names=tuple(paths), paths=paths,
        untied=True))(untied, *row.batch())
    assert abs(loss - want) <= REL * abs(want)
    for i, layer in enumerate(grads["layers"]):
        for name, tied in layer.items():
            summed = sum(copies[f"{t}.{i}.{name}"]
                         for t in range(cfg.loops))
            assert rel(tied, summed) <= REL, (i, name)
            # No single pass's is the whole of it.
            assert rel(tied, copies[f"0.{i}.{name}"]) > 0.05, (i, name)


def test_the_exit_distribution_sums_to_one_and_is_the_plain_product():
    gates = 3.0 * jax.random.normal(jax.random.key(0), (4, 2, 33))
    log_p = tfm.exit_log_probs(gates)
    np.testing.assert_allclose(jnp.exp(log_p).sum(0), 1.0, atol=1e-6)
    np.testing.assert_allclose(
        jnp.exp(log_p), reference.exit_probabilities(gates), rtol=1e-5,
        atol=1e-7)
    # Where the product underflows the logarithms still hold: after three
    # gates at 200 nothing is left, and the sum is one all the same.
    far = tfm.exit_log_probs(jnp.full((4, 1), 200.0))
    np.testing.assert_allclose(far[:, 0], [0.0, -200.0, -400.0, -600.0],
                               atol=1e-4)
    assert float(jnp.exp(far).sum()) == 1.0
    # One pass more takes the last one's share apart and nothing else.
    np.testing.assert_allclose(
        jnp.exp(tfm.exit_log_probs(gates))[:3],
        jnp.exp(tfm.exit_log_probs(jnp.concatenate([gates, gates[:1]])))[:3],
        rtol=1e-6)


def test_what_the_layers_hand_the_loss_is_listed_a_pass():
    """A looped stack of expert layers: the routers' sums enter the
    auxiliary losses a layer and pass (two passes x two layers of them),
    and the first pass's are the stack's run once."""
    from test_lm_configs import OLMOE_TINY

    row = built("olmoe")
    cfg = dataclasses.replace(OLMOE_TINY, loops=2, exit_entropy_coef=0.05)
    params = dict(tfm.init_params(jax.random.PRNGKey(0), cfg),
                  **{k: v for k, v in row.params.items()
                     if not k.startswith("exit_gate")})
    tokens = row.batch()[0]
    once, looped = (jax.jit(lambda p, c=c: tfm.forward_with_router_stats(
        p, tokens, c, attention="local")[1])(params)
        for c in (OLMOE_TINY, cfg))
    assert len(looped) == cfg.loops * cfg.n_layers
    for want, got in zip(jax.tree_util.tree_leaves(once),
                         jax.tree_util.tree_leaves(looped[:cfg.n_layers])):
        np.testing.assert_allclose(got, want, rtol=1e-6)


def test_forward_returns_the_last_pass_readout_normed_once():
    """``forward`` hands back the last pass's logits: their cross-entropy
    is the reference's ``l_4`` (a second final norm on the carried state
    would move it)."""
    row = built("ouro")
    tokens, labels = row.batch()
    logits = tfm.forward(row.params, tokens, OURO_TINY, attention="local")
    last = float(row.reference()[2]["l_mean"][-1])
    assert abs(tfm.xent(logits, labels) - last) <= REL * last


def test_the_trace_time_series_count_every_pass(hvd):
    """The flash kernels' blocks are counted over all passes."""
    from horovod_tpu import telemetry

    cfg = OURO_TINY
    once = dataclasses.replace(OURO_TINY, loops=1, exit_entropy_coef=0.0)
    tokens = jax.ShapeDtypeStruct((2, 64), jnp.int32)

    def blocks(config):
        telemetry.reset_for_tests()
        telemetry.configure(True)
        try:
            jax.eval_shape(
                lambda p, t: tfm.loss_fn(p, t, t, config, attention="flash"),
                tfm.init_abstract(config), tokens)
            text = telemetry.render_prometheus()
        finally:
            telemetry.reset_for_tests()
        total = sum(float(line.rsplit(" ", 1)[1])
                    for line in text.splitlines()
                    if line.startswith("hvd_flash_blocks_total{"))
        return total, text

    one_pass, _ = blocks(once)
    looped, text = blocks(cfg)
    assert one_pass > 0 and looped == cfg.loops * one_pass
    assert "hvd_lm_loops 4" in text


# sha256 (16 digits) of the lowered loss and gradient (StableHLO without
# locations) of the eight rows that loop nothing, on two sequences, taken
# at PR 51's parent (1a81a7c): with ``loops == 1`` and ``post_norm`` off
# the seam this PR opened in ``_hidden_states``, ``loss_fn``, ``xent``,
# ``_logits_head``, ``attn_out`` and ``mlp_block`` writes the program the
# parent wrote, for one configuration of each kind.  (The whole train
# step of every row under the three ``remat``, 24 programs, and
# ``init_params`` hashed equal too: PERF.md, PR 51.)  A PR that changes a
# row's program on purpose takes the new digest: ``nemotron``'s is PR 52's
# (its share, 4 experts of a choice of 6, routes by the membership mask;
# b1117effe96bac67 at the parent), and the seven others held through it.
PARENTS_TEXT = {
    "gpt2": "6aa8718098cb07e6", "olmoe": "ddbc442ecc7eec77",
    "hybrid": "7c17eb526f81d722", "nemotron": "06ddf462d64a1006",
    "glm": "ab683f72e196effb", "keye": "c496bd636f46a3b6",
    "sdar": "0b2f69d61413b55f", "jamba": "c3f5e469d6347354"}


@pytest.mark.parametrize("name", sorted(PARENTS_TEXT))
def test_without_a_loop_the_lowered_loss_is_the_parents_text(name):
    import hashlib

    from test_lm_configs import _traced

    loss, params, batch = _traced(built(name))
    text = jax.jit(jax.value_and_grad(loss)).lower(params, *batch).as_text()
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == PARENTS_TEXT[
        name]


_IDS = (jnp.arange(64) >= 40).astype(jnp.int32)   # documents of 40 and 24


def _one_step(axes, shape, **kw):
    """``(loss, new parameters)`` of one SGD step of the row's program on
    its batch over the mesh ``axes`` x ``shape``."""
    from horovod_tpu.topology import build_mesh

    row, optimizer = built("ouro"), optax.sgd(0.1)
    mesh = build_mesh(axes=axes, shape=shape,
                      devices=jax.devices()[:int(np.prod(shape))])
    step, specs, _ = tfm.make_train_step(
        OURO_TINY, optimizer, mesh, donate=False,
        attention="ring" if "seq_axis" in kw else "local", **kw)
    params = jax.tree_util.tree_map(
        lambda leaf, spec: jax.device_put(leaf, NamedSharding(mesh, spec)),
        row.params, specs)
    batch = row.batch()
    if kw.get("packed"):
        batch += (jnp.broadcast_to(_IDS, batch[0].shape),)
    init = step.init if kw.get("shard_optimizer") else optimizer.init
    new, _, loss = step(params, init(params), *batch)
    return float(loss), jax.device_get(new)


_single_device = functools.cache(
    lambda packed: _one_step(("data",), (1,), packed=packed))


@pytest.mark.parametrize("what", ("model_axis", "seq_axis", "packed",
                                  "shard_optimizer"))
def test_beyond_the_data_axis_the_looped_step_is_the_single_device_step(
        hvd, what):
    """The loop touches no axis: under a tensor axis, a sequence axis
    (ring attention), packing and ZeRO-1 the step takes the loss and the
    update of the single-device step on the same batch (tried before
    refusing, ISSUE 51: none of the four is refused)."""
    if what in ("model_axis", "seq_axis"):
        axis = what.split("_")[0]
        loss, new = _one_step(("data", axis), (2, 2), **{what: axis})
    else:
        loss, new = _one_step(("data",), (2,), **{what: True})
    want, want_new = _single_device(what == "packed")
    if what == "packed":
        # Two documents a row are another loss than one.
        assert abs(want - _single_device(False)[0]) > 1e-4
    assert abs(loss - want) <= 2e-5 * abs(want)
    before = jax.tree_util.tree_leaves(built("ouro").params)
    for (path, leaf), got, old in zip(
            jax.tree_util.tree_leaves_with_path(want_new),
            jax.tree_util.tree_leaves(new), before):
        assert rel(got - old, leaf - old) <= 2e-3, path


@pytest.mark.parametrize("where", ("decode_step", "pipelined"))
def test_a_sandwich_normed_block_run_once_decodes_and_pipelines(hvd, where):
    """``post_norm`` lives in ``attn_out`` and ``mlp_block``, which
    ``decode_step`` and the pipelined stage share with the training
    forward: a sandwich-normed GPT-2 block decodes to the forward's logits
    and pipelines to the plain step's loss; only ``loops`` is refused
    there."""
    from horovod_tpu.topology import build_mesh

    cfg = dataclasses.replace(GPT2_TINY, post_norm=True)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    # Scales off 1, so that leaving the norm out would show.
    params["layers"] = [
        dict(layer, ln1_post_scale=layer["ln1_post_scale"] * 1.5,
             ln2_post_scale=layer["ln2_post_scale"] * 0.5)
        for layer in params["layers"]]
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)
    if where == "decode_step":
        want = tfm.forward(params, tokens, cfg, attention="local")
        plain = tfm.forward(params, tokens, GPT2_TINY, attention="local")
        assert float(jnp.abs(want - plain).max()) > 1e-2
        cache = tfm.init_kv_cache(cfg, 2, 8)
        decode = jax.jit(lambda token, cache, pos: tfm.decode_step(
            params, token, cache, pos, cfg))
        for pos in range(8):
            logits, cache = decode(tokens[:, pos], cache, pos)
            np.testing.assert_allclose(logits, want[:, pos], rtol=1e-4,
                                       atol=1e-5)
        return
    mesh = build_mesh(axes=("data", "pipe"), shape=(1, 2),
                      devices=jax.devices()[:2])
    step, shardings = tfm.make_train_step_pipelined(
        cfg, optax.sgd(0.1), mesh, donate=False)
    split = tfm.split_pipeline_params(params, 2)
    p_sh, o_sh = shardings(split)
    split = jax.device_put(split, p_sh)
    opt = jax.device_put(optax.sgd(0.1).init(split), o_sh)
    _, _, loss = step(split, opt, tokens, tokens)
    want = tfm.loss_fn(params, tokens, tokens, cfg, attention="local")
    assert abs(loss - want) <= 1e-5 * abs(want)
