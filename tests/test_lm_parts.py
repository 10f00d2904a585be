"""The seam is a seam (``horovod_tpu/models/parts.py``): a part that the
package does not know, put in the table by a fixture, goes through
parameters, specs, the forward pass, the train step and the derived
refusals without an edit to ``models/transformer.py``; and the table
accounts for every field of the config, each once.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from horovod_tpu.models import parts
from horovod_tpu.models import transformer as tfm

FIELDS = {f.name for f in dataclasses.fields(tfm.TransformerConfig)}


@pytest.fixture()
def toy(monkeypatch):
    """A mixer of one leaf, ``x + scale * x``, selected by the layer kind
    ``"toy"``, which hands the loss something of its own and runs over the
    data axis alone."""
    part = parts.Part(
        name="toy", fields=(), validate=lambda cfg, used: None,
        init=lambda k, cfg: {"toy_scale": jnp.full((cfg.d_model,), 0.5)},
        specs=lambda cfg, model_axis: parts.whole("toy_scale"),
        apply=lambda x, layer, cfg, ctx: (
            x + x * layer["toy_scale"].astype(x.dtype),
            {"toy_sum": jnp.sum(x)}),
        unsupported=parts.everywhere("layer_types"))
    monkeypatch.setitem(tfm.PARTS, "toy", part)
    monkeypatch.setitem(tfm.LAYER_KINDS, "toy", ("toy", True))
    return tfm.TransformerConfig(
        vocab_size=64, d_model=32, n_heads=2, n_layers=2, d_ff=64,
        max_seq=32, dtype=jnp.float32, layer_types=("toy", "full_attention"))


def _tokens(batch=8, seq=32):
    return jax.random.randint(jax.random.PRNGKey(1), (batch, seq), 0, 64)


def test_a_part_the_package_does_not_know_gets_leaves_specs_and_a_body(toy):
    from jax.sharding import PartitionSpec

    params = tfm.init_params(jax.random.PRNGKey(0), toy)
    assert sorted(params["layers"][0]) == ["ln2_scale", "toy_scale", "w1",
                                           "w2"]
    assert "toy_scale" not in params["layers"][1]
    specs = tfm.param_specs(toy, None)
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(
                specs, is_leaf=lambda x: isinstance(x, PartitionSpec)))
    assert (jax.tree_util.tree_map(lambda a: a.shape, tfm.init_abstract(toy))
            == jax.tree_util.tree_map(lambda a: a.shape, params))
    # Its body runs: with a scale of zero the layer is its MLP alone, and
    # with its own scale it is not.
    tokens = _tokens(2)
    bare = dataclasses.replace(toy, layer_types=("mlp", "full_attention"))
    zeroed = jax.tree_util.tree_map(lambda x: x, params)
    zeroed["layers"][0]["toy_scale"] = jnp.zeros((32,))
    without = {k: v for k, v in params["layers"][0].items()
               if k != "toy_scale"}
    want = tfm.forward(dict(params, layers=[without, params["layers"][1]]),
                       tokens, bare, attention="local")
    np.testing.assert_allclose(
        tfm.forward(zeroed, tokens, toy, attention="local"), want, atol=1e-6)
    moved = tfm.forward(params, tokens, toy, attention="local")
    assert float(jnp.abs(moved - want).max()) > 1e-3
    # What it hands the loss is collected and asked for by nobody.
    assert tfm.forward_with_router_stats(params, tokens, toy,
                                         attention="local")[1] == []


@pytest.mark.parametrize("remat", ("none", "full"))
def test_the_train_step_trains_it_on_eight_devices(hvd, toy, remat):
    from horovod_tpu.topology import build_mesh

    mesh = build_mesh(axes=("data",), devices=jax.devices())
    optimizer = optax.sgd(0.1)
    step, _, _ = tfm.make_train_step(toy, optimizer, mesh, attention="local",
                                     donate=False, remat=remat)
    params = tfm.init_params(jax.random.PRNGKey(0), toy)
    tokens = _tokens()
    new, _, loss = step(params, optimizer.init(params), tokens, tokens)
    want, grads = jax.value_and_grad(tfm.loss_fn)(params, tokens, tokens,
                                                  toy, attention="local")
    np.testing.assert_allclose(loss, want, rtol=1e-5)
    moved = (new["layers"][0]["toy_scale"]
             - params["layers"][0]["toy_scale"]) / -0.1
    assert float(jnp.linalg.norm(moved)) > 0
    np.testing.assert_allclose(moved, grads["layers"][0]["toy_scale"],
                               rtol=2e-3, atol=1e-6)


@pytest.mark.parametrize("what", ("model_axis", "seq_axis", "packed",
                                  "segment_ids", "decode_step", "pipelined"))
def test_what_it_does_not_implement_is_refused_by_asking_it(hvd, toy, what):
    from test_lm_configs import _beyond_the_data_axis

    argument = {"pipelined": "make_train_step_pipelined"}.get(what, what)
    with pytest.raises(NotImplementedError,
                       match=rf"{argument}.*TransformerConfig\.layer_types"):
        _beyond_the_data_axis(what, toy, 32)


def test_a_part_that_implements_an_axis_runs_under_it(hvd, toy, monkeypatch):
    monkeypatch.setitem(tfm.PARTS, "toy", dataclasses.replace(
        tfm.PARTS["toy"], unsupported={}))
    from test_lm_configs import _beyond_the_data_axis

    for what in ("model_axis", "seq_axis", "packed"):
        _beyond_the_data_axis(what, toy, 32)


# --- the table accounts for the config --------------------------------------

@pytest.mark.parametrize("name", tuple(tfm.PARTS))
def test_every_part_states_its_fields_and_nobody_elses(name):
    part = tfm.PARTS[name]
    assert part.name == name
    assert part.fields and set(part.fields) <= FIELDS, part.fields
    others = {field for other in tfm.PARTS.values() if other is not part
              for field in other.fields}
    assert not set(part.fields) & (others | set(tfm.BLOCK_FIELDS))
    # What it cannot run under is said in terms of arguments and fields.
    assert set(part.unsupported) <= {"model_axis", "seq_axis", "segment_ids"}
    for fields in part.unsupported.values():
        assert set(fields) <= FIELDS, fields


def test_every_field_is_a_parts_or_the_blocks_own():
    owned = [field for part in tfm.PARTS.values() for field in part.fields]
    assert sorted(owned + list(tfm.BLOCK_FIELDS)) == sorted(FIELDS)


def test_one_function_chooses_and_the_table_holds_what_it_names():
    """Every layer kind names a mixer that :func:`layer_parts` can find,
    and every part of the table is chosen by some configuration of
    ``tests/test_lm_configs.py``."""
    from test_lm_configs import ROWS

    chosen = {part.name for row in ROWS.values()
              for part in tfm.parts_in_use(row.cfg)}
    assert chosen == set(tfm.PARTS)
    for kind, (mixer, _) in tfm.LAYER_KINDS.items():
        assert mixer in (None, "attention") or mixer in tfm.PARTS, kind
