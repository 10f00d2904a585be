"""The flax/BatchNorm data-parallel training step, and the two state
recipes of the start-up check.

Not a benchmark: the benchmark is ``perfbench/`` (``BENCHMARK.json``), and
this package ships no measurement code.  What lives here is what live
callers import under this path:

``make_train_step``      one jitted ``shard_map`` step for a flax model with
                         BatchNorm state (batch sharded over the data axis,
                         fused ``pmean`` of the gradients, identical update)
                         — the program of the ``resnet50_b256`` cell
                         (``perfbench/adapters/resnet.py``) and of
                         ``examples/jax_imagenet_resnet50.py``.
``make_bench_state``     model, optimizer, replicated state and one fixed
                         synthetic batch for that step.
``make_lm_bench_state``  the same for the dense LM step of
                         ``models/transformer.make_train_step``;
                         ``chip_smoke.py`` builds its LM phases from it.

The module keeps its name only until ``perfbench/adapters/resnet.py`` can
import the step from beside ``models/resnet.py`` (ROADMAP D14).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.ops.fusion import fused_pytree_mean
from horovod_tpu import telemetry
from horovod_tpu.telemetry import scopes
from horovod_tpu.topology import build_mesh, data_axis, mesh_size


@telemetry.span("make_train_step", step=scopes.TRAIN_STEP)
def make_train_step(model, optimizer, mesh, axis_name: Optional[str] = None,
                    steps_per_call: int = 1):
    """One SPMD training step for a flax model with BatchNorm state.

    Returns ``step(params, batch_stats, opt_state, images, labels) ->
    (params, batch_stats, opt_state, loss)`` jitted over ``mesh`` with the
    batch sharded on the data axis, everything else replicated.

    ``steps_per_call > 1`` runs that many steps inside ONE compiled
    program via ``lax.scan`` (same batch each step, like the reference's
    fixed synthetic batch), so host dispatch is paid once per call.  The
    protocol dates from a set-up where a dispatch+fetch round trip cost
    ~100 ms; whether it still pays on the present machine is unverified
    (``chip_smoke.py`` prints the seconds of one dispatched step;
    ROADMAP S10).
    """
    ax = axis_name or data_axis(mesh)

    def _step(params, batch_stats, opt_state, images, labels):
        def loss_fn(p):
            logits, mutated = model.apply(
                {"params": p, "batch_stats": batch_stats}, images,
                train=True, mutable=["batch_stats"])
            with jax.named_scope(scopes.LOSS):
                loss = optax.softmax_cross_entropy_with_integer_labels(
                    logits, labels).mean()
            return loss, mutated["batch_stats"]

        (loss, new_stats), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)

        def do_update():
            # The Horovod step: average gradients across the mesh (fused
            # psum — reference fusion_buffer_manager + NCCLAllreduce,
            # here one bf16-safe bucketed pmean riding ICI).
            g = fused_pytree_mean(grads, ax)
            with jax.named_scope(scopes.OPTIMIZER):
                updates, new_opt_state = optimizer.update(g, opt_state,
                                                          params)
                new_params = optax.apply_updates(params, updates)
            return new_params, new_stats, new_opt_state

        from horovod_tpu import resilience
        ((new_params, out_stats, new_opt_state),
         mean_loss) = resilience.apply_step_guard(
            do_update, loss=loss, grads=grads,
            old_state=(params, batch_stats, opt_state), axes=(ax,))
        return new_params, out_stats, new_opt_state, mean_loss

    if steps_per_call > 1:
        def _loop(params, batch_stats, opt_state, images, labels):
            def body(carry, _):
                p, s, o = carry
                p, s, o, loss = _step(p, s, o, images, labels)
                return (p, s, o), loss
            (p, s, o), losses = lax.scan(
                body, (params, batch_stats, opt_state), None,
                length=steps_per_call)
            return p, s, o, losses[-1]
        fn = _loop
    else:
        fn = _step

    repl, shard = P(), P(ax)
    smapped = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(repl, repl, repl, shard, shard),
        out_specs=(repl, repl, repl, repl),
        check_vma=False)
    return jax.jit(scopes.named(smapped, scopes.TRAIN_STEP),
                   donate_argnums=(0, 1, 2))


def make_bench_state(model_name: str = "resnet50", batch_size: int = 64,
                     image_size: int = 224, num_classes: int = 1000,
                     input_dtype: str = "float32", stem: str = "conv7",
                     remat: Optional[str] = None, mesh=None,
                     learning_rate: float = 0.01):
    """The ONE benchmark-state recipe, shared by the throughput run and
    ``chip_smoke.py`` so they always measure the same program.  Returns
    ``(mesh, ax, model, optimizer, s2d, (params, batch_stats, opt_state),
    (images, labels))`` with the batch sharded over the data axis and
    state replicated.
    """
    from horovod_tpu.models import get_model

    if not hvd.is_initialized():
        hvd.init()
    mesh = mesh if mesh is not None else hvd.mesh()
    ax = data_axis(mesh)
    global_bs = batch_size * mesh_size(mesh)

    # "s2d": space-to-depth input pipeline + exact 4x4/s1 stem
    # reparameterization (models/resnet.py:space_to_depth) — input arrives
    # packed [B, H/2, W/2, 12], a pure relayout done once host-side.
    if stem not in ("conv7", "s2d"):
        raise ValueError(f"stem={stem!r}: expected 'conv7' or 's2d'")
    s2d = stem == "s2d" and model_name.startswith("resnet")
    extra = {}
    if s2d:
        extra["stem"] = stem
    if remat and model_name.startswith("resnet"):
        extra["remat"] = remat
    model = get_model(model_name, num_classes=num_classes, **extra)
    init_shape = ((1, image_size // 2, image_size // 2, 12) if s2d
                  else (1, image_size, image_size, 3))
    rng = jax.random.PRNGKey(0)
    variables = model.init(rng, jnp.zeros(init_shape, jnp.float32),
                           train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]
    optimizer = optax.sgd(learning_rate, momentum=0.9)
    opt_state = optimizer.init(params)

    # Fixed synthetic batch, placed sharded on the data axis (reference keeps
    # one random batch for the whole run, :40-43).  ``input_dtype="bfloat16"``
    # feeds the batch in the model's compute dtype — the TPU-idiomatic input
    # pipeline (halves the first conv's HBM read; training semantics are
    # unchanged since the model casts to bf16 anyway).
    images_np = np.random.default_rng(0).standard_normal(
        (global_bs, image_size, image_size, 3), dtype=np.float32)
    if s2d:
        from horovod_tpu.models.resnet import space_to_depth
        images_np = space_to_depth(images_np)
    # Cast host-side (ml_dtypes handles bf16 in numpy) so device_put still
    # uploads only per-shard slices; a jnp cast would stage the full
    # global batch on one device first.
    images = jax.device_put(
        images_np.astype(jnp.dtype(input_dtype)),
        NamedSharding(mesh, P(ax)))
    labels = jax.device_put(
        np.random.default_rng(1).integers(0, num_classes, (global_bs,),
                                          dtype=np.int32),
        NamedSharding(mesh, P(ax)))
    repl = NamedSharding(mesh, P())
    params, batch_stats, opt_state = jax.device_put(
        (params, batch_stats, opt_state), repl)
    return (mesh, ax, model, optimizer, s2d,
            (params, batch_stats, opt_state), (images, labels))


def make_lm_bench_state(d_model: int, n_layers: int, n_heads: int,
                        d_ff: int, vocab_size: int, seq_len: int,
                        batch_size: int, attention: str = "flash",
                        remat: str = "none", steps_per_call: int = 1,
                        learning_rate: float = 1e-4, mesh=None,
                        shard_optimizer: bool = False,
                        compression: Optional[str] = None):
    """The LM twin of :func:`make_bench_state`, the state recipe of
    ``chip_smoke.py``'s LM phases.  Returns
    ``(mesh, cfg, step, (params, opt_state), (tokens, labels))``: bf16
    compute on every platform with f32 master weights, a ``("data",)``
    mesh over every device by default, ``batch_size`` per chip of one
    fixed synthetic batch sharded over it, state placed per the step's
    specs (1/N flat buckets under ``shard_optimizer``)."""
    from horovod_tpu.models import transformer as tfm

    if mesh is None:
        mesh = build_mesh(axes=("data",))
    global_bs = batch_size * mesh_size(mesh)
    cfg = tfm.TransformerConfig(
        vocab_size=vocab_size, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=d_ff, max_seq=seq_len, dtype=jnp.bfloat16)

    # SGD+momentum (the ResNet step's optimizer): one slot per param —
    # adam's two would displace ~4 GB of batch/activations at the
    # compute-bound sizes the start-up check runs.  The slot is bf16
    # (halves optimizer HBM; fp32 master weights unchanged).
    optimizer = optax.sgd(learning_rate, momentum=0.9,
                          accumulator_dtype=jnp.bfloat16)
    step, specs, opt_specs = tfm.make_train_step(
        cfg, optimizer, mesh, data_axis="data", attention=attention,
        remat=remat, steps_per_call=steps_per_call,
        shard_optimizer=shard_optimizer, compression=compression)

    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    params = jax.device_put(params, jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs))
    init_state = step.init if shard_optimizer else optimizer.init
    opt_state = jax.device_put(
        init_state(params), jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), opt_specs,
            is_leaf=lambda x: isinstance(x, P)))

    data_sh = NamedSharding(mesh, P("data"))
    toks = np.random.default_rng(0).integers(
        0, vocab_size, (global_bs, seq_len + 1), dtype=np.int32)
    tokens = jax.device_put(toks[:, :-1], data_sh)
    labels = jax.device_put(toks[:, 1:], data_sh)
    return mesh, cfg, step, (params, opt_state), (tokens, labels)
