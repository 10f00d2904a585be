"""Adapter of kind ``resnet``: a flax ResNet with BatchNorm state trained
through ``horovod_tpu.models.get_model`` and
``horovod_tpu.benchmark.make_train_step``."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import benchmark as program
from horovod_tpu.models import get_model
from horovod_tpu.models.resnet import space_to_depth
from perfbench.cell import Cell, on_first_device, seeded
from perfbench.reference import resnet as reference

MIX_KEYS = {"batch_per_chip", "mesh_axes", "optimizer", "stem",
            "input_dtype"}


def forward_macs(config: dict) -> int:
    """Multiply-accumulates of one forward pass of the published
    architecture on one image, counted from the convolution and dense
    shapes: 7x7/2 stem, 3x3/2 max-pool, ``stage_sizes`` blocks of width
    ``num_filters * 2^stage`` (stride on the first block of stages 2-4,
    on its 3x3: v1.5), a projection where a block changes shape, global
    average pool, dense to ``num_classes``."""
    width, expansion = config["num_filters"], config["bottleneck_expansion"]
    size = config["image_size"] // 2                  # after the stem
    macs = size * size * 7 * 7 * 3 * width
    size //= 2                                        # after the max-pool
    channels = width
    for stage, count in enumerate(config["stage_sizes"]):
        filters = width * 2 ** stage
        for j in range(count):
            stride = 2 if stage > 0 and j == 0 else 1
            out = size // stride
            if expansion == 1:                        # basic block
                macs += out * out * 9 * channels * filters
                macs += out * out * 9 * filters * filters
            else:                                     # bottleneck
                macs += size * size * channels * filters
                macs += out * out * 9 * filters * filters
                macs += out * out * filters * filters * expansion
            if stride != 1 or channels != filters * expansion:
                macs += out * out * channels * filters * expansion
            size, channels = out, filters * expansion
    return macs + channels * config["num_classes"]


def train_flops(config: dict, global_batch: int) -> float:
    """Model FLOPs of one training step: 2 FLOPs per MAC, forward plus the
    two backward matmuls of every layer (3x forward)."""
    return 3.0 * 2.0 * forward_macs(config) * global_batch


def build(config: dict, mix: dict, mesh) -> Cell:
    unknown = set(mix) - MIX_KEYS
    if unknown:
        raise ValueError(
            f"resnet adapter: unknown mix keys {sorted(unknown)}")
    spec = mix["optimizer"]
    if spec["name"] != "sgd" or spec.get("accumulator_dtype",
                                         "float32") != "float32":
        raise ValueError("resnet adapter: optimizer must be sgd with a "
                         "float32 momentum slot")
    data_axis = mix["mesh_axes"][0]
    replicas = int(mesh.shape[data_axis])
    global_batch = mix["batch_per_chip"] * replicas
    size, stem = config["image_size"], mix["stem"]
    model = get_model(config["model_name"],
                      num_classes=config["num_classes"], stem=stem)
    got = tuple(model.stage_sizes)
    if got != tuple(config["stage_sizes"]):
        raise ValueError(f"{config['model_name']} has stages {got}, the "
                         f"configuration says {config['stage_sizes']}")
    optimizer = optax.sgd(spec["learning_rate"], momentum=spec["momentum"])
    step = program.make_train_step(model, optimizer, mesh, data_axis,
                                   steps_per_call=1)
    input_dtype = jnp.dtype(mix["input_dtype"])
    packed = (size // 2, size // 2, 12) if stem == "s2d" else (size, size, 3)

    def make_arrays(key, pool):
        k_params, k_images, k_labels = jax.random.split(key, 3)
        variables = model.init(k_params,
                               jnp.zeros((1,) + packed, jnp.float32),
                               train=False)
        params = variables["params"]
        images = jax.random.normal(
            k_images, (pool, global_batch, size, size, 3), jnp.float32)
        labels = jax.random.randint(
            k_labels, (pool, global_batch), 0, config["num_classes"],
            jnp.int32)

        def one(i):
            # The relayout belongs to the input pipeline: done here, once.
            img = space_to_depth(images[i]) if stem == "s2d" else images[i]
            return img.astype(input_dtype), labels[i]

        return ((params, variables["batch_stats"], optimizer.init(params)),
                [one(i) for i in range(pool)])

    data_sharding = NamedSharding(mesh, P(data_axis))
    make, state_shapes, batch_shapes = seeded(
        make_arrays, NamedSharding(mesh, P()),
        (data_sharding, data_sharding))

    ref = jax.jit(functools.partial(
        reference.loss_and_head_grad,
        stage_sizes=tuple(config["stage_sizes"]), stem=stem,
        replicas=replicas))

    def run_reference(state, batch):
        params, (images, labels) = on_first_device((state[0], batch), mesh)
        return ref(params, images, labels)

    return Cell(
        step=step, state_shapes=state_shapes, batch_shapes=batch_shapes,
        make=make,
        flops_per_step=train_flops(config, global_batch),
        item="images", items_per_step=global_batch,
        grad_per_delta=-1.0 / spec["learning_rate"],
        checked=lambda state: {"head_kernel": state[0]["head"]["kernel"]},
        reference=run_reference)
