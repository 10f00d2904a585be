"""ResNet-50 ImageNet training recipe, TPU-native (reference
``examples/keras_imagenet_resnet50.py`` / ``pytorch_imagenet_resnet50.py``).

The full distributed recipe from the reference, on the SPMD plane:

* mesh + batch sharded over the data axis, params replicated
* gradient averaging fused into the jitted step (``make_train_step``)
* LR = base_lr x world size with ``LearningRateWarmupCallback`` ramping
  over the first epochs and staircase decay afterwards (the reference's
  schedule: x0.1 at epochs 30/60/80)
* metrics averaged across the mesh, ``MetricAverageCallback``-style
* rank-0 checkpointing with restart-resume (``hvd.checkpoint``)

Hermetic by default: synthetic ImageNet-shaped data (the reference's
synthetic-benchmark convention); point ``--data-dir`` at real NHWC
uint8 .npy shards to train on real data.

Run (single host, 8 simulated chips):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/jax_imagenet_resnet50.py --epochs 2 --image-size 64
"""

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import checkpoint
from horovod_tpu.benchmark import make_train_step
from horovod_tpu.callbacks import (LearningRateScheduleCallback,
                                   LearningRateWarmupCallback)
from horovod_tpu.models import get_model
from horovod_tpu.topology import data_axis, mesh_size
from horovod_tpu.utils.compile_cache import enable_compile_cache


def synthetic_batch(rng, global_bs, image_size, num_classes):
    images = rng.standard_normal(
        (global_bs, image_size, image_size, 3), dtype=np.float32)
    labels = rng.integers(0, num_classes, (global_bs,), dtype=np.int32)
    return images, labels


def main():
    p = argparse.ArgumentParser(description="ResNet-50 ImageNet recipe")
    p.add_argument("--epochs", type=int, default=90)
    p.add_argument("--steps-per-epoch", type=int, default=8)
    p.add_argument("--batch-size", type=int, default=8,
                   help="per-chip batch size")
    p.add_argument("--base-lr", type=float, default=0.0125,
                   help="per-chip LR (reference keras_imagenet_resnet50)")
    p.add_argument("--warmup-epochs", type=int, default=5)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--checkpoint-dir", default="./checkpoints-resnet50")
    p.add_argument("--data-dir", default=None,
                   help="optional dir of images.npy/labels.npy shards")
    args = p.parse_args()

    enable_compile_cache()
    hvd.init()
    mesh = hvd.mesh()
    ax = data_axis(mesh)
    n_chips = mesh_size(mesh)
    global_bs = args.batch_size * n_chips

    model = get_model("resnet50", num_classes=args.num_classes)
    rng = jax.random.PRNGKey(0)
    variables = model.init(
        rng, jnp.zeros((1, args.image_size, args.image_size, 3)),
        train=False)
    params, batch_stats = variables["params"], variables["batch_stats"]

    # inject_hyperparams makes the LR an opt-state leaf, so callbacks can
    # set it between steps without recompiling the jitted program.
    optimizer = optax.inject_hyperparams(optax.sgd)(
        learning_rate=args.base_lr, momentum=0.9, nesterov=True)
    opt_state = optimizer.init(params)

    # Reference schedule: warmup to base_lr*size over warmup_epochs, then
    # staircase decay x0.1 at 30/60/80 (keras_imagenet_resnet50.py).
    lr_box = {"lr": args.base_lr}

    def set_lr(lr):
        lr_box["lr"] = lr

    # The global batch scales with the MESH (all chips across all
    # processes), so the linear-scaling rule and the warmup target both
    # use n_chips — not the process count.
    size = n_chips
    warmup = LearningRateWarmupCallback(
        args.base_lr, warmup_epochs=args.warmup_epochs, set_lr=set_lr,
        steps_per_epoch=args.steps_per_epoch, size=size)

    def decay_mult(epoch):
        m = size
        for boundary in (30, 60, 80):
            if epoch >= boundary:
                m *= 0.1
        return m

    decay = LearningRateScheduleCallback(
        args.base_lr, decay_mult, start_epoch=args.warmup_epochs + 1,
        set_lr=set_lr)

    step = make_train_step(model, optimizer, mesh, ax)
    repl = NamedSharding(mesh, P())
    params, batch_stats, opt_state = jax.device_put(
        (params, batch_stats, opt_state), repl)

    # Resume from the latest checkpoint if one exists (restart-safe).
    start_epoch = 0
    last = checkpoint.latest_step(args.checkpoint_dir)
    if last is not None:
        params, batch_stats, opt_state = checkpoint.restore(
            args.checkpoint_dir, (params, batch_stats, opt_state))
        start_epoch = last + 1
        if hvd.rank() == 0:
            print(f"resumed from epoch {last}", flush=True)

    data_rng = np.random.default_rng(1234)
    shard = NamedSharding(mesh, P(ax))
    for epoch in range(start_epoch, args.epochs):
        warmup.on_epoch_begin(epoch)
        decay.on_epoch_begin(epoch)
        losses = []
        for batch_i in range(args.steps_per_epoch):
            warmup.on_batch_begin(batch_i)
            # Feed the scheduled LR into the opt state (an array leaf —
            # no recompile).
            opt_state.hyperparams["learning_rate"] = jnp.asarray(
                lr_box["lr"], jnp.float32)
            if args.data_dir:
                images = np.load(os.path.join(
                    args.data_dir, f"images_{epoch}_{batch_i}.npy"))
                labels = np.load(os.path.join(
                    args.data_dir, f"labels_{epoch}_{batch_i}.npy"))
            else:
                images, labels = synthetic_batch(
                    data_rng, global_bs, args.image_size, args.num_classes)
            images = jax.device_put(images, shard)
            labels = jax.device_put(labels.astype(np.int32), shard)
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, images, labels)
            losses.append(loss)
        # Metric averaging over the mesh happened inside the step (pmean);
        # the epoch mean here is a host-side reduction of per-step losses.
        mean_loss = float(np.mean([np.asarray(l) for l in losses]))
        if hvd.rank() == 0:
            print(f"epoch {epoch}: loss {mean_loss:.4f} "
                  f"lr {lr_box['lr']:.5f}", flush=True)
        checkpoint.save(args.checkpoint_dir,
                        (params, batch_stats, opt_state), step=epoch,
                        max_to_keep=3)

    if hvd.rank() == 0:
        print("OK", flush=True)


if __name__ == "__main__":
    main()
