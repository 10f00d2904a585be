"""JAX MNIST end-to-end (BASELINE config #1 analog; reference
``examples/tensorflow_mnist.py``).

The Horovod recipe, TPU-native: init → mesh → shard the batch on the
data axis → gradient-averaged training step → rank-0 checkpointing
(reference gates ``checkpoint_dir`` on rank 0, ``tensorflow_mnist.py:144``;
here that convention is the ``hvd.checkpoint`` API).

Runs single-process on CPU (the 1-process allreduce baseline) or under
``hvdrun -np N``.  Uses a deterministic synthetic MNIST-shaped dataset so
the example is hermetic (no downloads); pass ``--mnist-dir`` to point at
real idx files if you have them.
"""

import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

import horovod_tpu as hvd
from horovod_tpu.utils.compile_cache import enable_compile_cache


class ConvNet(nn.Module):
    """The classic MNIST convnet (reference tensorflow_mnist.py:32-58)."""

    @nn.compact
    def __call__(self, x):
        x = nn.Conv(32, (5, 5), padding="SAME")(x)
        x = nn.relu(nn.max_pool(x, (2, 2), (2, 2)))
        x = nn.Conv(64, (5, 5), padding="SAME")(x)
        x = nn.relu(nn.max_pool(x, (2, 2), (2, 2)))
        x = x.reshape((x.shape[0], -1))
        x = nn.relu(nn.Dense(512)(x))
        return nn.Dense(10)(x)


def synthetic_mnist(n, seed=0):
    """Deterministic class-structured fake MNIST: each digit d is a blob in
    a d-dependent location, so the model has real signal to learn."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 10, n).astype(np.int32)
    images = rng.normal(0.0, 0.1, (n, 28, 28, 1)).astype(np.float32)
    for i, d in enumerate(labels):
        r, c = 4 + (d % 5) * 4, 4 + (d // 5) * 10
        images[i, r:r + 6, c:c + 6, 0] += 1.0
    return images, labels


def main():
    p = argparse.ArgumentParser(description="JAX MNIST")
    p.add_argument("--batch-size", type=int, default=64,
                   help="global batch size")
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--lr", type=float, default=0.001)
    p.add_argument("--checkpoint-dir", default=None)
    args = p.parse_args()

    enable_compile_cache()
    hvd.init()
    mesh = hvd.mesh()
    n_dev = mesh.devices.size
    if args.batch_size % n_dev:
        args.batch_size += n_dev - args.batch_size % n_dev

    model = ConvNet()
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 28, 28, 1)))["params"]
    # Scale LR by world size, as the Horovod docs prescribe for DP.
    optimizer = optax.adam(args.lr * hvd.size())

    def loss_fn(params, batch):
        images, labels = batch
        logits = model.apply({"params": params}, images)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    step = hvd.make_training_step(loss_fn, optimizer, mesh)
    opt_state = step.init(params)

    # Resume if a checkpoint exists (restore on root + broadcast).
    start = 0
    if args.checkpoint_dir:
        state = hvd.checkpoint.restore(
            args.checkpoint_dir,
            {"params": params, "opt_state": opt_state,
             "step": np.asarray(0, np.int32)})
        params, opt_state = state["params"], state["opt_state"]
        start = int(state["step"])

    images, labels = synthetic_mnist(args.batch_size * 64, seed=hvd.rank())
    from jax.sharding import NamedSharding, PartitionSpec as P
    shard = NamedSharding(mesh, P(mesh.axis_names[0]))

    loss = None
    for i in range(start, args.steps):
        o = (i * args.batch_size) % (images.shape[0] - args.batch_size)
        xb = jax.device_put(images[o:o + args.batch_size], shard)
        yb = jax.device_put(labels[o:o + args.batch_size], shard)
        params, opt_state, loss = step(params, opt_state, (xb, yb))
        if i % 50 == 0 and hvd.rank() == 0:
            print(f"step {i}: loss {float(loss):.4f}", flush=True)

    if hvd.rank() == 0 and loss is not None:
        print(f"final loss: {float(loss):.4f}", flush=True)
    if args.checkpoint_dir:
        hvd.checkpoint.save(args.checkpoint_dir,
                            {"params": params, "opt_state": opt_state,
                             "step": np.asarray(args.steps, np.int32)},
                            step=args.steps)
    # model must have learned the synthetic structure
    logits = model.apply({"params": params}, jnp.asarray(images[:512]))
    acc = float((jnp.argmax(logits, -1) == jnp.asarray(labels[:512])).mean())
    if hvd.rank() == 0:
        print(f"train accuracy: {acc:.3f}", flush=True)
    assert acc > 0.5, f"model failed to learn (acc={acc})"


if __name__ == "__main__":
    main()
