"""JAX/SPMD synthetic benchmark: the port of the reference's
``examples/tensorflow2_synthetic_benchmark.py`` (``:86-132``) to one
compiled data-parallel step over the device mesh.

A flax model trains on one fixed synthetic batch; after the warm-up
batches, ``--num-iters`` rounds of ``--num-batches-per-iter`` steps are
timed and img/sec is printed as mean +- 1.96 sigma over the rounds.  Run::

    python examples/jax_synthetic_benchmark.py --model resnet50 --batch-size 64

On a chip-less host, force a virtual mesh first:
``XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu``.
This is a user's script, as the reference's is.  The repo's numbers of
record (utilization, memory, per-layer times on the chip) come from
``perfbench/run.py`` (``BENCHMARK.json``).
"""

import argparse
import timeit

import jax
import numpy as np

from horovod_tpu.benchmark import make_bench_state, make_train_step
from horovod_tpu.utils.compile_cache import enable_compile_cache


def main():
    p = argparse.ArgumentParser(
        description="JAX Synthetic Benchmark",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    p.add_argument("--model", default="resnet50")
    p.add_argument("--batch-size", type=int, default=64,
                   help="input batch size per chip")
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument("--num-warmup-batches", type=int, default=5)
    p.add_argument("--num-batches-per-iter", type=int, default=10)
    p.add_argument("--num-iters", type=int, default=10)
    args = p.parse_args()

    enable_compile_cache()
    mesh, ax, model, optimizer, _, state, batch = make_bench_state(
        args.model, args.batch_size, args.image_size)
    step = make_train_step(model, optimizer, mesh, ax)
    n_chips = mesh.devices.size
    print(f"Model: {args.model}\nBatch size: {args.batch_size} per chip\n"
          f"Number of chips: {n_chips} ({jax.devices()[0].device_kind})")

    def run(n):
        nonlocal state
        for _ in range(n):
            *state, loss = step(*state, *batch)
        jax.block_until_ready(loss)

    print("Running warmup...")
    run(args.num_warmup_batches)
    print("Running benchmark...")
    img_secs = []
    for i in range(args.num_iters):
        t = timeit.timeit(lambda: run(args.num_batches_per_iter), number=1)
        img_secs.append(args.batch_size * args.num_batches_per_iter / t)
        print(f"Iter #{i}: {img_secs[-1]:.1f} img/sec per chip")
    mean, conf = np.mean(img_secs), 1.96 * np.std(img_secs)
    print(f"Img/sec per chip: {mean:.1f} +-{conf:.1f}")
    print(f"Total img/sec on {n_chips} chip(s): "
          f"{n_chips * mean:.1f} +-{n_chips * conf:.1f}")


if __name__ == "__main__":
    main()
