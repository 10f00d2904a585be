"""JAX/SPMD synthetic benchmark — the TPU-native flagship (BASELINE
config #2 analog; reference ``examples/tensorflow2_synthetic_benchmark.py``).

Trains a flax ResNet on fixed synthetic data over the full device mesh
(DP via fused-psum gradient averaging), printing img/sec, achieved
TFLOP/s and MFU.  Run::

    python examples/jax_synthetic_benchmark.py --model resnet50 --batch-size 64
    # scaling efficiency (1 chip/host baseline vs all chips):
    python examples/jax_synthetic_benchmark.py --efficiency

On a chip-less host, force a virtual mesh first:
``XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu``.
"""

import argparse
import json

import horovod_tpu as hvd
from horovod_tpu.benchmark import (run_scaling_efficiency,
                                   run_synthetic_benchmark)
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
    p.add_argument("--efficiency", action="store_true",
                   help="measure weak-scaling efficiency instead")
    p.add_argument("--json", action="store_true",
                   help="emit one JSON line instead of prose")
    args = p.parse_args()

    enable_compile_cache()
    hvd.init()
    kw = dict(model_name=args.model, batch_size=args.batch_size,
              image_size=args.image_size,
              num_warmup_batches=args.num_warmup_batches,
              num_batches_per_iter=args.num_batches_per_iter,
              num_iters=args.num_iters, verbose=not args.json)
    if args.efficiency:
        res = run_scaling_efficiency(**kw)
    else:
        res = run_synthetic_benchmark(**kw)
    if args.json:
        print(json.dumps(res))


if __name__ == "__main__":
    main()
