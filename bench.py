#!/usr/bin/env python
"""Driver benchmark: ResNet-50 synthetic training throughput per chip.

One process, on the chip only: exits non-zero, naming the platform, when
``jax.devices()[0].platform`` is not ``tpu``.  Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "device": {"platform": ..., "kind": ..., "count": N}, ...}
with one key per further lane (``resnet101``, ``lm``, ``eager_allreduce``).
A lane that raises is reported under ``"errors"`` in that line and makes
the exit code 1; no figure is printed that this run did not measure.

Baseline anchor (BASELINE.md): the reference's published absolute number is
ResNet-101 at 1656.82 img/sec on 16 Pascal GPUs (reference
``docs/benchmarks.rst:26-43``) = 103.55 img/sec/GPU; that is the
``vs_baseline`` denominator for our ResNet-50-per-chip number (the closest
published absolute-throughput anchor the reference ships).
"""

import json
import os
import sys
import traceback

BASELINE_IMG_SEC_PER_CHIP = 1656.82 / 16.0


def _resnet50_bench():
    """The headline lane.  256/chip measured fastest on v5e before PR 1
    (the per-chip batch is a free parameter in the reference harness too:
    tensorflow2_synthetic_benchmark.py --batch-size)."""
    from horovod_tpu.benchmark import run_synthetic_benchmark

    # 150 batches/round: each round ends in a loss fetch (the sync
    # barrier).  The figure was swept on an earlier set-up where that
    # round trip cost ~100 ms (10 batches/round taxed every measurement
    # ~10%, 30 ~3%, 150 the last +0.4%); unverified on the present
    # machine — chip_smoke.py prints what one dispatched step costs
    # (ROADMAP S10).
    protocol = dict(
        model_name=os.environ.get("BENCH_MODEL", "resnet50"),
        batch_size=int(os.environ.get("BENCH_BATCH_SIZE", "256")),
        num_warmup_batches=int(os.environ.get("BENCH_WARMUP", "5")),
        num_batches_per_iter=int(os.environ.get("BENCH_BATCHES", "150")),
        num_iters=int(os.environ.get("BENCH_ITERS", "5")),
        per_step_dispatch=os.environ.get("BENCH_PER_STEP_DISPATCH",
                                         "0") == "1",
        # bf16 input pipeline: the model computes in bf16 regardless, so
        # feeding bf16 halves the first conv's HBM read.
        input_dtype=os.environ.get("BENCH_INPUT_DTYPE", "bfloat16"),
        # s2d: space-to-depth input layout + exact 4x4/s1 stem
        # reparameterization (models/resnet.py), the TPU-canonical input
        # pipeline (MLPerf ResNet does the same).
        stem=os.environ.get("BENCH_STEM", "s2d"),
    )
    res = run_synthetic_benchmark(
        verbose=os.environ.get("BENCH_VERBOSE", "0") == "1", **protocol)
    value = res["img_sec_per_chip"]
    out = {
        "value": round(value, 2),
        "vs_baseline": round(value / BASELINE_IMG_SEC_PER_CHIP, 3),
    }
    # Utilization accounting (extra keys; the driver reads the four above).
    if res.get("tflops_per_chip") is not None:
        out["tflops_per_chip"] = round(res["tflops_per_chip"], 2)
    if res.get("mfu") is not None:
        out["mfu"] = round(res["mfu"], 4)
    # Protocol keys so result lines are self-describing.
    out["protocol"] = {k: protocol[k] for k in
                       ("batch_size", "input_dtype", "num_batches_per_iter",
                        "num_iters")}
    # effective stem, not requested (non-resnet models ignore the knob)
    out["protocol"]["stem"] = res["stem"]
    return out


def _r101_bench():
    """Apples-to-apples datapoint: the reference's published absolute
    number IS ResNet-101 (1656.82 img/s on 16 P100s = 103.55/GPU,
    reference docs/benchmarks.rst:26-43).  BENCH_R101=0 skips."""
    if os.environ.get("BENCH_R101", "1") != "1":
        return None
    from horovod_tpu.benchmark import run_synthetic_benchmark
    r = run_synthetic_benchmark(
        model_name="resnet101",
        batch_size=int(os.environ.get("BENCH_R101_BATCH", "128")),
        num_warmup_batches=3,
        num_batches_per_iter=int(os.environ.get("BENCH_R101_BATCHES",
                                                "90")),
        num_iters=int(os.environ.get("BENCH_R101_ITERS", "3")),
        input_dtype=os.environ.get("BENCH_INPUT_DTYPE", "bfloat16"),
        verbose=os.environ.get("BENCH_VERBOSE", "0") == "1")
    v = r["img_sec_per_chip"]
    out = {"img_sec_per_chip": round(v, 2),
           "vs_baseline_apples_to_apples": round(
               v / BASELINE_IMG_SEC_PER_CHIP, 3)}
    if r.get("tflops_per_chip") is not None:
        out["tflops_per_chip"] = round(r["tflops_per_chip"], 2)
    if r.get("mfu") is not None:
        out["mfu"] = round(r["mfu"], 4)
    return out


def _lm_bench():
    """Compute-bound LM datapoint: d3072/L10/H24 (head 128), T=2048,
    batch 4 per chip, flash attention with auto blocks, bf16 momentum,
    data-parallel over every chip (docs/benchmarks.md has the sweep that
    chose it).  BENCH_LM=0 skips; knobs mirror the sweep's axes."""
    if os.environ.get("BENCH_LM", "1") != "1":
        return None
    from horovod_tpu.benchmark import run_lm_benchmark
    r = run_lm_benchmark(
        d_model=int(os.environ.get("BENCH_LM_D_MODEL", "3072")),
        n_layers=int(os.environ.get("BENCH_LM_LAYERS", "10")),
        n_heads=int(os.environ.get("BENCH_LM_HEADS", "24")),
        seq_len=int(os.environ.get("BENCH_LM_SEQ", "2048")),
        batch_size=int(os.environ.get("BENCH_LM_BATCH", "4")),
        attention=os.environ.get("BENCH_LM_ATTENTION", "flash"),
        remat=os.environ.get("BENCH_LM_REMAT", "none"),
        num_batches_per_iter=int(os.environ.get("BENCH_LM_BATCHES",
                                                "8")),
        num_iters=int(os.environ.get("BENCH_LM_ITERS", "3")),
        verbose=os.environ.get("BENCH_VERBOSE", "0") == "1")
    return {
        "tok_sec_per_chip": round(r["tok_sec_per_chip"], 1),
        "tflops_per_chip": round(r["tflops_per_chip"], 2),
        "mfu": round(r["mfu"], 4),
        "n_chips": r["n_chips"],
        "protocol": {k: r[k] for k in
                     ("d_model", "n_layers", "d_ff", "n_heads",
                      "vocab_size", "seq_len", "batch_size", "attention",
                      "remat")},
    }


def _eager_allreduce_bench():
    """Native eager-plane (TCP data plane) allreduce bandwidth, measured
    at bench time: 2 local ranks under the launcher, steady-state 64 MB
    allreduce.  A host metric: the ranks are pinned to the CPU platform
    (tools/bench_eager.py), since this process holds the chips.  The full
    size x fusion x hierarchical x autotune sweep lives in
    ``tools/bench_eager.py`` -> ``BENCH_eager.json``.  BENCH_EAGER=0
    skips."""
    if os.environ.get("BENCH_EAGER", "1") != "1":
        return None
    import importlib.util
    repo = os.path.dirname(os.path.abspath(__file__))
    spec = importlib.util.spec_from_file_location(
        "bench_eager", os.path.join(repo, "tools", "bench_eager.py"))
    be = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(be)
    r = be._run_config(
        "bench_smoke", 2,
        {"BENCH_EAGER_MODE": "large",
         "BENCH_EAGER_SIZES_MB":
             os.environ.get("BENCH_EAGER_SIZES_MB", "64")},
        timeout=300)
    row = r["rows"][0]
    return {"payload_mb": row["mb"],
            "busbw_gbs": row["busbw_gbs"],
            "np": r["np"],
            "note": ("loopback TCP, 2 local ranks on the CPU platform; "
                     "protocol+memory path, not a NIC")}


# The headline lane's keys land at the top level of the line, the others
# under their own name.
HEADLINE = "resnet50"
LANES = ((HEADLINE, _resnet50_bench), ("resnet101", _r101_bench),
         ("lm", _lm_bench), ("eager_allreduce", _eager_allreduce_bench))


def run_lanes(lanes, out):
    """Run each ``(name, fn)``; merge results into ``out``.  A lane that
    raises is recorded under ``out["errors"][name]`` with its traceback on
    stderr, and the others still run.  Returns the failed names."""
    failed = []
    for name, fn in lanes:
        try:
            res = fn()
        except Exception as e:
            traceback.print_exc()
            out.setdefault("errors", {})[name] = f"{type(e).__name__}: {e}"
            failed.append(name)
            continue
        if res is None:           # lane switched off
            continue
        if name == HEADLINE:
            out.update(res)
        else:
            out[name] = res
    return failed


def main() -> int:
    from horovod_tpu.benchmark import device_info
    device = device_info()
    if device["platform"] != "tpu":
        print(f"bench: JAX found platform {device['platform']!r} "
              f"({device['kind']}, {device['count']} device(s)), not 'tpu'; "
              f"a benchmark number is a chip run or it is not made",
              file=sys.stderr)
        return 2

    import horovod_tpu as hvd
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    hvd.init()
    out = {
        "metric": "resnet50_synthetic_img_sec_per_chip",
        "value": None,
        "unit": "img/sec/chip",
        "vs_baseline": None,
        "device": device,
    }
    failed = run_lanes(LANES, out)
    print(json.dumps(out))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
