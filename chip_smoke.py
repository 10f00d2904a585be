#!/usr/bin/env python
"""The quickest proof that the SPMD training path still starts on the chip.

    python chip_smoke.py                 # on a machine with TPU chips
    python chip_smoke.py --rehearse-cpu  # tiny sizes on the CPU; not a result

One process drives every chip JAX reports, through the entry points a user
calls: ``hvd.init()`` -> ``hvd.mesh()`` -> a jitted ``shard_map`` training
step from the two step builders the benchmark's cells use.  Phases:

``flash_kernel``  ``ops.flash_attention`` forward and backward at the LM's
                  attention shape, with and without ``segment_ids``, against
                  ``parallel.sequence.local_attention`` (one device).
``lm_dp``         the dense LM at the width of record (d3072/L10/H24/T2048,
                  batch 4 per chip, bf16 compute, f32 master weights, SGD
                  with a bf16 momentum slot, ``attention="flash"``), built
                  by ``models.transformer.make_train_step`` through the
                  state recipe ``benchmark.make_lm_bench_state``, one step
                  per call.
``resnet50_dp``   ResNet-50 at 224x224, batch 256 per chip, bf16 input,
                  ``stem="s2d"``: ``benchmark.make_bench_state`` +
                  ``benchmark.make_train_step``.
``lm_zero``       (more than one chip) the LM step with
                  ``shard_optimizer=True``; each device must hold about 1/N
                  of the optimizer state a replica holds whole.

Each training phase must compile, take its steps with a finite loss that
falls on the fixed batch, compile nothing after its first step, leave
``device.memory_stats()`` readable, and keep a shard of the batch and of
the output on every device of the mesh.  The lowered LM step must contain
the Mosaic custom call, so the flash kernel was compiled and not
interpreted or replaced.  Any phase that raises makes the exit code 1.

Without a TPU the script exits 2, naming the platform it found, and prints
no result.  On success the last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

import horovod_tpu as hvd
from horovod_tpu import benchmark
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel.sequence import local_attention
from horovod_tpu.utils.compile_cache import enable_compile_cache

# Keyword arguments of benchmark.make_lm_bench_state / make_bench_state;
# batch_size is per chip.
LM = dict(d_model=3072, n_layers=10, n_heads=24, d_ff=12288,
          vocab_size=32768, seq_len=2048, batch_size=4)
RESNET = dict(model_name="resnet50", batch_size=256, image_size=224)
# --rehearse-cpu: the same code at sizes the CPU and the Pallas
# interpreter finish in seconds.
LM_TINY = dict(d_model=256, n_layers=2, n_heads=2, d_ff=512,
               vocab_size=512, seq_len=256, batch_size=2)
RESNET_TINY = dict(model_name="resnet18", batch_size=2, image_size=32)
STEPS = 4

_backend_compiles = 0


def _count_compile(event, duration, **kwargs):
    global _backend_compiles
    if event == "/jax/core/compile/backend_compile_duration":
        _backend_compiles += 1


def _device_info():
    """The devices this check runs on, as JAX reports them."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def _peak_bytes():
    """Largest ``peak_bytes_in_use`` over the local devices (None where the
    backend reports no memory stats, as the CPU does).  The backend's peak
    is that of the process so far and cannot be reset, so a phase's figure
    includes the phases before it.  It leaves out the temporaries of every
    loaded program: a footprint is ``perfbench``'s ``peak_hbm_gib``, this
    only proves the stats are readable."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()]
    return None if None in peaks else max(peaks)


def _tree_bytes_per_device(tree):
    """Bytes one device holds for ``tree``: per leaf, the first addressable
    shard's size (a ``P()`` leaf contributes its full size, a ``P(ax)``
    leaf 1/N)."""
    return sum(leaf.addressable_shards[0].data.nbytes
               for leaf in jax.tree_util.tree_leaves(tree))


def _held_by(array):
    return {s.device for s in array.addressable_shards}


def _run_steps(name, mesh, compiled, state, batch, on_tpu):
    """Take STEPS steps of ``compiled(*state, *batch) -> (*state, loss)``;
    assert what the module docstring promises; return the final state."""
    devices = set(mesh.devices.ravel())
    for arr in batch:
        assert _held_by(arr) == devices, (
            f"{name}: batch shards on {len(_held_by(arr))} of "
            f"{len(devices)} devices")
    losses, secs = [], []
    compiles_after_first = 0
    for i in range(STEPS):
        before = _backend_compiles
        t0 = time.perf_counter()
        *state, loss = compiled(*state, *batch)
        t_dispatch = time.perf_counter() - t0
        jax.block_until_ready(loss)
        t_block = time.perf_counter() - t0
        losses.append(float(np.asarray(loss)))
        t_fetch = time.perf_counter() - t0 - t_block
        secs.append(t_block)
        if i:
            compiles_after_first += _backend_compiles - before
        print(f"  step {i}: {t_block:.4f}s (dispatch returned after "
              f"{t_dispatch:.4f}s, scalar fetch after block_until_ready "
              f"{t_fetch:.4f}s) loss {losses[-1]:.5f}", flush=True)
    # ROADMAP S10's two facts: the cost of one dispatched step, and
    # whether block_until_ready is a fence (the fetch that follows it
    # then has nothing left to wait for).
    print(f"  block_until_ready fences the step: "
          f"{t_fetch < 0.05 * t_block} (last fetch {t_fetch:.4f}s of a "
          f"{t_block:.4f}s step)", flush=True)
    assert all(np.isfinite(losses)), f"{name}: non-finite loss {losses}"
    assert losses[-1] < losses[0], f"{name}: loss did not fall: {losses}"
    assert compiles_after_first == 0, (
        f"{name}: {compiles_after_first} compilations after the first step")
    for leaf in jax.tree_util.tree_leaves((state, loss)):
        assert _held_by(leaf) == devices, (
            f"{name}: an output lives on {len(_held_by(leaf))} of "
            f"{len(devices)} devices")
    peak = _peak_bytes()
    assert peak is not None or not on_tpu, \
        f"{name}: device.memory_stats() unreadable"
    print(f"  steps {[round(s, 4) for s in secs]} s; peak bytes in use "
          f"(process so far) {peak}; compilations after the first step "
          f"{compiles_after_first}", flush=True)
    return state


def _compile(name, step, args, on_tpu, want_kernel=False):
    t0 = time.perf_counter()
    lowered = step.lower(*args)
    t_lower = time.perf_counter() - t0
    if want_kernel:
        has_kernel = "tpu_custom_call" in lowered.as_text()
        print(f"  lowered step contains tpu_custom_call: {has_kernel}",
              flush=True)
        assert has_kernel or not on_tpu, (
            f"{name}: no Mosaic custom call in the lowered step — the "
            f"flash kernel was interpreted or replaced")
    t0 = time.perf_counter()
    compiled = lowered.compile()
    print(f"  lower {t_lower:.1f}s, compile {time.perf_counter() - t0:.1f}s "
          f"(the compile is what a warm persistent cache saves)",
          flush=True)
    return compiled


def phase_flash_kernel(cfg, on_tpu):
    b, t = cfg["batch_size"], cfg["seq_len"]
    h = cfg["n_heads"]
    d = cfg["d_model"] // h
    rng = np.random.default_rng(0)
    q, k, v, do = (jnp.asarray(rng.standard_normal((b, t, h, d)) * 0.5,
                               jnp.bfloat16) for _ in range(4))
    # Four packed documents of uneven length.
    cuts = [0, t // 8, t // 2, t // 2 + 3 * (t // 16), t]
    ids = np.zeros((b, t), np.int32)
    for i in range(4):
        ids[:, cuts[i]:cuts[i + 1]] = i

    def fwd_and_grads(attn):
        def f(q, k, v, do):
            out, pull = jax.vjp(attn, q, k, v)
            return (out,) + pull(do)
        return jax.jit(f)

    for seg in (None, jnp.asarray(ids)):
        label = "segment_ids" if seg is not None else "causal only"
        kernel = fwd_and_grads(
            lambda q, k, v: flash_attention(q, k, v, True, segment_ids=seg))
        ref = fwd_and_grads(
            lambda q, k, v: local_attention(q, k, v, causal=True,
                                            segment_ids=seg))
        t0 = time.perf_counter()
        lowered = kernel.lower(q, k, v, do)
        n_calls = lowered.as_text().count("tpu_custom_call")
        assert n_calls >= 3 or not on_tpu, (
            f"flash_kernel ({label}): {n_calls} Mosaic custom calls in "
            f"the lowered forward+backward, expected 3 (fwd, dq, dkv)")
        got = lowered.compile()(q, k, v, do)
        print(f"  {label}: B*H={b * h} T={t} D={d} bf16, "
              f"{n_calls} tpu_custom_call, compile+run "
              f"{time.perf_counter() - t0:.1f}s", flush=True)
        want = ref(q, k, v, do)
        for what, g, w in zip(("out", "dq", "dk", "dv"), got, want):
            g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
            assert np.isfinite(g).all(), f"flash_kernel: {what} not finite"
            err = float(np.abs(g - w).max())
            # bf16 tolerance (docs/kernels.md): two units in the last
            # place at the reference's largest magnitude.
            tol = 2.0 ** -6 * max(1.0, float(np.abs(w).max()))
            print(f"    {what}: max|kernel - local_attention| {err:.4g} "
                  f"(tolerance {tol:.4g})", flush=True)
            assert err <= tol, (
                f"flash_kernel ({label}): {what} off by {err} > {tol}")


def phase_lm(name, cfg, mesh, on_tpu, shard_optimizer):
    n = mesh.devices.size
    mesh, _, step, state, batch = benchmark.make_lm_bench_state(
        attention="flash", remat="none", steps_per_call=1, mesh=mesh,
        shard_optimizer=shard_optimizer, **cfg)
    opt_state = state[1]
    per_device = _tree_bytes_per_device(opt_state)
    whole = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(opt_state))
    print(f"  LM d{cfg['d_model']}/L{cfg['n_layers']}/H{cfg['n_heads']}/"
          f"T{cfg['seq_len']} batch {cfg['batch_size']} per chip x {n}, "
          f"attention=flash, shard_optimizer={shard_optimizer}; optimizer "
          f"state {per_device} bytes per device of {whole} "
          f"({per_device / whole:.3f})", flush=True)
    if shard_optimizer:
        # Each device holds 1/N of the state a replica would hold whole;
        # bucket padding to a multiple of N is all that may be added.
        assert 1 / n <= per_device / whole < 1.1 / n, (
            f"{name}: each device holds {per_device / whole:.3f} of the "
            f"optimizer state, expected about 1/{n}")
    compiled = _compile(name, step, state + batch, on_tpu, want_kernel=True)
    _run_steps(name, mesh, compiled, state, batch, on_tpu)


def phase_resnet50_dp(cfg, mesh, on_tpu):
    (mesh, ax, model, optimizer, _s2d, state, batch) = \
        benchmark.make_bench_state(input_dtype="bfloat16", stem="s2d",
                                   mesh=mesh, **cfg)
    step = benchmark.make_train_step(model, optimizer, mesh, ax,
                                     steps_per_call=1)
    print(f"  {cfg['model_name']} {cfg['image_size']}x{cfg['image_size']} "
          f"batch {cfg['batch_size']} per chip x {mesh.devices.size}, bf16 "
          f"input, stem=s2d", flush=True)
    compiled = _compile("resnet50_dp", step, state + batch, on_tpu)
    _run_steps("resnet50_dp", mesh, compiled, state, batch, on_tpu)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--rehearse-cpu", action="store_true",
        help="run the same phases at tiny sizes on whatever JAX finds "
             "(the CPU); prints that it is a rehearsal, never a result")
    args = parser.parse_args(argv)

    device = _device_info()
    print(f"device: platform={device['platform']} "
          f"device_kind={device['kind']} count={device['count']}",
          flush=True)
    on_tpu = device["platform"] == "tpu"
    if args.rehearse_cpu:
        print("REHEARSAL at tiny sizes: exercises the code path only; "
              "nothing printed below is a chip result", flush=True)
        lm_cfg, resnet_cfg = LM_TINY, RESNET_TINY
    elif not on_tpu:
        print(f"chip_smoke: JAX found platform {device['platform']!r} "
              f"({device['kind']}, {device['count']} device(s)), not 'tpu'; "
              f"this check runs on the chip only", file=sys.stderr)
        return 2
    else:
        lm_cfg, resnet_cfg = LM, RESNET

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    jax.monitoring.register_event_duration_secs_listener(_count_compile)
    hvd.init()
    mesh = hvd.mesh()            # 1-D ("data",) over every device
    assert mesh.devices.size == device["count"], mesh

    phases = [
        ("flash_kernel", lambda: phase_flash_kernel(lm_cfg, on_tpu)),
        ("lm_dp", lambda: phase_lm("lm_dp", lm_cfg, mesh, on_tpu, False)),
        ("resnet50_dp", lambda: phase_resnet50_dp(resnet_cfg, mesh, on_tpu)),
    ]
    if device["count"] > 1:
        phases.append(
            ("lm_zero", lambda: phase_lm("lm_zero", lm_cfg, mesh, on_tpu,
                                         True)))

    failed = []
    for name, run in phases:
        print(f"phase {name}:", flush=True)
        t0 = time.perf_counter()
        try:
            run()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        print(f"phase {name}: {'FAILED' if name in failed else 'ok'} in "
              f"{time.perf_counter() - t0:.1f}s", flush=True)

    result = {"ok": not failed, "device": device}
    if failed:
        result["failed"] = failed
    if args.rehearse_cpu:
        result["rehearsal"] = True
    print(json.dumps(result), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
