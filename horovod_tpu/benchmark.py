"""Synthetic training benchmark — the measurement harness of record.

Faithful to the reference harness (``examples/tensorflow2_synthetic_benchmark.py``:
synthetic fixed batch, ``--num-warmup-batches`` then ``num_iters`` rounds of
``num_batches_per_iter`` steps, img/sec mean ± 1.96σ over rounds,
``:86-132``), rebuilt as one jitted SPMD program over the device mesh.

The whole Horovod DP recipe — shard the batch over chips, replicate
parameters, allreduce (fused ``pmean``) gradients, identical update — is a
single XLA program here; the gradient averaging that the reference performs
with its background thread + NCCL rings lowers to ICI collectives that XLA
overlaps with backprop compute.
"""

from __future__ import annotations

import os
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.ops.fusion import fused_pytree_mean
from horovod_tpu.telemetry import scopes
from horovod_tpu.topology import build_mesh, data_axis, mesh_size

# Peak dense bf16 FLOP/s per chip by device kind (public TPU spec sheet
# numbers), for MFU accounting.  An accelerator that is not listed is an
# error (device_peak_tflops), never a default.
PEAK_TFLOPS_BY_KIND = {
    "TPU v2": 45.0,
    "TPU v3": 123.0,
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,   # v5e
    "TPU v5e": 197.0,
    "TPU v5": 459.0,        # v5p
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,   # v6e (Trillium)
    "TPU v6e": 918.0,
}

# Forward-pass GFLOPs per 224x224 image (standard analytic counts, 2 FLOPs
# per MAC); training step ~= 3x forward.  Used where XLA's cost analysis
# reports no FLOPs for the module, and as the scan-multiplication guard.
_FWD_GFLOPS_224 = {
    "resnet18": 1.82, "resnet34": 3.67, "resnet50": 4.09,
    "resnet101": 7.80, "resnet152": 11.52,
    # VGG-BN conv stacks (GAP head; the convs are >99% of FLOPs).
    "vgg11": 7.6, "vgg13": 11.3, "vgg16": 15.5, "vgg19": 19.6,
    # Inception V3 is 5.7 GFLOPs at its canonical 299x299 => ~3.2 at 224
    # under the quadratic spatial scaling the fallback applies.
    "inception3": 3.2, "inceptionv3": 3.2,
}


def device_peak_tflops(device) -> Optional[float]:
    """Peak bf16 TFLOP/s of `device`.  None on the CPU platform only
    (MFU is not meaningful there); an accelerator whose ``device_kind``
    is not in :data:`PEAK_TFLOPS_BY_KIND` is an error, so a utilization
    is never quietly left out or computed against a guess."""
    if device.platform == "cpu":
        return None
    kind = device.device_kind
    for prefix, peak in sorted(PEAK_TFLOPS_BY_KIND.items(),
                               key=lambda kv: -len(kv[0])):
        if kind.startswith(prefix):
            return peak
    raise ValueError(
        f"no peak FLOP/s on record for device_kind {kind!r} (platform "
        f"{device.platform!r}); add it to PEAK_TFLOPS_BY_KIND with its "
        f"source")


def device_info(devices=None) -> dict:
    """The devices a result was measured on, as JAX reports them; every
    result dict carries this under ``"device"``."""
    devices = jax.devices() if devices is None else list(devices)
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def _step_flops(compiled, model_name: str, global_bs: int,
                image_size: int, n_chips: int) -> Optional[float]:
    """GLOBAL FLOPs of one training step.

    XLA's cost analysis reports the PER-DEVICE SPMD module (verified: an
    8-way-sharded program reports 1/8 of the single-device figure), so the
    count is scaled by n_chips; the analytic fallback is global already.
    ``compiled=None`` requests the analytic estimate directly."""
    if compiled is not None:
        flops = float(compiled.cost_analysis().get("flops", 0.0))
        if flops > 0:
            return flops * n_chips
    fwd = _FWD_GFLOPS_224.get(model_name)
    if fwd is None:
        return None
    scale = (image_size / 224.0) ** 2
    return 3.0 * fwd * 1e9 * scale * global_bs


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


def run_synthetic_benchmark(model_name: str = "resnet50",
                            batch_size: int = 64,
                            image_size: int = 224,
                            num_classes: int = 1000,
                            num_warmup_batches: int = 5,
                            num_batches_per_iter: int = 10,
                            num_iters: int = 10,
                            learning_rate: float = 0.01,
                            mesh=None,
                            per_step_dispatch: bool = False,
                            input_dtype: str = "float32",
                            stem: str = "conv7",
                            remat: Optional[str] = None,
                            verbose: bool = True) -> dict:
    """Run the ResNet synthetic benchmark; returns a result dict.

    ``batch_size`` is per chip, as in the reference (``--batch-size`` is per
    worker, ``tensorflow2_synthetic_benchmark.py:20``).
    """
    (mesh, ax, model, optimizer, s2d,
     (params, batch_stats, opt_state),
     (images, labels)) = make_bench_state(
        model_name, batch_size, image_size=image_size,
        num_classes=num_classes, input_dtype=input_dtype, stem=stem,
        remat=remat, mesh=mesh, learning_rate=learning_rate)
    n_chips = mesh_size(mesh)
    global_bs = batch_size * n_chips

    # Fused dispatch (default): each timed round is ONE compiled program
    # of num_batches_per_iter scanned steps, so host->device dispatch
    # latency is paid once per round, not once per step (see
    # make_train_step; unverified on the present machine, ROADMAP S10).
    # ``per_step_dispatch`` restores the reference's per-step dispatch
    # shape for comparison.
    steps_per_call = 1 if per_step_dispatch else max(num_batches_per_iter,
                                                     1)
    step = make_train_step(model, optimizer, mesh, ax,
                           steps_per_call=steps_per_call)

    # AOT-compile and execute through the compiled object: one compile
    # (shapes are fixed for the whole run), and XLA's own FLOP count comes
    # with it for MFU accounting.  This backend's cost analysis counts a
    # scan body ONCE (verified: the scanned module reports the same flops
    # as a single step), so the module figure already IS per-step; guard
    # against an XLA that multiplies by trip count by comparing with the
    # analytic estimate.
    compiled = step.lower(params, batch_stats, opt_state, images,
                          labels).compile()
    flops_per_step = _step_flops(compiled, model_name, global_bs,
                                 image_size, n_chips)
    analytic = _step_flops(None, model_name, global_bs, image_size,
                           n_chips)
    if (flops_per_step and analytic and steps_per_call > 1 and
            flops_per_step > 2.5 * analytic):
        flops_per_step /= steps_per_call
    if flops_per_step and s2d:
        # XLA counts the 45 structurally-zero tap-channels of the
        # reparameterized 4x4x(4*3) stem (conv7_to_s2d_weights zeroes
        # them) as FLOPs; subtract so MFU stays comparable with the
        # conv7 stem (fwd+bwd(dX)+bwd(dW) ~= 3x fwd).
        out_hw = (image_size // 2) ** 2
        flops_per_step -= 3 * 2 * global_bs * out_hw * 45 * 64
    step = compiled

    if verbose:
        print(f"Model: {model_name}", flush=True)
        print(f"Batch size: {batch_size} per chip, {global_bs} global "
              f"({n_chips} chips)", flush=True)

    # Sync point: a tiny scalar D2H transfer of the loss (the loss of
    # step N depends on every prior step's params, so it fences the whole
    # round).  Chosen when `block_until_ready` was seen to return early
    # on an earlier set-up; unverified on the present machine, where
    # chip_smoke.py reports whether block_until_ready fences (ROADMAP
    # S10).
    # Fused mode rounds warmup UP to whole calls; 0 stays 0 (the timed
    # loop runs the already-compiled object either way).
    warmup_calls = (num_warmup_batches if steps_per_call == 1 else
                    -(-num_warmup_batches // steps_per_call))
    for _ in range(warmup_calls):
        params, batch_stats, opt_state, loss = step(
            params, batch_stats, opt_state, images, labels)
    if warmup_calls > 0:
        float(np.asarray(loss))

    calls_per_iter = (num_batches_per_iter if steps_per_call == 1 else 1)
    img_secs = []
    for i in range(num_iters):
        t0 = time.perf_counter()
        for _ in range(calls_per_iter):
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, images, labels)
        float(np.asarray(loss))
        dt = time.perf_counter() - t0
        img_sec = global_bs * num_batches_per_iter / dt
        img_secs.append(img_sec)
        if verbose:
            print(f"Iter #{i}: {img_sec:.1f} img/sec total", flush=True)

    img_sec_mean = float(np.mean(img_secs))
    img_sec_conf = float(1.96 * np.std(img_secs))

    # Achieved TFLOP/s + MFU (BASELINE.md asks for utilization, not just
    # throughput: 2260 img/sec that is 10% MFU is unfinished work).
    tflops_per_chip = None
    mfu = None
    if flops_per_step:
        steps_per_sec = img_sec_mean / global_bs
        tflops_per_chip = flops_per_step * steps_per_sec / n_chips / 1e12
        peak = device_peak_tflops(mesh.devices.ravel()[0])
        if peak:
            mfu = tflops_per_chip / peak

    if verbose:
        print(f"Img/sec per chip: {img_sec_mean / n_chips:.1f} "
              f"+-{img_sec_conf / n_chips:.1f}", flush=True)
        print(f"Total img/sec on {n_chips} chip(s): "
              f"{img_sec_mean:.1f} +-{img_sec_conf:.1f}", flush=True)
        if tflops_per_chip is not None:
            mfu_s = f", MFU {mfu * 100:.1f}%" if mfu is not None else ""
            print(f"Achieved {tflops_per_chip:.1f} TFLOP/s per chip"
                  f"{mfu_s}", flush=True)
    return {
        "model": model_name,
        "device": device_info(mesh.devices.ravel()),
        "batch_size_per_chip": batch_size,
        "stem": stem if s2d else "conv7",
        "n_chips": n_chips,
        "img_sec_total": img_sec_mean,
        "img_sec_conf": img_sec_conf,
        "img_sec_per_chip": img_sec_mean / n_chips,
        "flops_per_step": flops_per_step,
        "tflops_per_chip": tflops_per_chip,
        "mfu": mfu,
        "loss": float(np.asarray(loss)),
    }


def _device_memory_report(verbose: bool = True) -> list:
    """Per-device live/peak HBM bytes from ``device.memory_stats()``.

    The PJRT CPU backend reports no memory stats — entries carry ``None``
    there (the benchmark still runs; only the numbers are TPU-only)."""
    rows = []
    for d in jax.local_devices():
        ms = d.memory_stats() or {}
        rows.append({
            "device": str(d),
            "bytes_in_use": ms.get("bytes_in_use"),
            "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
        })
    if verbose:
        for r in rows:
            if r["bytes_in_use"] is None:
                print(f"  {r['device']}: memory_stats unavailable "
                      f"(CPU backend)", flush=True)
            else:
                peak = r["peak_bytes_in_use"]
                peak_s = (f", peak {peak / 2**20:,.1f} MiB"
                          if peak is not None else "")
                print(f"  {r['device']}: live "
                      f"{r['bytes_in_use'] / 2**20:,.1f} MiB{peak_s}",
                      flush=True)
    return rows


def _tree_bytes_per_device(tree) -> Optional[int]:
    """Bytes one device holds for ``tree``: per-leaf, the first addressable
    shard's size (a ``P()`` leaf contributes its full size, a ``P(ax)``
    leaf 1/N — exactly the ZeRO memory story the benchmark reports)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        try:
            total += leaf.addressable_shards[0].data.nbytes
        except (AttributeError, IndexError):
            try:
                total += leaf.nbytes
            except AttributeError:
                return None
    return total


def lm_train_flops(cfg, global_bs: int) -> float:
    """Analytic GLOBAL FLOPs of one LM training step — the standard MFU
    accounting (PaLM appendix-B convention): ``6·N·tokens`` for every
    matmul parameter (2 fwd + 4 bwd FLOPs per param per token; embedding
    LOOKUP excluded, tied logits head included) plus causal attention
    ``6·B·T²·d·L`` (QKᵀ and PV are 4·B·T²·d per layer fwd, 3x for
    train, halved by causality).  Rematerialization recompute is NOT
    counted (MFU counts model FLOPs, not hardware FLOPs)."""
    d, f, v = cfg.d_model, cfg.d_ff, cfg.vocab_size
    l, t = cfg.n_layers, cfg.max_seq
    n_matmul = l * (4 * d * d + 2 * d * f) + d * v
    tokens = global_bs * t
    return 6.0 * n_matmul * tokens + 6.0 * global_bs * t * t * d * l


def make_lm_bench_state(d_model: int, n_layers: int, n_heads: int,
                        d_ff: int, vocab_size: int, seq_len: int,
                        batch_size: int, attention: str = "flash",
                        remat: str = "none", steps_per_call: int = 1,
                        learning_rate: float = 1e-4, mesh=None,
                        shard_optimizer: bool = False,
                        compression: Optional[str] = None):
    """The ONE LM benchmark-state recipe (the LM twin of
    :func:`make_bench_state`), shared by :func:`run_lm_benchmark` and
    ``chip_smoke.py`` so they always build the same program.  Returns
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

    # SGD+momentum (the ResNet harness's optimizer): one slot per param —
    # adam's two would displace ~4 GB of batch/activations at the
    # compute-bound sizes this harness exists to measure.  BENCH_LM
    # protocol keeps the slot bf16 (halves optimizer HBM so batch 8 fits
    # at d4096; fp32 master weights unchanged).
    acc_dtype = os.environ.get("BENCH_LM_MOMENTUM_DTYPE", "bfloat16")
    optimizer = optax.sgd(learning_rate, momentum=0.9,
                          accumulator_dtype=jnp.dtype(acc_dtype).type
                          if acc_dtype != "float32" else None)
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


def run_lm_benchmark(d_model: int = 2048, n_layers: int = 8,
                     n_heads: int = 16, d_ff: Optional[int] = None,
                     vocab_size: int = 32768, seq_len: int = 2048,
                     batch_size: int = 8,
                     attention: str = "flash", remat: str = "none",
                     num_warmup_batches: int = 2,
                     num_batches_per_iter: int = 8, num_iters: int = 5,
                     learning_rate: float = 1e-4, mesh=None,
                     shard_optimizer: bool = False,
                     compression: Optional[str] = None,
                     verbose: bool = True) -> dict:
    """Transformer-LM synthetic training benchmark, data-parallel over
    every device by default — the compute-bound counterpart to the
    ResNet harness: same protocol (fixed synthetic batch of
    ``batch_size`` per chip, scanned rounds, loss-fetch sync barrier),
    flash attention + optional remat, fp32 master weights with bf16
    matmuls on every platform.

    MFU here uses the ANALYTIC model-FLOPs count (:func:`lm_train_flops`)
    — XLA's cost analysis cannot see inside the Pallas flash kernel, and
    counting remat recompute would inflate the number; the dict carries
    the raw cost-analysis figure too so the two can be compared.

    ``shard_optimizer=True`` runs the ZeRO-1 sharded-update lane
    (:mod:`horovod_tpu.parallel.zero`) and reports per-device
    live-memory bytes next to MFU, since memory headroom is half the
    point of sharding the optimizer state.  ``compression`` selects a
    gradient wire codec (``"none"``, ``"bf16"``, ``"fp16"``, ``"int8"``,
    ``"powersgd[:rank]"``) riding that wire — see
    :mod:`horovod_tpu.ops.compression`."""
    steps_per_call = max(num_batches_per_iter, 1)
    mesh, cfg, step, (params, opt_state), (tokens, labels) = \
        make_lm_bench_state(
            d_model, n_layers, n_heads, d_ff or 4 * d_model, vocab_size,
            seq_len, batch_size, attention=attention, remat=remat,
            steps_per_call=steps_per_call, learning_rate=learning_rate,
            mesh=mesh, shard_optimizer=shard_optimizer,
            compression=compression)
    n_chips = mesh_size(mesh)
    global_bs = batch_size * n_chips

    flops_per_step = lm_train_flops(cfg, global_bs)
    compiled = step.lower(params, opt_state, tokens, labels).compile()
    xla_flops = (float(compiled.cost_analysis().get("flops", 0.0))
                 * n_chips or None)
    step = compiled

    if verbose:
        comp_s = f" compression={compression}" if compression else ""
        print(f"LM: d_model={d_model} n_layers={n_layers} d_ff="
              f"{cfg.d_ff} vocab={vocab_size} T={seq_len} "
              f"batch={global_bs} attention={attention} remat={remat} "
              f"shard_optimizer={shard_optimizer}{comp_s} "
              f"chips={n_chips}", flush=True)
        print(f"Analytic {flops_per_step / 1e12:.2f} TFLOP/step "
              f"({flops_per_step / (global_bs * seq_len) / 1e6:.1f} "
              f"MFLOP/token)", flush=True)

    # Same sync protocol as the ResNet harness: the loss scalar fetch is
    # the barrier (see run_synthetic_benchmark).
    for _ in range(max(1, -(-num_warmup_batches // steps_per_call))):
        params, opt_state, loss = step(params, opt_state, tokens, labels)
    float(np.asarray(loss))

    tok_secs = []
    for i in range(num_iters):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, tokens, labels)
        float(np.asarray(loss))
        dt = time.perf_counter() - t0
        tok_sec = global_bs * seq_len * steps_per_call / dt
        tok_secs.append(tok_sec)
        if verbose:
            print(f"Iter #{i}: {tok_sec:,.0f} tok/sec", flush=True)

    tok_sec_mean = float(np.mean(tok_secs))
    steps_per_sec = tok_sec_mean / (global_bs * seq_len)
    tflops_per_chip = flops_per_step * steps_per_sec / n_chips / 1e12
    peak = device_peak_tflops(mesh.devices.ravel()[0])
    mfu = tflops_per_chip / peak if peak else None
    opt_bytes = _tree_bytes_per_device(opt_state)
    if verbose:
        mfu_s = f", MFU {mfu * 100:.1f}%" if mfu is not None else ""
        print(f"{tok_sec_mean / n_chips:,.0f} tok/sec/chip, "
              f"{tflops_per_chip:.1f} TFLOP/s per chip{mfu_s}",
              flush=True)
        if opt_bytes is not None:
            print(f"Optimizer state per device: {opt_bytes / 2**20:,.1f} "
                  f"MiB" + (" (ZeRO-1 sharded 1/%d)" % n_chips
                            if shard_optimizer else " (replicated)"),
                  flush=True)
        print("Per-device memory:", flush=True)
    memory = _device_memory_report(verbose=verbose)
    return {
        "device": device_info(mesh.devices.ravel()),
        "d_model": d_model, "n_layers": n_layers, "d_ff": cfg.d_ff,
        "n_heads": n_heads, "vocab_size": vocab_size,
        "seq_len": seq_len, "batch_size": global_bs,
        "attention": attention, "remat": remat,
        "shard_optimizer": shard_optimizer,
        "compression": compression, "n_chips": n_chips,
        "tok_sec_per_chip": tok_sec_mean / n_chips,
        "tok_sec_conf": float(1.96 * np.std(tok_secs)) / n_chips,
        "flops_per_step_analytic": flops_per_step,
        "flops_per_step_xla": xla_flops,
        "tflops_per_chip": tflops_per_chip,
        "mfu": mfu,
        "opt_state_bytes_per_device": opt_bytes,
        "memory": memory,
        "loss": float(np.asarray(loss)),
    }


def run_decode_benchmark(d_model: int = 2048, n_layers: int = 8,
                         n_heads: int = 16, vocab_size: int = 32768,
                         batch_size: int = 8, prompt_len: int = 16,
                         total_len: int = 512, num_iters: int = 3,
                         verbose: bool = True) -> dict:
    """Greedy-decode (KV-cache) throughput: new tokens/sec and ms/step.

    Decode is HBM-bandwidth-bound (every step reads the full weight
    set); the scanned ``generate`` loop compiles to one program, so the
    measured ms/step is the device cost.  bf16 on every platform; runs on
    the first device only, and the result says so."""
    from horovod_tpu.models import transformer as tfm

    if prompt_len >= total_len:
        raise ValueError(f"prompt_len ({prompt_len}) must be < "
                         f"total_len ({total_len}) to decode anything")
    cfg = tfm.TransformerConfig(
        vocab_size=vocab_size, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, d_ff=4 * d_model, max_seq=total_len,
        dtype=jnp.bfloat16)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, vocab_size, (batch_size, prompt_len)), jnp.int32)
    gen = jax.jit(lambda p, pr: tfm.generate(p, pr, total_len, cfg))
    out = gen(params, prompt)
    int(np.asarray(out)[0, -1])           # sync barrier (scalar fetch)
    t0 = time.perf_counter()
    for _ in range(num_iters):
        out = gen(params, prompt)
    int(np.asarray(out)[0, -1])
    dt = (time.perf_counter() - t0) / num_iters
    new_tokens = batch_size * (total_len - prompt_len)
    # generate's scan runs total_len - 1 decode steps (prompt positions
    # are teacher-forced but still stepped); per-step latency divides
    # by the STEPS, tok/s by the NEW tokens.
    res = {
        "device": device_info(jax.devices()[:1]),
        "d_model": d_model, "n_layers": n_layers,
        "batch_size": batch_size, "total_len": total_len,
        "decode_tok_sec": new_tokens / dt,
        "ms_per_step": dt / (total_len - 1) * 1e3,
    }
    if verbose:
        print(f"decode d{d_model} L{n_layers} B{batch_size}: "
              f"{res['decode_tok_sec']:,.0f} tok/s, "
              f"{res['ms_per_step']:.2f} ms/step", flush=True)
    return res


def run_scaling_efficiency(model_name: str = "resnet50",
                           batch_size: int = 64,
                           n_devices: Optional[int] = None,
                           verbose: bool = True,
                           **bench_kwargs) -> dict:
    """Weak-scaling efficiency: img_sec_N / (N * img_sec_1).

    The reference's headline metric (README.rst:75 — 90% on 512 GPUs,
    measured by the same synthetic harness).  Per-chip batch is fixed
    (weak scaling), so perfect scaling doubles total img/sec per doubling
    of chips.  On a single-chip host this runs over the virtual CPU mesh —
    the efficiency *plumbing* is identical; real numbers need real chips.
    """
    # init() first: on multi-host it runs jax.distributed.initialize, which
    # must precede any backend-initializing call like jax.devices().
    if not hvd.is_initialized():
        hvd.init()
    devices = list(jax.devices())
    n = n_devices or len(devices)
    if n < 2:
        raise ValueError(f"scaling efficiency needs >= 2 devices, have {n}")

    # Baseline mesh: the FIRST device of every process.  On a single host
    # that is one device; on a multi-host pod every process keeps an
    # addressable device in the baseline mesh (a devices[:1] mesh would
    # strand the other hosts — jax.device_put rejects shardings with no
    # local device).  Efficiency is then img_sec_n / (growth * img_sec_base)
    # where growth = n / len(baseline): weak scaling from one chip per host
    # to all chips per host.
    by_process: dict = {}
    for d in devices[:n]:
        by_process.setdefault(getattr(d, "process_index", 0), d)
    base_devices = [by_process[k] for k in sorted(by_process)]
    n_base = len(base_devices)
    if n_base >= n:
        raise ValueError(
            f"scaling efficiency needs more total devices ({n}) than "
            f"baseline devices ({n_base}; one per process)")

    mesh_1 = build_mesh(axes=("data",), shape=(n_base,),
                        devices=base_devices)
    mesh_n = build_mesh(axes=("data",), shape=(n,), devices=devices[:n])

    res_1 = run_synthetic_benchmark(model_name, batch_size, mesh=mesh_1,
                                    verbose=False, **bench_kwargs)
    res_n = run_synthetic_benchmark(model_name, batch_size, mesh=mesh_n,
                                    verbose=False, **bench_kwargs)

    growth = n / n_base
    efficiency = res_n["img_sec_total"] / (growth * res_1["img_sec_total"])
    if verbose:
        print(f"{n_base} device(s): {res_1['img_sec_total']:.1f} img/sec",
              flush=True)
        print(f"{n} devices: {res_n['img_sec_total']:.1f} img/sec "
              f"(perfect: {growth * res_1['img_sec_total']:.1f})", flush=True)
        print(f"Scaling efficiency: {efficiency * 100:.1f}%", flush=True)
    return {
        "model": model_name,
        "n_devices": n,
        "n_baseline_devices": n_base,
        "img_sec_1": res_1["img_sec_total"],
        "img_sec_n": res_n["img_sec_total"],
        "scaling_efficiency": efficiency,
    }


def run_step_guard_benchmark(model_name: str = "resnet50",
                             batch_size: int = 64,
                             verbose: bool = True,
                             **kwargs) -> dict:
    """Measure the step-guard overhead (docs/fault_tolerance.md): run the
    synthetic benchmark twice — once with ``HOROVOD_STEP_GUARD`` unset
    (baseline) and once with policy ``skip`` (the in-graph finiteness
    psum + per-leaf select compiled into the step) — and report the
    throughput delta.  The policy is read at trace time, so each run
    builds and compiles a fresh step.  Target: < 2% step time.

    Prints one BENCH JSON line
    (``{"metric": "step_guard_overhead_pct", ...}``) and returns the same
    dict."""
    import json

    prev = os.environ.pop("HOROVOD_STEP_GUARD", None)
    try:
        base = run_synthetic_benchmark(model_name, batch_size,
                                       verbose=False, **kwargs)
        os.environ["HOROVOD_STEP_GUARD"] = "skip"
        guarded = run_synthetic_benchmark(model_name, batch_size,
                                          verbose=False, **kwargs)
    finally:
        if prev is None:
            os.environ.pop("HOROVOD_STEP_GUARD", None)
        else:
            os.environ["HOROVOD_STEP_GUARD"] = prev
    overhead_pct = ((base["img_sec_total"] - guarded["img_sec_total"])
                    / base["img_sec_total"] * 100.0)
    result = {
        "metric": "step_guard_overhead_pct",
        "value": round(overhead_pct, 3),
        "unit": "%",
        "target_pct": 2.0,
        "model": model_name,
        "baseline_img_sec": round(base["img_sec_total"], 1),
        "guarded_img_sec": round(guarded["img_sec_total"], 1),
    }
    if verbose:
        print(f"Step guard overhead: {overhead_pct:.2f}% "
              f"({base['img_sec_total']:.1f} -> "
              f"{guarded['img_sec_total']:.1f} img/sec; target < 2%)",
              flush=True)
    print("BENCH " + json.dumps(result), flush=True)
    return result


def run_compression_benchmark(codec: str = "int8", verbose: bool = True,
                              **lm_kwargs) -> dict:
    """Gradient-compression A/B on the LM ZeRO lane (docs/performance.md):
    run :func:`run_lm_benchmark` twice from identical seeds — once with
    the uncompressed wire (``compression="none"``) and once with
    ``codec`` — and report the loss delta at equal steps next to the
    logical wire-byte ratio from ``hvd_collective_bytes_total``
    (reduce-scatter + all-gather planes, diffed per run so repeated
    invocations don't pollute each other).

    The bytes counters are recorded at trace time, so the ratio is the
    codec's logical transport saving, independent of host speed; the
    loss delta is the error-feedback quality gate (target < 1%).

    Prints one BENCH JSON line
    (``{"metric": "compression_wire_ratio", ...}``) and returns the same
    dict."""
    import json

    from horovod_tpu import telemetry
    from horovod_tpu.ops import compression as compression_mod
    from horovod_tpu.telemetry import aggregate

    name = compression_mod.resolve_codec(codec).name
    if name == "none":
        raise ValueError(
            "--compression needs a real codec (bf16, fp16, int8, "
            "powersgd[:rank]); the lane already compares against 'none'")
    # The codec rides the ZeRO reduce-scatter wire; force the sharded
    # lane regardless of what the caller passed.
    lm_kwargs["shard_optimizer"] = True
    was_enabled = telemetry.enabled()
    telemetry.configure(enabled_flag=True)

    def _wire_bytes(before, after, codec_name):
        return sum(
            aggregate.counter_total(after, "hvd_collective_bytes_total",
                                    {"kind": kind, "codec": codec_name})
            - aggregate.counter_total(before, "hvd_collective_bytes_total",
                                      {"kind": kind, "codec": codec_name})
            for kind in ("reduce_scatter", "all_gather"))

    try:
        snap0 = telemetry.metrics_snapshot()
        base = run_lm_benchmark(compression="none", verbose=verbose,
                                **lm_kwargs)
        snap1 = telemetry.metrics_snapshot()
        comp = run_lm_benchmark(compression=codec, verbose=verbose,
                                **lm_kwargs)
        snap2 = telemetry.metrics_snapshot()
    finally:
        telemetry.configure(enabled_flag=was_enabled)

    bytes_none = _wire_bytes(snap0, snap1, "none")
    bytes_codec = _wire_bytes(snap1, snap2, name)
    ratio = (bytes_none / bytes_codec) if bytes_codec else float("inf")
    loss_delta_pct = (abs(comp["loss"] - base["loss"])
                      / max(abs(base["loss"]), 1e-12) * 100.0)
    # Acceptance floors (docs/performance.md): int8 packs 4 fp32 bytes
    # into ~1 wire byte (minus per-bucket qparams), casts halve them.
    target = {"int8": 3.0, "bf16": 1.9, "fp16": 1.9}.get(name)
    result = {
        "metric": "compression_wire_ratio",
        "codec": name,
        "value": round(ratio, 3),
        "target_ratio": target,
        "wire_bytes_none": int(bytes_none),
        "wire_bytes_codec": int(bytes_codec),
        "loss_none": round(base["loss"], 6),
        "loss_codec": round(comp["loss"], 6),
        "loss_delta_pct": round(loss_delta_pct, 4),
        "loss_target_pct": 1.0,
        "n_chips": base["n_chips"],
        "d_model": base["d_model"],
        "n_layers": base["n_layers"],
        "tok_sec_per_chip_none": round(base["tok_sec_per_chip"], 1),
        "tok_sec_per_chip_codec": round(comp["tok_sec_per_chip"], 1),
    }
    if verbose:
        tgt = f" (target >= {target}x)" if target else ""
        print(f"Compression {name}: wire bytes {int(bytes_none):,} -> "
              f"{int(bytes_codec):,} ({ratio:.2f}x{tgt}); loss "
              f"{base['loss']:.5f} -> {comp['loss']:.5f} "
              f"({loss_delta_pct:.3f}% delta, target < 1%)", flush=True)
    print("BENCH " + json.dumps(result), flush=True)
    return result


def run_hierarchical_worker(sizes=(1 << 16, 1 << 20),
                            iters: int = 8) -> None:
    """Worker half of ``--hierarchical`` (spawned by the driver under
    ``hvdrun -np 4``; detected by ``HOROVOD_RANK`` being set).

    Simulates a 2x2 host split on loopback (the
    tests/distributed/hier_check_np4.py trick: override
    ``HOROVOD_LOCAL_*`` before init so the bootstrap agreement sees two
    2-slot hosts), asserts the ``hier_allreduce`` knob is observed LIVE
    in ``runtime.tuned_config()`` in exactly the mode the driver
    requested, then times eager allreduces of each payload size.  Rank 0
    prints one ``HIERBENCH {json}`` line per size for the driver to
    parse."""
    import json

    rank = int(os.environ["HOROVOD_RANK"])
    size = int(os.environ["HOROVOD_SIZE"])
    local = max(size // 2, 1)
    # Override unconditionally: the loopback launcher exports
    # LOCAL_SIZE=np (one host), which makes the topology ineligible.
    os.environ["HOROVOD_LOCAL_SIZE"] = str(local)
    os.environ["HOROVOD_LOCAL_RANK"] = str(rank % local)
    hvd.init()
    from horovod_tpu import basics

    rt = basics.runtime()
    hier = os.environ.get("HOROVOD_HIERARCHICAL_ALLREDUCE", "0") == "1"
    cfg = rt.tuned_config()
    assert cfg.get("hier_allreduce") is hier, \
        f"tuned_config() does not reflect the requested routing: {cfg}"
    if hier:
        assert rt.hierarchical_enabled(), \
            "hierarchical allreduce did not engage"
    rows = []
    for n in sizes:
        x = np.random.default_rng(rank).standard_normal(n).astype(
            np.float32)
        for i in range(2):
            hvd.allreduce(x, average=False, name=f"hb.warm{i}.{n}")
        t0 = time.perf_counter()
        for i in range(iters):
            hvd.allreduce(x, average=False, name=f"hb.{i}.{n}")
        dt = (time.perf_counter() - t0) / iters
        rows.append({"size": n, "sec_per_op": dt,
                     "mb_per_sec": n * 4 / dt / 2**20})
    # Rank-agreed view — the collective the fusion bucketer follows.
    agreed = rt.sync_tuned_config()
    assert agreed.get("hier_allreduce") is hier, agreed
    hvd.shutdown()
    if rank == 0:
        for r in rows:
            print("HIERBENCH " + json.dumps(r), flush=True)


def run_hierarchical_benchmark(np_ranks: int = 4,
                               out: Optional[str] = None,
                               verbose: bool = True) -> dict:
    """Hierarchical-vs-flat eager allreduce A/B (docs/performance.md,
    'Hierarchical collectives'): spawn two ``hvdrun -np 4`` loopback
    runs of :func:`run_hierarchical_worker` — flat ring vs the 2-level
    local-RS / leader-ring / local-AG path — and report per-size
    latency side by side.

    On the loopback rig both levels ride the same TCP stack, so the
    latency delta only bounds the SOFTWARE overhead of the extra local
    phases; the transport win (cross-"host" bytes shrink by
    1/local_size, asserted exactly by the CI np=4 telemetry gate) pays
    off where DCN is the bottleneck.  Each worker asserts the
    ``hier_allreduce`` knob is observed live in ``tuned_config()`` and
    in the rank-agreed ``sync_tuned_config()`` view, so a passing run
    certifies the knob plumbing end to end.

    Prints one BENCH JSON line and (with ``out``) writes the same dict
    as a JSON artifact (CI commits ``BENCH_hier.json``)."""
    import json
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def launch(hier: bool) -> list:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        env["HOROVOD_HIERARCHICAL_ALLREDUCE"] = "1" if hier else "0"
        env["HOROVOD_HIERARCHICAL_ALLREDUCE_THRESHOLD"] = "0"
        cmd = [sys.executable, "-m", "horovod_tpu.runner",
               "-np", str(np_ranks),
               sys.executable, "-m", "horovod_tpu.benchmark",
               "--hierarchical"]
        p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                           timeout=600)
        if p.returncode != 0:
            raise RuntimeError(
                f"hierarchical bench run (hier={hier}) failed rc="
                f"{p.returncode}\n{p.stdout[-2000:]}\n{p.stderr[-2000:]}")
        rows = [json.loads(line.split("HIERBENCH ", 1)[1])
                for line in p.stdout.splitlines() if "HIERBENCH " in line]
        if not rows:
            raise RuntimeError(
                f"hierarchical bench run (hier={hier}) printed no "
                f"HIERBENCH rows:\n{p.stdout[-2000:]}")
        return rows

    flat = {r["size"]: r for r in launch(False)}
    hier = {r["size"]: r for r in launch(True)}
    assert flat.keys() == hier.keys(), (flat, hier)
    sizes = []
    for n in sorted(flat):
        sizes.append({
            "size": n,
            "flat_sec_per_op": round(flat[n]["sec_per_op"], 6),
            "hier_sec_per_op": round(hier[n]["sec_per_op"], 6),
            "speedup": round(flat[n]["sec_per_op"]
                             / hier[n]["sec_per_op"], 3),
        })
    result = {
        "metric": "hierarchical_allreduce_latency",
        "np": np_ranks,
        "local_size": max(np_ranks // 2, 1),
        "knob_observed_live": True,   # every worker asserted it
        "cross_bytes_ratio": "1/local_size (asserted exactly by the "
                             "np=4 CI telemetry gate)",
        "sizes": sizes,
        "note": "loopback CPU rig: both levels share one TCP stack, so "
                "this bounds software overhead only; DCN wins need "
                "real pods",
    }
    if verbose:
        for s in sizes:
            print(f"allreduce {s['size']:>8} floats: flat "
                  f"{s['flat_sec_per_op'] * 1e3:.2f} ms, hier "
                  f"{s['hier_sec_per_op'] * 1e3:.2f} ms "
                  f"({s['speedup']:.2f}x)", flush=True)
    print("BENCH " + json.dumps(result), flush=True)
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
    return result


def _transport_backend_totals(rt) -> dict:
    """Sum ``Runtime.transport_counters()`` across levels into one
    ``{backend: {bytes, seconds, ops}}`` dict (zero-filled)."""
    totals = {b: {"bytes": 0, "seconds": 0.0, "ops": 0}
              for b in ("socket", "shm", "striped")}
    for (backend, _level), kinds in rt.transport_counters().items():
        row = totals[backend]
        row["bytes"] += kinds["bytes"]
        row["seconds"] += kinds["seconds"]
        row["ops"] += kinds["ops"]
    return totals


def run_transport_worker(sizes=(1 << 20, 1 << 24),
                         iters: int = 6) -> None:
    """Worker half of ``--transport`` (spawned by the driver under
    ``hvdrun -np 2``; detected by ``HOROVOD_RANK`` being set).

    Times eager allreduces per payload size under whatever transport the
    driver forced via ``HOROVOD_TRANSPORT``/``HOROVOD_TRANSPORT_STRIPES``,
    asserts the expected backend actually carried the bytes
    (``TRANSPORT_BENCH_EXPECT``; a silent fallback would invalidate the
    A/B), and snapshots the transport counters around each timed loop so
    every row also reports link-level pump bandwidth — the end-to-end
    number folds in submit/fusion/reduce costs shared by all lanes, the
    link number isolates the wire.  Rank 0 prints one
    ``TRANSBENCH {json}`` line per row for the driver to parse."""
    import json

    rank = int(os.environ["HOROVOD_RANK"])
    hvd.init()
    from horovod_tpu import basics

    rt = basics.runtime()
    expect = os.environ.get("TRANSPORT_BENCH_EXPECT", "socket")
    cfg = rt.tuned_config()
    if expect == "shm":
        assert cfg.get("transport_shm"), \
            f"rank {rank}: no shm links negotiated: {cfg}"
    elif expect == "striped":
        want = int(os.environ.get("HOROVOD_TRANSPORT_STRIPES", "0"))
        assert cfg.get("transport_striped"), \
            f"rank {rank}: no striped links negotiated: {cfg}"
        assert cfg.get("transport_stripes") == want, \
            f"rank {rank}: negotiated {cfg.get('transport_stripes')} " \
            f"stripes, wanted {want}"

    rng = np.random.default_rng(rank)
    rows = []
    streams = (int(os.environ.get("HOROVOD_TRANSPORT_STRIPES", "0"))
               if expect == "striped" else 1)

    def timed(label, tensors, names):
        before = _transport_backend_totals(rt)
        t0 = time.perf_counter()
        for x, name in zip(tensors, names):
            hvd.allreduce(x, average=False, name=name)
        wall = time.perf_counter() - t0
        after = _transport_backend_totals(rt)
        nbytes = sum(int(x.nbytes) for x in tensors)
        link_bytes = sum(after[b]["bytes"] - before[b]["bytes"]
                         for b in after)
        # Link seconds are THREAD-CPU seconds (transport::PumpClockUs),
        # so bytes/seconds is per-stream bandwidth on a dedicated core —
        # stable under scheduler pressure — and the aggregate (x streams)
        # is what concurrent stripes deliver with cores/NIC queues of
        # their own.
        link_secs = sum(after[b]["seconds"] - before[b]["seconds"]
                        for b in after)
        link_bw = (link_bytes / link_secs / 2**20
                   if link_secs > 0 else 0.0)
        rows.append({
            "label": label,
            "payload_bytes": nbytes,
            "streams": streams,
            "sec_per_op": wall / len(tensors),
            "algbw_mb_per_sec": nbytes / wall / 2**20,
            "link_mb_per_sec": link_bw,
            "aggregate_link_mb_per_sec": link_bw * streams,
        })

    for n in sizes:
        x = rng.standard_normal(n).astype(np.float32)
        for i in range(2):
            hvd.allreduce(x, average=False, name=f"tb.warm{i}.{n}")
        timed(f"{n * 4 // 2**20}MB",
              [x] * iters, [f"tb.{i}.{n}" for i in range(iters)])
    # Sub-granule burst: 64 x 4 KiB ops measure per-op overhead on the
    # small-tensor path (ring slot reuse / stripe frame headers).
    small = [rng.standard_normal(1024).astype(np.float32)
             for _ in range(64)]
    for i, x in enumerate(small):
        hvd.allreduce(x, average=False, name=f"tb.smallwarm.{i}")
    timed("64x4KB", small, [f"tb.small.{i}" for i in range(64)])

    totals = _transport_backend_totals(rt)
    by_bytes = {b: totals[b]["bytes"] for b in totals}
    if expect == "shm":
        assert by_bytes["shm"] > 0 and by_bytes["socket"] == 0, \
            f"rank {rank}: shm lane leaked to sockets: {by_bytes}"
    elif expect == "striped":
        assert by_bytes["striped"] > 0 and by_bytes["shm"] == 0, \
            f"rank {rank}: striped lane engagement wrong: {by_bytes}"
    else:
        assert by_bytes["socket"] > 0 and by_bytes["shm"] == 0 \
            and by_bytes["striped"] == 0, \
            f"rank {rank}: socket lane engagement wrong: {by_bytes}"
    hvd.shutdown()
    if rank == 0:
        for r in rows:
            print("TRANSBENCH " + json.dumps(r), flush=True)


def run_transport_benchmark(out: Optional[str] = None,
                            verbose: bool = True) -> dict:
    """Transport-backend A/B (docs/performance.md, 'Transport
    backends'): spawn one ``hvdrun -np 2`` loopback run of
    :func:`run_transport_worker` per lane — single TCP socket, the
    shared-memory intra-host ring, and the striped multi-socket
    transport at 1/2/4 stripes — and report per-payload algorithm
    bandwidth side by side.

    ``stripes=1`` deliberately resolves to the plain socket backend
    (``transport::Enabled``), so the striped ratio is measured against
    an identical code path minus the frame/reassembly machinery.  Each
    worker asserts the forced backend actually carried the bytes, so a
    passing run certifies both the numbers and the selection plumbing.

    Targets (checked into the emitted dict, not enforced here): shm
    >= 1.5x single-socket algbw at 64 MB loopback; striped x4 >= 1.2x
    vs stripes=1; CRC32C framing (the ``socket`` vs ``socket_nocrc``
    A/B) < 5% link-bandwidth overhead at 64 MB.  Prints one BENCH JSON
    line and (with ``out``) writes the same dict as a JSON artifact (CI
    commits ``BENCH_transport.json``)."""
    import json
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    lanes = [
        ("socket", "socket", {"HOROVOD_TRANSPORT": "socket"}),
        # Checksum A/B: `socket` above rides the default CRC32C-framed
        # engine (HOROVOD_TRANSPORT_CHECKSUM=auto -> on); this lane is
        # the unframed fast path, so socket/socket_nocrc isolates the
        # wire-integrity overhead (docs/performance.md target < 5%).
        ("socket_nocrc", "socket", {"HOROVOD_TRANSPORT": "socket",
                                    "HOROVOD_TRANSPORT_CHECKSUM": "off"}),
        ("shm", "shm", {"HOROVOD_TRANSPORT": "shm"}),
        ("striped1", "socket", {"HOROVOD_TRANSPORT": "striped",
                                "HOROVOD_TRANSPORT_STRIPES": "1"}),
        ("striped2", "striped", {"HOROVOD_TRANSPORT": "striped",
                                 "HOROVOD_TRANSPORT_STRIPES": "2"}),
        ("striped4", "striped", {"HOROVOD_TRANSPORT": "striped",
                                 "HOROVOD_TRANSPORT_STRIPES": "4"}),
    ]

    def launch(name, expect, knobs) -> list:
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        env["TRANSPORT_BENCH_EXPECT"] = expect
        env.update(knobs)
        cmd = [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
               sys.executable, "-m", "horovod_tpu.benchmark",
               "--transport"]
        p = subprocess.run(cmd, env=env, capture_output=True, text=True,
                           timeout=600)
        if p.returncode != 0:
            raise RuntimeError(
                f"transport bench lane {name} failed rc={p.returncode}\n"
                f"{p.stdout[-2000:]}\n{p.stderr[-2000:]}")
        rows = [json.loads(line.split("TRANSBENCH ", 1)[1])
                for line in p.stdout.splitlines()
                if "TRANSBENCH " in line]
        if not rows:
            raise RuntimeError(
                f"transport bench lane {name} printed no TRANSBENCH "
                f"rows:\n{p.stdout[-2000:]}")
        return rows

    by_lane = {}
    for name, expect, knobs in lanes:
        by_lane[name] = {r["label"]: r for r in launch(name, expect,
                                                       knobs)}
        if verbose:
            for label, r in by_lane[name].items():
                print(f"{name:>8} {label:>7}: "
                      f"{r['algbw_mb_per_sec']:8.1f} MB/s algbw, "
                      f"{r['link_mb_per_sec']:8.1f} MB/s link, "
                      f"{r['sec_per_op'] * 1e3:7.2f} ms/op", flush=True)

    big = "64MB"
    # Headline ratios come from the link counters (thread-CPU seconds,
    # see run_transport_worker): per-stream pump bandwidth for the
    # shm-vs-socket A/B (one stream each), aggregate across stripes for
    # the striping A/B.  Wall-clock algbw ratios ride along for context
    # but on a single-core CI rig they measure the scheduler, not the
    # transport: every pump thread timeshares one core, so stripe
    # parallelism can never show up in wall time there.
    shm_vs_socket = (by_lane["shm"][big]["link_mb_per_sec"]
                     / by_lane["socket"][big]["link_mb_per_sec"])
    striped4_vs_1 = (by_lane["striped4"][big]["aggregate_link_mb_per_sec"]
                     / by_lane["striped1"][big]["aggregate_link_mb_per_sec"])
    # CRC overhead = lost link bandwidth fraction vs the unframed fast
    # path (clamped at 0: on a noisy rig the framed lane can win).
    checksum_overhead = max(
        0.0, 1.0 - (by_lane["socket"][big]["link_mb_per_sec"]
                    / by_lane["socket_nocrc"][big]["link_mb_per_sec"]))
    result = {
        "metric": "transport_backend_algbw",
        "np": 2,
        "rig": "loopback CPU",
        "cores": os.cpu_count(),
        "lanes": {name: sorted(rows.values(),
                               key=lambda r: r["payload_bytes"])
                  for name, rows in by_lane.items()},
        "shm_vs_socket_64mb": round(shm_vs_socket, 3),
        "shm_target": 1.5,
        "shm_vs_socket_64mb_wall": round(
            by_lane["shm"][big]["algbw_mb_per_sec"]
            / by_lane["socket"][big]["algbw_mb_per_sec"], 3),
        "striped4_vs_striped1_64mb": round(striped4_vs_1, 3),
        "striped_target": 1.2,
        "striped4_vs_striped1_64mb_wall": round(
            by_lane["striped4"][big]["algbw_mb_per_sec"]
            / by_lane["striped1"][big]["algbw_mb_per_sec"], 3),
        "checksum_overhead_64mb": round(checksum_overhead, 4),
        "checksum_overhead_target": 0.05,
        "backend_engagement_asserted": True,   # every worker asserted it
        "note": "link bandwidth = bytes / thread-CPU pump seconds, i.e. "
                "per-dedicated-core throughput; aggregate = x streams. "
                "Wall ratios are scheduler-bound on single-core rigs.",
    }
    if verbose:
        print(f"shm vs socket @64MB: {shm_vs_socket:.2f}x link "
              f"(target >= 1.5x); striped x4 vs x1 @64MB: "
              f"{striped4_vs_1:.2f}x aggregate link (target >= 1.2x); "
              f"CRC overhead @64MB: {checksum_overhead * 100:.1f}% "
              f"(target < 5%)", flush=True)
    print("BENCH " + json.dumps(result), flush=True)
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
    return result


def run_serving_benchmark(out: Optional[str] = None, *,
                          num_requests: int = 64,
                          tokens_per_request: int = 8,
                          step_time: float = 0.002,
                          verbose: bool = False):
    """Offered load vs latency for the continuous-batching router
    (``horovod_tpu/serving/``), A/B-ing two batch policies: no batching
    (``max_batch=1``, one sequence per replica step) against continuous
    batching at ``max_batch=8``.

    The rig runs on a virtual clock — two in-process replicas, zero real
    sleeps, time advanced by a fixed simulated decode-step cost — so the
    lane is deterministic and finishes in milliseconds while still
    exercising the real router (queues, round-robin fill, join/leave at
    step boundaries).  Reported tokens/s and latencies are therefore
    properties of the BATCHING POLICY under the modeled step cost, not
    of any accelerator."""
    import json
    from horovod_tpu.serving import (LocalReplicaHandle, ReplicaWorker,
                                     Router, TenantConfig, ToyModel)

    rows = []
    for policy in (1, 8):
        for offered_rps in (50.0, 200.0, 800.0):
            vt = [0.0]  # virtual seconds; advanced per decode step
            replicas = [
                LocalReplicaHandle(ReplicaWorker(ToyModel(),
                                                 replica_id=f"r{i}"))
                for i in range(2)]
            router = Router(replicas,
                            [TenantConfig("bench", quota=1 << 30,
                                          slo_ms=0.0)],
                            max_batch=policy, clock=lambda: vt[0])
            arrivals = [i / offered_rps for i in range(num_requests)]
            pending = {}
            lats = []
            done = 0
            nxt = 0
            while done < num_requests:
                while nxt < num_requests and arrivals[nxt] <= vt[0]:
                    h = router.submit("bench", prompt_token=nxt,
                                      max_new_tokens=tokens_per_request)
                    assert h.rejected is None, h.rejected
                    pending[h.request_id] = (h, arrivals[nxt])
                    nxt += 1
                router.step()
                vt[0] += step_time
                for rid, (h, t0) in list(pending.items()):
                    if h.completed:
                        lats.append(vt[0] - t0)
                        done += 1
                        del pending[rid]
            router.close()
            lats.sort()
            rows.append({
                "policy_max_batch": policy,
                "offered_rps": offered_rps,
                "p50_ms": round(lats[len(lats) // 2] * 1e3, 3),
                "p99_ms": round(
                    lats[min(len(lats) - 1,
                             int(0.99 * len(lats)))] * 1e3, 3),
                "tokens_per_s": round(
                    num_requests * tokens_per_request / vt[0], 1),
            })
            if verbose:
                r = rows[-1]
                print(f"serving max_batch={policy} "
                      f"{offered_rps:g} req/s: p50 {r['p50_ms']} ms, "
                      f"p99 {r['p99_ms']} ms, "
                      f"{r['tokens_per_s']} tok/s", flush=True)
    result = {
        "metric": "serving_continuous_batching",
        "replicas": 2,
        "num_requests": num_requests,
        "tokens_per_request": tokens_per_request,
        "step_time_ms": step_time * 1e3,
        "rows": rows,
        "note": "virtual-clock rig: two in-process replicas with a "
                "fixed modeled decode-step cost; numbers compare "
                "batching policies, not hardware",
    }
    print("BENCH " + json.dumps(result), flush=True)
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
    return result


def run_coordsim_benchmark(out: Optional[str] = None, *,
                           sizes=(8, 64, 256, 1024), ticks: int = 60,
                           verbose: bool = True) -> dict:
    """Control-plane message complexity: tree vs flat coordination
    (docs/control_plane.md) measured on the deterministic protocol
    simulator — no accelerator, no sockets, one process.

    For each world size the same fault-free episode runs twice: flat
    (every rank a direct child of the coordinator — the reference
    O(world) shape) and tree (host blocks + k-ary leader tree).  Two
    numbers per run: the worst per-tick fan-in any single node ingested
    (the hot-spot the coordinator's accept loop serializes) and the
    mean messages per tick across the whole fabric.  Tree must keep the
    fan-in bounded by ``arity + slots - 1`` — effectively O(log N) in
    depth — while flat grows linearly.

    Prints one BENCH JSON line and (with ``out``) writes the same dict;
    also publishes the ``hvd_coord_tick_messages`` gauge per (mode, n)
    when telemetry is on."""
    import json

    from horovod_tpu import telemetry
    from tools.coordsim.sim import Simulation

    rows = []
    for n in sizes:
        row = {"n": n}
        for mode, tree in (("flat", False), ("tree", True)):
            sim = Simulation(n, tree=tree, seed=7)
            stats = sim.run(ticks)
            fan_in = (stats["observed_coord_fan_in"] if mode == "flat"
                      else stats["observed_max_fan_in"])
            per_tick = round(stats["net"]["sent"] / max(stats["ticks"], 1),
                             1)
            row[f"{mode}_max_fan_in"] = fan_in
            row[f"{mode}_msgs_per_tick"] = per_tick
            if mode == "tree":
                row["tree_depth"] = stats["tree_depth"]
            telemetry.gauge(
                "hvd_coord_tick_messages",
                "Worst per-tick control-message fan-in any node ingested "
                "(coordsim benchmark lane)", mode=mode, n=str(n)
            ).set(float(fan_in))
        # Every round still takes one full sweep of announcements, so
        # total traffic is O(N) in both modes; the win is the HOT SPOT —
        # no node ever serializes more than the bounded tree fan-in.
        row["fan_in_ratio"] = round(
            row["flat_max_fan_in"] / max(row["tree_max_fan_in"], 1), 2)
        rows.append(row)
        if verbose:
            print(f"coordsim n={n:5d}: flat fan-in "
                  f"{row['flat_max_fan_in']:4d} -> tree "
                  f"{row['tree_max_fan_in']:3d} "
                  f"(depth {row['tree_depth']}, "
                  f"ratio {row['fan_in_ratio']:.1f}x)", flush=True)
    result = {
        "metric": "coord_tree_vs_flat_fan_in",
        "ticks": ticks,
        "rows": rows,
    }
    print("BENCH " + json.dumps(result), flush=True)
    if out:
        with open(out, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
    return result


def _main():
    import argparse
    parser = argparse.ArgumentParser(
        description="Synthetic benchmark (reference "
                    "examples/tensorflow2_synthetic_benchmark.py)")
    parser.add_argument("--model", default="resnet50")
    parser.add_argument("--batch-size", type=int, default=64,
                        help="per-chip batch size")
    parser.add_argument("--image-size", type=int, default=224)
    parser.add_argument("--num-warmup-batches", type=int, default=5)
    parser.add_argument("--num-batches-per-iter", type=int, default=10)
    parser.add_argument("--num-iters", type=int, default=10)
    parser.add_argument("--efficiency", action="store_true",
                        help="weak-scaling efficiency: 1 device vs all")
    parser.add_argument("--stem", default="conv7",
                        choices=("conv7", "s2d"))
    parser.add_argument("--lm", action="store_true",
                        help="run the transformer-LM lane instead of the "
                             "ResNet harness")
    parser.add_argument("--step-guard", action="store_true",
                        help="measure the NaN/Inf step-guard overhead: "
                             "baseline vs HOROVOD_STEP_GUARD=skip "
                             "(target < 2%% step time)")
    parser.add_argument("--shard-optimizer", action="store_true",
                        help="LM lane with the ZeRO-1 sharded update over "
                             "all devices (reports MFU + per-device "
                             "live-memory bytes)")
    parser.add_argument("--compression", default=None, metavar="CODEC",
                        help="A/B the LM ZeRO lane with gradient codec "
                             "CODEC (bf16, fp16, int8, powersgd[:rank]) "
                             "against the uncompressed wire; prints a "
                             "BENCH JSON row with the wire-byte ratio "
                             "and loss delta")
    parser.add_argument("--hierarchical", action="store_true",
                        help="A/B the 2-level eager allreduce vs the "
                             "flat ring over two hvdrun -np 4 loopback "
                             "runs; prints a BENCH JSON row (inside a "
                             "launched rank this flag selects the "
                             "worker half instead)")
    parser.add_argument("--transport", action="store_true",
                        help="A/B the transport backends (single socket "
                             "vs shm ring vs striped x1/x2/x4) over "
                             "hvdrun -np 2 loopback runs; prints a "
                             "BENCH JSON row (inside a launched rank "
                             "this flag selects the worker half "
                             "instead)")
    parser.add_argument("--serving", action="store_true",
                        help="offered load vs p50/p99 latency and "
                             "tokens/s for the continuous-batching "
                             "router at max_batch 1 vs 8 (virtual-clock "
                             "rig, no accelerator needed)")
    parser.add_argument("--coordsim", action="store_true",
                        help="tree vs flat coordination message "
                             "complexity at N in {8,64,256,1024} on the "
                             "protocol simulator (no accelerator, no "
                             "sockets)")
    parser.add_argument("--out", default=None, metavar="FILE",
                        help="also write the BENCH result dict to FILE "
                             "(e.g. BENCH_hier.json)")
    parser.add_argument("--d-model", type=int, default=None)
    parser.add_argument("--n-layers", type=int, default=None)
    parser.add_argument("--seq-len", type=int, default=None)
    parser.add_argument("--vocab-size", type=int, default=None)
    args = parser.parse_args()

    kwargs = dict(image_size=args.image_size,
                  num_warmup_batches=args.num_warmup_batches,
                  num_batches_per_iter=args.num_batches_per_iter,
                  num_iters=args.num_iters)
    if args.coordsim:
        run_coordsim_benchmark(out=args.out, verbose=True)
        return
    if args.serving:
        run_serving_benchmark(out=args.out, verbose=True)
        return
    if args.hierarchical:
        if "HOROVOD_RANK" in os.environ:
            run_hierarchical_worker()
        else:
            run_hierarchical_benchmark(out=args.out)
        return
    if args.transport:
        if "HOROVOD_RANK" in os.environ:
            run_transport_worker()
        else:
            run_transport_benchmark(out=args.out)
        return
    # Everything below compiles for the device.
    from horovod_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.lm or args.shard_optimizer or args.compression:
        lm_kwargs = dict(num_warmup_batches=args.num_warmup_batches,
                         num_batches_per_iter=args.num_batches_per_iter,
                         num_iters=args.num_iters,
                         shard_optimizer=args.shard_optimizer)
        if jax.devices()[0].platform == "cpu":
            # CPU run = plumbing smoke (MFU needs real chips): downsize to
            # a config the interpreter finishes in seconds, dense
            # attention (no Pallas on CPU).
            lm_kwargs.update(d_model=128, n_layers=2, n_heads=4,
                             d_ff=256, vocab_size=512, seq_len=64,
                             batch_size=2, attention="dense",
                             num_batches_per_iter=min(
                                 args.num_batches_per_iter, 2),
                             num_iters=min(args.num_iters, 3))
        for k, v in (("d_model", args.d_model),
                     ("n_layers", args.n_layers),
                     ("seq_len", args.seq_len),
                     ("vocab_size", args.vocab_size)):
            if v is not None:
                lm_kwargs[k] = v
        # --batch-size is the ResNet knob (default 64); the LM lane keeps
        # its own default of 8/chip unless the flag was set explicitly.
        bs = lm_kwargs.pop("batch_size",
                           args.batch_size if args.batch_size != 64 else 8)
        if args.compression:
            run_compression_benchmark(args.compression, batch_size=bs,
                                      **lm_kwargs)
        else:
            run_lm_benchmark(batch_size=bs, **lm_kwargs)
    elif args.step_guard:
        sg_kwargs = dict(kwargs, stem=args.stem)
        model, bs = args.model, args.batch_size
        if jax.devices()[0].platform == "cpu":
            # CPU run = plumbing smoke: the lane compiles the step TWICE
            # (baseline + guarded), so downsize to finish in seconds.
            model = "resnet18" if args.model == "resnet50" else args.model
            bs = min(bs, 4)
            sg_kwargs.update(image_size=min(args.image_size, 64),
                             num_warmup_batches=1,
                             num_batches_per_iter=min(
                                 args.num_batches_per_iter, 2),
                             num_iters=min(args.num_iters, 3))
        run_step_guard_benchmark(model, bs, **sg_kwargs)
    elif args.efficiency:
        run_scaling_efficiency(args.model, args.batch_size, **kwargs)
    else:
        run_synthetic_benchmark(args.model, args.batch_size, stem=args.stem,
                                **kwargs)


if __name__ == "__main__":
    _main()
