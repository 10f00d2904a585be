"""GPT-style LM pretraining over a composed DP x TP x SP mesh — the
long-context flagship recipe (no reference equivalent: Horovod is
DP-only, SURVEY §2.5; this example shows the same 5-line-change workflow
scaling axes Horovod never had).

The whole recipe is one jitted SPMD program per step:

* ``data`` axis  — batch sharded, gradients fused-pmean'd (the Horovod DP
  contract)
* ``model`` axis — Megatron column/row tensor parallelism inside every
  attention/MLP block
* ``seq`` axis   — ring attention over sequence chunks riding ICI
  neighbor exchanges (set ``--attention ulysses`` for all-to-all head
  parallelism instead)

plus cosine LR schedule with warmup, rank-0 orbax checkpointing with
restart-resume, and tokens/sec accounting.

Run (single host, 8 simulated chips, 2x2x2 mesh):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/jax_lm_pretrain.py --dp 2 --tp 2 --sp 2 --steps 20
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import checkpoint
from horovod_tpu.models import transformer as tfm
from horovod_tpu.topology import build_mesh
from horovod_tpu.utils.compile_cache import enable_compile_cache


def synthetic_tokens(rng, batch, seq, vocab):
    """Zipf-ish synthetic corpus: next token correlates with current, so
    the model has real structure to learn (loss visibly decreases)."""
    toks = rng.integers(0, vocab, (batch, seq + 1), dtype=np.int32)
    # Make 70% of transitions deterministic-ish: t[i+1] = (t[i]*7+3) % vocab
    mask = rng.random((batch, seq)) < 0.7
    for i in range(seq):
        nxt = (toks[:, i] * 7 + 3) % vocab
        toks[:, i + 1] = np.where(mask[:, i], nxt, toks[:, i + 1])
    return toks[:, :-1], toks[:, 1:]


def main():
    p = argparse.ArgumentParser(description="LM pretraining, DPxTPxSP")
    p.add_argument("--dp", type=int, default=2)
    p.add_argument("--tp", type=int, default=1)
    p.add_argument("--sp", type=int, default=1)
    p.add_argument("--pp", type=int, default=1,
                   help="pipeline stages (composes with --dp only)")
    p.add_argument("--microbatches", type=int, default=2,
                   help="GPipe microbatches per step (with --pp)")
    p.add_argument("--pp-schedule",
                   choices=("gpipe", "1f1b", "interleaved",
                            "interleaved_1f1b"),
                   default="gpipe",
                   help="pipeline schedule: gpipe (AD backward pipeline), "
                        "1f1b (O(stages) activation memory), "
                        "interleaved (virtual stages), or "
                        "interleaved_1f1b (full Megatron: bubble/v at "
                        "O(stages) memory, docs/parallelism.md)")
    p.add_argument("--virtual", type=int, default=2,
                   help="virtual chunks per device (--pp-schedule "
                        "interleaved / interleaved_1f1b)")
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--batch-size", type=int, default=4,
                   help="global batch (sequences)")
    p.add_argument("--seq-len", type=int, default=256)
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--d-model", type=int, default=256)
    p.add_argument("--n-heads", type=int, default=8)
    p.add_argument("--n-layers", type=int, default=2)
    p.add_argument("--d-ff", type=int, default=512)
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--warmup-steps", type=int, default=10)
    p.add_argument("--attention", default=None,
                   choices=["ring", "ring_flash", "ulysses", "local",
                            "flash", "auto"],
                   help="default: ring (local under --pp); ring_flash = "
                        "ring schedule with the Pallas kernel per block")
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--log-every", type=int, default=10)
    args = p.parse_args()

    enable_compile_cache()
    hvd.init()
    if args.pp > 1 and (args.tp > 1 or args.sp > 1):
        raise SystemExit("--pp composes with --dp only; TP/SP ride the "
                         "model/seq axes of the non-pipelined step")
    if args.attention is None:
        args.attention = "local" if args.pp > 1 else "ring"
    elif args.pp > 1 and args.attention not in ("local", "auto"):
        # "auto" resolving to local inside stages IS its documented
        # behavior — only explicit ring/ulysses/flash must fail loudly.
        raise SystemExit("--pp uses local attention inside each stage; "
                         f"--attention {args.attention} is not available "
                         "(never silently substitute algorithms)")
    axes, shape = [], []
    for name, n in (("data", args.dp), ("model", args.tp),
                    ("seq", args.sp), ("pipe", args.pp)):
        if n > 1:
            axes.append(name)
            shape.append(n)
    if not axes:
        axes, shape = ["data"], [1]
    mesh = build_mesh(axes=tuple(axes), shape=tuple(shape))
    model_axis = "model" if args.tp > 1 else None
    seq_axis = "seq" if args.sp > 1 else None

    cfg = tfm.TransformerConfig(
        vocab_size=args.vocab, d_model=args.d_model, n_heads=args.n_heads,
        n_layers=args.n_layers, d_ff=args.d_ff, max_seq=args.seq_len,
        dtype=jnp.float32 if jax.default_backend() == "cpu"
        else jnp.bfloat16)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)

    warmup = min(args.warmup_steps, args.steps - 1)
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, args.lr, warmup, max(args.steps, warmup + 1))
    if args.pp > 1:
        # Pipelined path differentiates OUTSIDE the shard_map, so grads
        # are global arrays and the plain optax clip is correct.
        optimizer = optax.chain(
            optax.clip_by_global_norm(1.0),
            optax.scale_by_adam(),
            optax.scale_by_schedule(schedule),
            optax.scale(-1.0))
        v = (args.virtual if args.pp_schedule in
             ("interleaved", "interleaved_1f1b") else 1)
        params = tfm.split_pipeline_params(params, args.pp, virtual=v)
        step_fn, shard_of = tfm.make_train_step_pipelined(
            cfg, optimizer, mesh,
            data_axis="data" if args.dp > 1 else None,
            pipe_axis="pipe", n_microbatches=args.microbatches,
            schedule=args.pp_schedule, virtual=v)
        p_sh, opt_sh = shard_of(params)
        params = {g: {k: jax.device_put(v, p_sh[g][k])
                      for k, v in params[g].items()} for g in params}
        opt_state = jax.device_put(optimizer.init(params), opt_sh)
    else:
        # Sharding-aware clip: the plain optax clip would compute the
        # norm of LOCAL weight shards inside the TP shard_map (wrong and
        # model-axis-varying); this one psums sharded square-sums.
        from horovod_tpu.parallel.tensor import clip_by_global_norm
        optimizer = optax.chain(
            clip_by_global_norm(1.0, tfm.param_specs(cfg, model_axis)),
            optax.scale_by_adam(),
            optax.scale_by_schedule(schedule),
            optax.scale(-1.0))
        opt_state = optimizer.init(params)

        step_fn, specs, opt_specs = tfm.make_train_step(
            cfg, optimizer, mesh,
            data_axis="data" if args.dp > 1 else None,
            model_axis=model_axis, seq_axis=seq_axis,
            attention=args.attention)
        params = jax.device_put(
            params, jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), specs))
        opt_state = jax.device_put(
            opt_state, jax.tree_util.tree_map(
                lambda s: NamedSharding(mesh, s), opt_specs))

    start = 0
    if args.checkpoint_dir:
        last = checkpoint.latest_step(args.checkpoint_dir)
        if last is not None:
            params, opt_state = checkpoint.restore(
                args.checkpoint_dir, (params, opt_state))
            start = last + 1
            if hvd.rank() == 0:
                print(f"resumed from step {last}", flush=True)

    data_ax = "data" if args.dp > 1 else None
    data_spec = NamedSharding(mesh, P(data_ax, seq_axis)
                              if seq_axis else P(data_ax))
    rng = np.random.default_rng(0)
    tokens_per_step = args.batch_size * args.seq_len
    t0, first_loss, loss = time.perf_counter(), None, None
    for i in range(start, args.steps):
        toks, labels = synthetic_tokens(rng, args.batch_size, args.seq_len,
                                        args.vocab)
        toks = jax.device_put(toks, data_spec)
        labels = jax.device_put(labels, data_spec)
        params, opt_state, loss = step_fn(params, opt_state, toks, labels)
        if i == start or (i + 1) % args.log_every == 0 or i == args.steps - 1:
            lval = float(np.asarray(loss))
            if first_loss is None:
                first_loss = lval
                t0 = time.perf_counter()   # exclude compile from rate
            elif hvd.rank() == 0:
                rate = tokens_per_step * (i - start) / (
                    time.perf_counter() - t0)
                print(f"step {i}: loss {lval:.4f} "
                      f"({rate:,.0f} tok/s)", flush=True)
        if args.checkpoint_dir and (i + 1) % 50 == 0:
            checkpoint.save(args.checkpoint_dir, (params, opt_state),
                            step=i, max_to_keep=2)

    final = float(np.asarray(loss))
    if args.checkpoint_dir:
        checkpoint.save(args.checkpoint_dir, (params, opt_state),
                        step=args.steps - 1, max_to_keep=2)
    if hvd.rank() == 0:
        print(f"final loss {final:.4f} (first {first_loss:.4f})",
              flush=True)
        assert final < first_loss, "loss did not decrease"
        print("OK", flush=True)


if __name__ == "__main__":
    main()
