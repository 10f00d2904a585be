"""Mixture-of-Experts training over an expert-parallel mesh axis (no
reference equivalent: Horovod has no alltoall at all in this version,
SURVEY §2.5 — EP is a capability this framework adds).

A Switch-style classifier: router + one FFN expert per chip, tokens
exchanged via ``lax.all_to_all`` on the ``expert`` axis
(:func:`horovod_tpu.parallel.expert.moe_layer`), trained data-parallel on
the same mesh's ``data`` axis with the load-balancing auxiliary loss.
Synthetic clustered tokens: each class lives in a distinct subspace, so
routing has structure to discover and accuracy is the learning check.

Run (single host, 8 simulated chips, 2 data x 4 experts):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/jax_moe.py --dp 2 --experts 4
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.parallel.expert import (load_balancing_loss, moe_layer,
                                         moe_layer_ragged)
from horovod_tpu.topology import build_mesh
from horovod_tpu.utils.compile_cache import enable_compile_cache


def synthetic_clusters(rng, n, d, n_classes):
    """Tokens of class c live around a class-specific direction."""
    dirs = np.linalg.qr(
        np.random.default_rng(7).normal(size=(d, d)))[0][:n_classes]
    labels = rng.integers(0, n_classes, n).astype(np.int32)
    x = dirs[labels] * 3.0 + rng.normal(0, 0.5, (n, d))
    return x.astype(np.float32), labels


def main():
    p = argparse.ArgumentParser(description="Switch-MoE classifier, DPxEP")
    p.add_argument("--dp", type=int, default=2)
    p.add_argument("--experts", type=int, default=4,
                   help="expert-axis size (one expert per chip)")
    p.add_argument("--dim", type=int, default=32)
    p.add_argument("--hidden", type=int, default=64)
    p.add_argument("--classes", type=int, default=8)
    p.add_argument("--tokens", type=int, default=64,
                   help="tokens per chip per step")
    p.add_argument("--steps", type=int, default=150)
    p.add_argument("--lr", type=float, default=3e-2)
    p.add_argument("--aux-weight", type=float, default=0.01)
    p.add_argument("--router", choices=("top1", "top2"), default="top1",
                   help="Switch top-1 or GShard top-2 routing")
    p.add_argument("--dispatch", choices=("dense", "ragged"),
                   default="dense",
                   help="dense: one-hot [T,E,C] dispatch einsum; "
                        "ragged: alltoall_ragged transport (top1 only - "
                        "O(T*D) dispatch memory, real tokens on the "
                        "wire)")
    p.add_argument("--capacity-factor", type=float, default=None,
                   help="expert capacity factor (default 1.25 for top1, "
                        "2.5 for top2 - top-2 emits twice the "
                        "token-choices)")
    args = p.parse_args()

    cap_factor = (args.capacity_factor if args.capacity_factor is not None
                  else (2.5 if args.router == "top2" else 1.25))

    enable_compile_cache()
    hvd.init()
    mesh = build_mesh(axes=("data", "expert"),
                      shape=(args.dp, args.experts))
    d, h = args.dim, args.hidden

    rng = np.random.default_rng(0)

    def init_params():
        g = np.random.default_rng(1)
        return {
            "router": jnp.asarray(g.normal(0, 0.1, (d, args.experts)),
                                  jnp.float32),
            # One expert per chip on the expert axis: leading dim 1 local.
            "w1": jnp.asarray(g.normal(0, 0.1, (args.experts, d, h)),
                              jnp.float32),
            "w2": jnp.asarray(g.normal(0, 0.1, (args.experts, h, d)),
                              jnp.float32),
            "head": jnp.asarray(g.normal(0, 0.1, (d, args.classes)),
                                jnp.float32),
        }

    params = init_params()
    # Expert weights shard over the expert axis; router/head replicate.
    specs = {"router": P(), "w1": P("expert"), "w2": P("expert"),
             "head": P()}
    optimizer = optax.adam(args.lr)
    opt_state = optimizer.init(params)
    # Adam momenta inherit param shardings (same structure).
    opt_specs = optax.tree_map_params(
        optimizer, lambda _l, s: s, jax.eval_shape(optimizer.init, params),
        specs, transform_non_params=lambda _l: P())

    def expert_fn(p, tokens):
        # p: {"w1": [1, D, H], "w2": [1, H, D]} — this chip's expert.
        return jax.nn.relu(tokens @ p["w1"][0]) @ p["w2"][0]

    def loss_fn(params, x, labels):
        logits_r = x @ params["router"]
        epar = {"w1": params["w1"], "w2": params["w2"]}
        if args.dispatch == "ragged":
            if args.router != "top1":
                raise SystemExit("--dispatch ragged supports --router "
                                 "top1 only")
            y = moe_layer_ragged(x, params["router"], expert_fn, epar,
                                 axis_name="expert",
                                 capacity_factor=cap_factor)
        else:
            y = moe_layer(x, params["router"], expert_fn, epar,
                          axis_name="expert", router=args.router,
                          capacity_factor=cap_factor)
        out = (x + y) @ params["head"]
        ce = optax.softmax_cross_entropy_with_integer_labels(
            out, labels).mean()
        aux = load_balancing_loss(logits_r, "expert")
        acc = (out.argmax(-1) == labels).mean()
        return ce + args.aux_weight * aux, acc

    def _step(params, opt_state, x, labels):
        (loss, acc), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, x, labels)
        # The batch is sharded over BOTH axes (the expert axis doubles as
        # data parallelism for the non-expert params).  Consistent target:
        # gradients of the GLOBAL mean loss (1/(DP*E) * sum of per-chip
        # means).  Replicated params: pmean over both axes.  Expert shards:
        # the all_to_all backward already SUMS the E chips of a data row
        # into the shard, so pmean over 'data' alone leaves an extra
        # factor of E — divide it out or SGD-style optimizers see an
        # E-times larger effective LR on expert weights.
        e_sz = lax.axis_size("expert")
        grads = {k: lax.pmean(g, "data") / e_sz if specs[k] != P()
                 else lax.pmean(g, ("data", "expert"))
                 for k, g in grads.items()}
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                lax.pmean(loss, ("data", "expert")),
                lax.pmean(acc, ("data", "expert")))

    step = jax.jit(jax.shard_map(
        _step, mesh=mesh,
        in_specs=(specs, opt_specs, P(("data", "expert")),
                  P(("data", "expert"))),
        out_specs=(specs, opt_specs, P(), P()),
        check_vma=False),
        donate_argnums=(0, 1))

    shard = NamedSharding(mesh, P(("data", "expert")))
    params = jax.device_put(params, jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs))
    opt_state = jax.device_put(opt_state, jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), opt_specs,
        is_leaf=lambda l: isinstance(l, P)))

    n_global = args.tokens * args.dp * args.experts
    acc = None
    for i in range(args.steps):
        x, labels = synthetic_clusters(rng, n_global, d, args.classes)
        params, opt_state, loss, acc = step(
            params, opt_state,
            jax.device_put(jnp.asarray(x), shard),
            jax.device_put(jnp.asarray(labels), shard))
        if hvd.rank() == 0 and (i + 1) % 50 == 0:
            print(f"step {i + 1}: loss {float(np.asarray(loss)):.4f} "
                  f"acc {float(np.asarray(acc)):.3f}", flush=True)

    final_acc = float(np.asarray(acc))
    if hvd.rank() == 0:
        print(f"final accuracy {final_acc:.3f}", flush=True)
        assert final_acc > 0.8, final_acc
        print("OK", flush=True)


if __name__ == "__main__":
    main()
