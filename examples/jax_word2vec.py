"""Skip-gram word2vec with negative sampling, data-parallel (reference
``examples/tensorflow_word2vec.py``).

The embedding workload the CNN/LM examples don't cover: wide sparse
lookups, a dense scoring matmul, and DP gradient averaging over the mesh.
Hermetic: a synthetic topic-structured corpus (words from the same topic
co-occur), so intra-topic embedding similarity measurably rises — the
assert at the end is the learning check.

Run (single process, 8 simulated chips):
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  python examples/jax_word2vec.py
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.ops.fusion import fused_pytree_mean
from horovod_tpu.topology import data_axis, mesh_size
from horovod_tpu.utils.compile_cache import enable_compile_cache


def synthetic_corpus(rng, n_pairs, vocab, n_topics=8):
    """(center, context) pairs drawn within topics; negatives are global."""
    per_topic = vocab // n_topics
    topics = rng.integers(0, n_topics, n_pairs)
    center = topics * per_topic + rng.integers(0, per_topic, n_pairs)
    context = topics * per_topic + rng.integers(0, per_topic, n_pairs)
    return center.astype(np.int32), context.astype(np.int32)


def main():
    p = argparse.ArgumentParser(description="skip-gram word2vec, DP")
    p.add_argument("--vocab", type=int, default=512)
    p.add_argument("--dim", type=int, default=64)
    p.add_argument("--batch-size", type=int, default=64,
                   help="pairs per chip")
    p.add_argument("--negatives", type=int, default=8)
    p.add_argument("--steps", type=int, default=600)
    p.add_argument("--lr", type=float, default=1.0)
    args = p.parse_args()

    enable_compile_cache()
    hvd.init()
    mesh = hvd.mesh()
    ax = data_axis(mesh)
    n_chips = mesh_size(mesh)
    global_bs = args.batch_size * n_chips

    rng = np.random.default_rng(0)
    emb_in = jnp.asarray(
        rng.normal(0, 0.05, (args.vocab, args.dim)), jnp.float32)
    emb_out = jnp.asarray(
        rng.normal(0, 0.05, (args.vocab, args.dim)), jnp.float32)
    params = {"in": emb_in, "out": emb_out}
    optimizer = optax.adagrad(args.lr)   # the classic word2vec choice
    opt_state = optimizer.init(params)

    def loss_fn(params, center, context, negatives):
        # Negative-sampling objective (Mikolov et al. 2013): dense ops
        # only — gather + batched dot products — all MXU/VPU friendly.
        v = params["in"][center]                       # [B, D]
        u_pos = params["out"][context]                 # [B, D]
        u_neg = params["out"][negatives]               # [B, K, D]
        pos = jnp.sum(v * u_pos, axis=-1)              # [B]
        neg = jnp.einsum("bd,bkd->bk", v, u_neg)       # [B, K]
        return -(jnp.mean(jax.nn.log_sigmoid(pos)) +
                 jnp.mean(jnp.sum(jax.nn.log_sigmoid(-neg), axis=-1)))

    def _step(params, opt_state, center, context, negatives):
        loss, grads = jax.value_and_grad(loss_fn)(
            params, center, context, negatives)
        grads = fused_pytree_mean(grads, ax)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        return (optax.apply_updates(params, updates), opt_state,
                lax.pmean(loss, ax))

    repl, shard = P(), P(ax)
    step = jax.jit(jax.shard_map(
        _step, mesh=mesh,
        in_specs=(repl, repl, shard, shard, shard),
        out_specs=(repl, repl, repl), check_vma=False),
        donate_argnums=(0, 1))

    repl_s = NamedSharding(mesh, P())
    shard_s = NamedSharding(mesh, P(ax))
    params, opt_state = jax.device_put((params, opt_state), repl_s)

    def topic_similarity(emb):
        """Mean cosine similarity of same-topic word pairs minus
        cross-topic pairs (the learning signal)."""
        e = np.asarray(emb)
        e = e / (np.linalg.norm(e, axis=1, keepdims=True) + 1e-9)
        per_topic = args.vocab // 8
        same, cross = [], []
        r = np.random.default_rng(1)
        for _ in range(512):
            t = r.integers(0, 8)
            a, b = t * per_topic + r.integers(0, per_topic, 2)
            c = ((t + 1) % 8) * per_topic + r.integers(0, per_topic)
            same.append(e[a] @ e[b])
            cross.append(e[a] @ e[c])
        return float(np.mean(same) - np.mean(cross))

    sim0 = topic_similarity(params["in"])
    loss = None
    for i in range(args.steps):
        center, context = synthetic_corpus(rng, global_bs, args.vocab)
        negatives = rng.integers(
            0, args.vocab, (global_bs, args.negatives)).astype(np.int32)
        params, opt_state, loss = step(
            params, opt_state,
            jax.device_put(jnp.asarray(center), shard_s),
            jax.device_put(jnp.asarray(context), shard_s),
            jax.device_put(jnp.asarray(negatives), shard_s))
        if hvd.rank() == 0 and (i + 1) % 50 == 0:
            print(f"step {i + 1}: loss {float(np.asarray(loss)):.4f}",
                  flush=True)

    sim1 = topic_similarity(params["in"])
    if hvd.rank() == 0:
        print(f"topic-similarity margin: {sim0:.4f} -> {sim1:.4f}",
              flush=True)
        assert sim1 > sim0 + 0.05, (sim0, sim1)
        print("OK", flush=True)


if __name__ == "__main__":
    main()
