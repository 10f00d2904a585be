"""Sharded-update data parallelism (ZeRO stage 1) for the SPMD plane.

The classic Horovod recipe — and this framework's replicated
:func:`horovod_tpu.parallel.data.make_training_step` — allreduces every
gradient and then runs a fully **replicated** optimizer update on every
chip: update FLOPs and optimizer-state memory scale with 1, not 1/N.
ZeRO stage 1 (Rajbhandari et al., SC'20; automatic weight-update sharding
on TPUs, Xu et al. 2020) observes that a ring allreduce is already a
reduce-scatter followed by an all-gather, and slides the optimizer update
between the two phases:

1. **reduce-scatter** the fused gradient buckets — each rank keeps the
   mean of its 1/N slice (:func:`horovod_tpu.ops.fusion.fused_reduce_scatter`);
2. run the optimizer **only on this rank's slice** of the flat parameter /
   optimizer-state buckets — N-times less update compute, and the
   optimizer state (Adam's m/v, momentum) lives ONLY as the local shard:
   ~(2 + K)/N per-rank optimizer memory for a K-slot optimizer;
3. **all-gather** the resulting update slices back to full parameters
   (:func:`horovod_tpu.ops.fusion.fused_all_gather`).

Same total wire bytes as the allreduce it replaces; the training
trajectory is identical to the replicated path up to float reduction
order, because every element-wise optimizer commutes with the slicing.

The state layout is deliberately *global-array friendly*: each optimizer
state leaf that mirrors the parameters is ONE flat padded bucket vector
whose GLOBAL shape is the full bucket; sharding it ``P(axis)`` over the
data axis makes the local view exactly this rank's shard.  That means
``jax.device_put`` with :meth:`ShardedOptimizer.state_shardings` places
the 1/N shards, checkpoints can gather the global array transparently
(:func:`gather_full_state`), and the replicated path's checkpoints stay
interchangeable with the sharded path's (:func:`scatter_full_state`).

Restriction: the wrapped optax optimizer must be **element-wise** (SGD,
momentum, Adam/AdamW, RMSProp, Lion, ...).  Transforms that mix
information across elements of one tensor (e.g. per-layer norm clipping,
``optax.clip_by_global_norm``) would see only the local shard; compose
those *before* the sharded wrapper on the full gradients if needed.
"""

from __future__ import annotations

from typing import Any, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from horovod_tpu import telemetry
from horovod_tpu.ops import compression as compression_mod
from horovod_tpu.ops import fusion
from horovod_tpu.telemetry import scopes


@jax.tree_util.register_pytree_node_class
class ZeroShardedState:
    """Optimizer state over the flat bucket vectors (ZeRO-1 layout).

    ``inner`` is the wrapped optax optimizer's state with the *list of
    flat padded bucket vectors* playing the role of the params pytree.
    ``wire`` is the wire codec's error-feedback residual state
    (:class:`horovod_tpu.ops.compression.CodecState`, ``None`` for
    stateless codecs).  The bucketing plan, the params treedef, the
    wrapped optimizer and the codec ride along as static aux data so
    checkpointing can convert to/from the replicated per-leaf layout
    without out-of-band bookkeeping.
    """

    def __init__(self, inner: Any, plan: fusion.ReduceScatterPlan,
                 treedef, optimizer: optax.GradientTransformation,
                 wire: Any = None, codec: Any = None):
        self.inner = inner
        self.plan = plan
        self.treedef = treedef
        self.optimizer = optimizer
        self.wire = wire
        self.codec = codec

    def tree_flatten(self):
        return ((self.inner, self.wire),
                (self.plan, self.treedef, self.optimizer, self.codec))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], aux[0], aux[1], aux[2],
                   wire=children[1], codec=aux[3])

    def __repr__(self):
        codec = getattr(self.codec, "name", None) or "none"
        return (f"ZeroShardedState(buckets={len(self.plan.buckets)}, "
                f"axis_size={self.plan.axis_size}, codec={codec})")


def is_zero_state(x) -> bool:
    return isinstance(x, ZeroShardedState)


def _map_param_subtrees(optimizer, f, state_inner):
    """Apply ``f`` to every whole params-shaped subtree inside an optax
    state (``is_leaf=always`` stops :func:`optax.tree_map_params`'s inner
    map at the subtree root, so ``f`` sees the list-of-buckets / the
    per-leaf tree in one piece)."""
    return optax.tree_map_params(optimizer, f, state_inner,
                                 is_leaf=lambda _: True)


class ShardedOptimizer:
    """ZeRO-1 wrapper around an element-wise optax optimizer.

    Follows the ``GradientTransformation`` calling convention —
    ``init(params) -> state`` and ``update(grads, state, params) ->
    (updates, state)`` — but ``update`` MUST run inside ``shard_map``
    with ``axis_name`` bound (it issues the reduce-scatter / all-gather
    pair), and ``params`` is required (the update slices this rank's
    parameter shard out of the replicated params).
    """

    def __init__(self, optimizer: optax.GradientTransformation,
                 axis_name: str = "data", *,
                 axis_size: Optional[int] = None,
                 threshold: Optional[int] = None,
                 mean: bool = True,
                 compression=None,
                 cross_axis_name: Optional[str] = None,
                 cross_compression=None):
        if not isinstance(axis_name, str):
            raise NotImplementedError(
                f"sharded_optimizer shards over ONE mesh axis; got "
                f"axis_name={axis_name!r}.  For dp x sp grids, shard over "
                f"the data axis and average the seq axis upstream.")
        self.inner = optimizer
        self.axis_name = axis_name
        self._axis_size = axis_size
        self.threshold = threshold
        self.mean = mean
        self.codec = compression_mod.resolve_codec(compression)
        # Hierarchical (two-level) mode: axis_name is the intra-slice ICI
        # axis; cross_axis_name the DCN axis.  Shards stay 1/ici per slice
        # (replicated over DCN) and only the reduce leg crosses hosts —
        # cross-host bytes drop to 1/ici of the flat scheme's.  The cross
        # codec is deliberately independent ("int8 on DCN, none on ICI")
        # and NEVER read from HOROVOD_COMPRESSION: quantizing the slow
        # link is an explicit choice.
        self.cross_axis_name = cross_axis_name
        self.cross_codec = (compression_mod.resolve_codec(
            cross_compression if cross_compression is not None else "none")
            if cross_axis_name is not None else None)

    # -- layout ------------------------------------------------------------
    def _resolve_axis_size(self) -> int:
        if self._axis_size is not None:
            return int(self._axis_size)
        from horovod_tpu import basics
        try:
            m = basics.mesh()
            self._axis_size = int(m.shape[self.axis_name])
        except Exception as e:
            raise ValueError(
                f"sharded_optimizer could not resolve the size of axis "
                f"{self.axis_name!r}: pass axis_size= (or mesh=) "
                f"explicitly, or hvd.init() first") from e
        return self._axis_size

    # -- GradientTransformation surface ------------------------------------
    def init(self, params) -> ZeroShardedState:
        """Build the sharded-layout state from (global, replicated) params.

        State leaves that mirror params come out as FULL flat padded
        bucket vectors — place them with :meth:`state_shardings` (or let
        the training step's ``shard_map`` in_specs shard them on entry)
        so each rank materializes only its 1/N shard.
        """
        leaves, treedef = jax.tree_util.tree_flatten(params)
        plan = fusion.make_reduce_scatter_plan(
            leaves, self._resolve_axis_size(), self.threshold,
            codec=self.codec)
        flats = plan.concat(leaves)
        return ZeroShardedState(self.inner.init(flats), plan, treedef,
                                self.inner,
                                wire=self.codec.init_state(plan),
                                codec=self.codec)

    def update(self, grads, state: ZeroShardedState, params=None):
        """The sharded update: reduce-scatter grads, step the optimizer on
        this rank's shard, all-gather the updates.  Returns the FULL
        updates pytree (feed to ``optax.apply_updates``) and the new
        sharded state."""
        if params is None:
            raise ValueError(
                "sharded_optimizer.update requires params: the update "
                "slices this rank's parameter shard out of them")
        plan = state.plan
        gleaves, gdef = jax.tree_util.tree_flatten(grads)
        if gdef != state.treedef:
            raise ValueError(
                f"gradient tree structure {gdef} does not match the "
                f"structure this state was initialized with "
                f"({state.treedef})")
        n = lax.axis_size(self.axis_name)
        if int(n) != plan.axis_size:
            raise ValueError(
                f"axis {self.axis_name!r} has size {n} here but the "
                f"optimizer state was sharded {plan.axis_size}-way — "
                f"re-init (or re-shard the checkpoint) for this mesh")
        self._record(plan)

        with jax.named_scope(scopes.GRAD_REDUCE_SCATTER):
            if self.cross_axis_name is not None:
                # Two-level: intra-slice RS (unscaled) -> per-shard DCN
                # psum (with the cross codec) -> one hoisted 1/(ici*dcn)
                # multiply on the shard.  The all-gather below stays
                # intra-slice.
                grad_shards, wire = (
                    compression_mod.compressed_reduce_scatter(
                        gleaves, self.axis_name, self.codec, plan=plan,
                        state=state.wire, mean=False))
                dcn = lax.axis_size(self.cross_axis_name)
                grad_shards = [
                    compression_mod.cross_level_psum(
                        s, self.cross_axis_name, self.cross_codec)
                    for s in grad_shards]
                if self.mean:
                    inv = 1.0 / (plan.axis_size * dcn)
                    grad_shards = [s * jnp.asarray(inv, s.dtype)
                                   for s in grad_shards]
            else:
                grad_shards, wire = (
                    compression_mod.compressed_reduce_scatter(
                        gleaves, self.axis_name, self.codec, plan=plan,
                        state=state.wire, mean=self.mean))
        with jax.named_scope(scopes.OPTIMIZER):
            idx = lax.axis_index(self.axis_name)
            flats = plan.concat(jax.tree_util.tree_leaves(params))
            param_shards = [plan.shard_slice(b, flat, idx)
                            for b, flat in enumerate(flats)]
            upd_shards, new_inner = self.inner.update(
                grad_shards, state.inner, param_shards)
        with jax.named_scope(scopes.PARAM_ALL_GATHER):
            upd_leaves, wire = compression_mod.compressed_all_gather(
                upd_shards, plan, self.axis_name, self.codec, state=wire)
        updates = jax.tree_util.tree_unflatten(state.treedef, upd_leaves)
        return updates, ZeroShardedState(new_inner, plan, state.treedef,
                                         self.inner, wire=wire,
                                         codec=self.codec)

    def _record(self, plan: fusion.ReduceScatterPlan) -> None:
        if not telemetry.enabled():
            return
        telemetry.counter(
            "hvd_zero_updates_total",
            "Sharded (ZeRO-1) optimizer updates traced").inc()
        if self.cross_axis_name is not None:
            telemetry.counter(
                "hvd_zero_hier_updates_total",
                "ZeRO-1 updates using the two-level (ICI+DCN) reduce "
                "path").inc()
        telemetry.counter(
            "hvd_zero_buckets_total",
            "Flat buckets in sharded optimizer updates").inc(
            len(plan.buckets))
        hist = telemetry.histogram(
            "hvd_zero_shard_bytes",
            "Per-rank shard size of each sharded-update bucket",
            bounds=telemetry.DEFAULT_BYTE_BUCKETS)
        for b in range(len(plan.buckets)):
            hist.observe(float(plan.shard_size(b) *
                               plan.bucket_dtype(b).itemsize))

    # -- placement helpers -------------------------------------------------
    def state_specs(self, state: ZeroShardedState) -> ZeroShardedState:
        """PartitionSpec tree congruent to ``state``: flat bucket leaves
        sharded ``P(axis_name)`` on dim 0, scalar bookkeeping (step
        counts) replicated.  Usable directly as a ``shard_map``
        in/out_spec or mapped to ``NamedSharding`` for ``device_put``."""
        ax = self.axis_name
        specs = optax.tree_map_params(
            self.inner,
            lambda _leaf: P(ax),
            state.inner,
            transform_non_params=lambda _leaf: P())
        return ZeroShardedState(specs, state.plan, state.treedef,
                                self.inner,
                                wire=self.codec.state_specs(state.plan, ax),
                                codec=self.codec)

    def state_shardings(self, mesh, state: ZeroShardedState):
        """``NamedSharding`` tree for ``jax.device_put``-placing a freshly
        built (or checkpoint-restored) state as actual 1/N shards."""
        return jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s),
            self.state_specs(state),
            is_leaf=lambda x: isinstance(x, P))


def sharded_optimizer(optimizer: optax.GradientTransformation,
                      axis_name: str = "data", *,
                      axis_size: Optional[int] = None,
                      mesh=None,
                      threshold: Optional[int] = None,
                      mean: bool = True,
                      compression=None,
                      cross_axis_name: Optional[str] = None,
                      cross_compression=None) -> ShardedOptimizer:
    """Wrap an element-wise optax ``optimizer`` for ZeRO-1 sharded updates
    over ``axis_name`` (see the module docstring for the algorithm and
    restrictions).  ``axis_size`` (or ``mesh``) pins the shard count at
    init time; omitted, it is read from ``hvd.mesh()``.  ``compression``
    selects the wire codec applied per bucket inside the reduce-scatter /
    all-gather pair (:mod:`horovod_tpu.ops.compression`; default none,
    overridable via ``HOROVOD_COMPRESSION``).

    ``cross_axis_name`` enables the hierarchical mode on a two-level
    (``"dcn"``/``"ici"``) mesh: ``axis_name`` becomes the intra-slice ICI
    axis, state shards 1/ici-way per slice, and gradients cross hosts
    only as 1/ici-size shards through one DCN ``psum`` — optionally
    quantized by ``cross_compression`` (stateless: none/bf16/fp16/int8,
    see :func:`horovod_tpu.ops.compression.cross_level_psum`)."""
    if mesh is not None and axis_size is None:
        axis_size = int(mesh.shape[axis_name])
    return ShardedOptimizer(optimizer, axis_name, axis_size=axis_size,
                            threshold=threshold, mean=mean,
                            compression=compression,
                            cross_axis_name=cross_axis_name,
                            cross_compression=cross_compression)


# ---------------------------------------------------------------------------
# Checkpoint interchange: sharded layout <-> replicated per-leaf layout.
# ---------------------------------------------------------------------------

def gather_full_state(state: ZeroShardedState):
    """Convert a sharded-layout state into the equivalent REPLICATED optax
    state pytree — exactly what ``optimizer.init(params)`` would hold after
    the same training steps.  Checkpoints written in this layout are
    mesh-size-independent and interchangeable with the replicated path's.

    Reads the state leaves as GLOBAL arrays (a ``P(axis)``-sharded leaf's
    global shape is the full flat bucket), so on a fully-addressable mesh
    no explicit collective is needed.

    Wire-codec residual state (``state.wire``) is deliberately EXCLUDED:
    checkpoints stay byte-identical with and without compression, and a
    restore simply starts with zero residuals (error feedback loses at
    most one pending step of correction).  Elastic axis-size changes go
    through :func:`reshard_state`, which DOES carry the pending error.
    """
    plan, treedef = state.plan, state.treedef

    def expand(flats):
        leaves = plan.split(list(flats))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return _map_param_subtrees(state.optimizer, expand, state.inner)


def local_state_digest(state: ZeroShardedState) -> int:
    """Cheap deterministic digest of THIS process's optimizer-state
    bytes: crc32 chained over each inner leaf's addressable shards in
    device order.  The divergence sentinel (``horovod_tpu.resilience``)
    allreduces this per rank — under ZeRO-1 the state only exists as
    shards, and digesting the local bytes avoids gathering the full
    buckets just to hash them."""
    import zlib
    crc = 0
    for leaf in jax.tree_util.tree_leaves(state.inner):
        shards = getattr(leaf, "addressable_shards", None)
        if not shards:
            arr = np.ascontiguousarray(np.asarray(leaf))
            crc = zlib.crc32(arr.tobytes(), crc)
            continue
        for shard in sorted(shards,
                            key=lambda s: getattr(s.device, "id", 0)):
            arr = np.ascontiguousarray(np.asarray(shard.data))
            crc = zlib.crc32(arr.tobytes(), crc)
    return crc


def scatter_full_state(full_state, like: ZeroShardedState
                       ) -> ZeroShardedState:
    """Inverse of :func:`gather_full_state`: re-shard a replicated-layout
    optax state into ``like``'s flat-bucket layout (``like`` supplies the
    plan/treedef — typically the freshly ``init``-ed state the restore is
    about to replace).  The result's flat leaves are global full vectors;
    place them with :meth:`ShardedOptimizer.state_shardings` before
    training."""
    plan = like.plan

    def collapse(per_leaf_subtree):
        return plan.concat(jax.tree_util.tree_leaves(per_leaf_subtree))

    new_inner = _map_param_subtrees(like.optimizer, collapse, full_state)
    return ZeroShardedState(new_inner, plan, like.treedef, like.optimizer,
                            wire=like.wire, codec=like.codec)


def reshard_state(state: ZeroShardedState, like: ZeroShardedState
                  ) -> ZeroShardedState:
    """Re-bucket a sharded state for a DIFFERENT axis size: round-trip
    through the portable layout (``gather_full_state`` then
    ``scatter_full_state`` against ``like``'s plan).  This is the
    world-size-change path of an elastic warm restart — a state sharded
    for the old N becomes ``like``'s layout for the new N, bit-exactly
    (the element-wise moments are only re-arranged, never recomputed).
    ``like`` is the freshly ``init``-ed state on the new mesh; place the
    result with :meth:`ShardedOptimizer.state_shardings` before
    training.

    Wire-codec residual state rides along codec-aware: the pending
    error-feedback correction is re-bucketed for the new axis size
    (:meth:`horovod_tpu.ops.compression.BucketCodec.reshard_state`) so a
    shrink/grow does not silently drop the error a quantizing codec still
    owes the model."""
    if telemetry.enabled():
        telemetry.counter(
            "hvd_zero_reshards_total",
            "ZeRO-1 states re-bucketed for a different axis size").inc()
    out = scatter_full_state(gather_full_state(state), like=like)
    codec = like.codec if like.codec is not None else state.codec
    if codec is not None and codec.stateful and state.wire is not None:
        out = ZeroShardedState(
            out.inner, out.plan, out.treedef, out.optimizer,
            wire=codec.reshard_state(state.wire, state.plan, like.plan),
            codec=codec)
    return out
