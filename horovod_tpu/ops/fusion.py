"""Tensor fusion for the SPMD plane.

Horovod equivalent: the fusion buffer
(``horovod/common/fusion_buffer_manager.{h,cc}``: persistent 64 MB scratch,
``operations.cc:379`` default threshold; ``FUSION_BUFFER_ATOMIC_UNIT=64``,
``common.h:92``) plus ``FuseResponses`` (``controller.cc:551-672``) which
batches small tensors into one collective to amortize latency.

TPU-native redesign: under XLA the *latency* motivation partially disappears
(the compiler fuses and schedules collectives), but launching one big
``psum`` over a flat buffer instead of hundreds of tiny ones still wins on
real meshes — fewer collective launches, full ICI payloads.  Because shapes
are static at trace time, fusion here is *ahead-of-time bucketing* of a
gradient pytree: group leaves by dtype into buckets up to the threshold,
concatenate into one flat vector per bucket, one ``psum`` per bucket,
then split back.  No runtime buffer management is needed — XLA owns memory.

Fusion v2 adds the sharded-update wire format (ZeRO-1, Rajbhandari et al.
SC'20; Xu et al. 2020 automatic weight-update sharding): the same bucketing
walk, but each flat bucket is padded to an axis-size multiple and
**reduce-scattered** (``lax.psum_scatter``) so every rank keeps only its
1/N shard — same ring wire bytes as an allreduce's reduce-scatter phase —
and re-materialized later with ``lax.all_gather`` + unpad/split
(:func:`fused_all_gather`).  :mod:`horovod_tpu.parallel.zero` builds the
sharded optimizer update on top of exactly this pair.
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu import telemetry
from horovod_tpu.telemetry import scopes
from horovod_tpu.utils.logging import get_logger

log = get_logger(__name__)

# Reference default: 64 MB (operations.cc:379); same env knob name.
DEFAULT_FUSION_THRESHOLD = 64 * 1024 * 1024

# Reduce-scatter buckets are additionally CHUNKED at this cap: BENCH_eager
# measured a bandwidth cliff at 64 MB payloads (0.8 -> 0.2 GB/s), so plans
# split any bucket above the cap into several pipeline-friendly chunks.
# 0 disables chunking.
DEFAULT_MAX_BUCKET_BYTES = 32 * 1024 * 1024

_SIZE_SUFFIXES = {
    "": 1, "b": 1,
    "k": 1024, "kb": 1024, "kib": 1024,
    "m": 1024 ** 2, "mb": 1024 ** 2, "mib": 1024 ** 2,
    "g": 1024 ** 3, "gb": 1024 ** 3, "gib": 1024 ** 3,
}

_warned_bad_threshold = False
_warned_bad_cap = False

# Live fusion-threshold provider (adaptive control plane): the native
# runtime registers a callable returning the latest autotuned threshold
# so bucketing follows the tuner instead of freezing the env value at
# import.  None (no provider, or provider returns None) falls back to
# the HOROVOD_FUSION_THRESHOLD env / default path below.
#
# CONTRACT: the provider must return a RANK-AGREED value — the same
# number on every rank at the same point of the (SPMD) Python program.
# Bucketing runs on framework threads at trace time; if two ranks read
# different thresholds they trace DIFFERENT fused programs, which
# desynchronizes the collective streams and hangs the job rather than
# erroring.  ``native.runtime.Runtime`` honors this by serving a value
# latched only inside ``Runtime.sync_tuned_config()`` (a collective),
# never the raw tuner atomic that each rank updates at its own cycle
# tick.
_live_threshold_provider = None


def set_live_threshold_provider(provider) -> None:
    """Register (or clear, with ``None``) the live-threshold source.

    Called by ``native.runtime.Runtime`` on start/stop; anything else
    supplying a dynamic threshold (tests, notebooks) may use it too —
    but every registered provider must honor the rank-agreement
    contract documented on ``_live_threshold_provider``."""
    global _live_threshold_provider
    _live_threshold_provider = provider


def parse_size_bytes(value: str) -> Optional[int]:
    """``"64mb"`` / ``"32MiB"`` / ``"67108864"`` -> bytes, or None when the
    string is not a size.  Decimal multipliers are intentionally absent:
    Horovod's knob has always been binary (64 MB == 2**26)."""
    m = re.fullmatch(r"\s*(\d+(?:\.\d+)?)\s*([a-zA-Z]*)\s*", str(value))
    if not m:
        return None
    mult = _SIZE_SUFFIXES.get(m.group(2).lower())
    if mult is None:
        return None
    return int(float(m.group(1)) * mult)


def fusion_threshold_bytes() -> int:
    """The live fusion bucket limit: the rank-agreed autotuned value when
    a native runtime registered a provider (set_live_threshold_provider)
    and has latched one via ``Runtime.sync_tuned_config()``, else
    ``HOROVOD_FUSION_THRESHOLD`` (bytes, or with a ``kb``/``mb``/``MiB``-style
    binary suffix).  An unparseable env value falls back to the 64 MB
    default with a one-time warning — a typo in an env var must not
    surface as a ``ValueError`` deep inside a jit trace."""
    global _warned_bad_threshold
    if _live_threshold_provider is not None:
        try:
            live = _live_threshold_provider()
        except Exception:
            live = None   # a dying runtime must not break bucketing
        if live is not None and live > 0:
            return int(live)
    v = os.environ.get("HOROVOD_FUSION_THRESHOLD")
    if not v:
        return DEFAULT_FUSION_THRESHOLD
    parsed = parse_size_bytes(v)
    if parsed is None:
        if not _warned_bad_threshold:
            _warned_bad_threshold = True
            log.warning(
                "HOROVOD_FUSION_THRESHOLD=%r is not a byte size (expected "
                "e.g. 67108864, 64mb or 32MiB); using the default %d bytes",
                v, DEFAULT_FUSION_THRESHOLD)
        return DEFAULT_FUSION_THRESHOLD
    return parsed


def max_bucket_bytes() -> int:
    """The reduce-scatter bucket chunking cap from
    ``HOROVOD_MAX_BUCKET_BYTES`` (same size grammar as the fusion
    threshold; ``0`` disables chunking).  Unparseable values fall back to
    the 32 MB default with a one-time warning."""
    global _warned_bad_cap
    v = os.environ.get("HOROVOD_MAX_BUCKET_BYTES")
    if not v:
        return DEFAULT_MAX_BUCKET_BYTES
    parsed = parse_size_bytes(v)
    if parsed is None:
        if not _warned_bad_cap:
            _warned_bad_cap = True
            log.warning(
                "HOROVOD_MAX_BUCKET_BYTES=%r is not a byte size (expected "
                "e.g. 33554432, 32mb or 16MiB); using the default %d bytes",
                v, DEFAULT_MAX_BUCKET_BYTES)
        return DEFAULT_MAX_BUCKET_BYTES
    return parsed


def record_collective_bytes(kind: str, codec: str, nbytes: int,
                            level: Optional[str] = None) -> None:
    """Trace-time wire accounting for SPMD collectives: the LOGICAL payload
    bytes a collective moves per invocation (per rank), labeled by the wire
    codec that produced them.  Like all fusion telemetry this counts
    trace-time decisions — per-step traffic is trace counts x payload — so
    two runs of the same program are directly comparable: the none-codec /
    int8 ratio of ``hvd_collective_bytes_total`` IS the wire compression
    ratio.  ``level`` ("ici"/"dcn") labels the leg of a two-level
    hierarchical collective; flat collectives omit it."""
    if nbytes and telemetry.enabled():
        labels = dict(plane="spmd", kind=kind, codec=codec)
        if level is not None:
            labels["level"] = level
        telemetry.counter(
            "hvd_collective_bytes_total",
            "Logical wire payload bytes of SPMD collectives (trace-time)",
            **labels).inc(int(nbytes))


def _vma_key(leaf):
    """Sorted tuple of mesh axes the (traced) leaf varies over.

    Fusion buckets must be vma-homogeneous: concatenating a TP-sharded
    gradient (varying over 'model') with a replicated one would pvary the
    whole bucket and the replicated leaf could no longer be returned
    through a P() out_spec."""
    try:
        return tuple(sorted(jax.typeof(leaf).vma))
    except AttributeError:
        return ()


def _bucket_leaves(leaves, threshold: int):
    """Group leaf indices into buckets: same dtype + same vma, cumulative
    nbytes under threshold (mirrors the dtype-homogeneous fusion walk with
    look-ahead in ``controller.cc:551-672``; we sort by (dtype, vma)
    instead of looking ahead)."""
    keys = [(str(leaves[i].dtype), _vma_key(leaves[i]))
            for i in range(len(leaves))]
    order = sorted(range(len(leaves)), key=lambda i: (keys[i], i))
    buckets: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    cur_key = None
    for i in order:
        leaf = leaves[i]
        nbytes = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        if cur and (keys[i] != cur_key or cur_bytes + nbytes > threshold):
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_key = keys[i]
        cur_bytes += nbytes
    if cur:
        buckets.append(cur)
    return buckets


def _record_buckets(kind: str, tensors, buckets, pad_bytes: int = 0):
    """Trace-time fusion telemetry.  Bucketing happens when the step is
    TRACED (shapes are static under jit), so these count fusion DECISIONS,
    not per-step traffic — per-step wire volume is trace counts x bucket
    bytes."""
    if not telemetry.enabled():
        return
    telemetry.counter(
        "hvd_fusion_requests_total",
        "Fusion walks (trace-time bucketing decisions)", kind=kind).inc()
    telemetry.counter(
        "hvd_fusion_buckets_total",
        "Fusion buckets produced across all fusion walks", kind=kind).inc(
        len(buckets))
    telemetry.counter(
        "hvd_fusion_tensors_total",
        "Tensors routed through the fusion walks", kind=kind).inc(
        len(tensors))
    hist = telemetry.histogram(
        "hvd_fusion_bucket_bytes",
        "Per-bucket payload size produced by the fusion walk",
        bounds=telemetry.DEFAULT_BYTE_BUCKETS)
    for bucket in buckets:
        hist.observe(float(sum(
            int(np.prod(tensors[i].shape)) * tensors[i].dtype.itemsize
            for i in bucket)))
    if pad_bytes:
        telemetry.counter(
            "hvd_fusion_pad_bytes_total",
            "Bytes of axis-size padding added to reduce-scatter buckets "
            "(padding waste)", kind=kind).inc(pad_bytes)


def _record_plan(kind: str, plan: "ReduceScatterPlan") -> None:
    """Plan-based twin of :func:`_record_buckets` for the span wire format."""
    if not telemetry.enabled():
        return
    telemetry.counter(
        "hvd_fusion_requests_total",
        "Fusion walks (trace-time bucketing decisions)", kind=kind).inc()
    telemetry.counter(
        "hvd_fusion_buckets_total",
        "Fusion buckets produced across all fusion walks", kind=kind).inc(
        len(plan.buckets))
    telemetry.counter(
        "hvd_fusion_tensors_total",
        "Tensors routed through the fusion walks", kind=kind).inc(
        plan.n_leaves)
    hist = telemetry.histogram(
        "hvd_fusion_bucket_bytes",
        "Per-bucket payload size produced by the fusion walk",
        bounds=telemetry.DEFAULT_BYTE_BUCKETS)
    for b in range(len(plan.buckets)):
        hist.observe(float(plan.bucket_size(b) *
                           plan.bucket_dtype(b).itemsize))
    pad = plan.total_pad_bytes()
    if pad:
        telemetry.counter(
            "hvd_fusion_pad_bytes_total",
            "Bytes of axis-size padding added to reduce-scatter buckets "
            "(padding waste)", kind=kind).inc(pad)


def fused_psum(tensors: Sequence[jax.Array], axis_name,
               mean: bool = True, threshold: int | None = None,
               prescale_factor: float = 1.0, postscale_factor: float = 1.0):
    """Allreduce a list of (traced) tensors with bucketed fusion.

    Returns reduced tensors in the original order.  ``prescale_factor`` /
    ``postscale_factor`` are applied to the flat bucket around the wire
    reduction (one multiply per bucket, not per leaf) — the fused rendition
    of ``allreduce``'s scaling knobs.
    """
    tensors = list(tensors)
    if not tensors:
        return []
    threshold = fusion_threshold_bytes() if threshold is None else threshold
    buckets = _bucket_leaves(tensors, threshold)
    _record_buckets("psum", tensors, buckets)
    record_collective_bytes("psum", "none", sum(
        int(np.prod(t.shape)) * t.dtype.itemsize for t in tensors))
    reduce = lax.pmean if mean else lax.psum
    out: List = [None] * len(tensors)
    for bucket in buckets:
        if len(bucket) == 1:
            i = bucket[0]
            t = tensors[i]
            if prescale_factor != 1.0:
                t = t * prescale_factor
            r = reduce(t, axis_name)
            if postscale_factor != 1.0:
                r = r * postscale_factor
            out[i] = r
            continue
        # One 1-D reshape per leaf, one concat, one reduce, ONE split at
        # precomputed offsets — K reshapes instead of K dynamic-slice-shaped
        # gathers in the emitted trace.
        sizes = [int(np.prod(tensors[i].shape)) for i in bucket]
        offsets = np.cumsum(sizes[:-1]).tolist()
        flat = jnp.concatenate([tensors[i].reshape(-1) for i in bucket])
        if prescale_factor != 1.0:
            flat = flat * prescale_factor
        red = reduce(flat, axis_name)
        if postscale_factor != 1.0:
            red = red * postscale_factor
        for i, part in zip(bucket, jnp.split(red, offsets)):
            out[i] = part.reshape(tensors[i].shape)
    return out


def fused_pytree_mean(tree, axis_name, threshold: int | None = None):
    """Average a gradient pytree across ``axis_name`` with fusion — the core
    of :class:`horovod_tpu.parallel.data.DistributedOptimizer`'s jit path."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    with jax.named_scope(scopes.GRAD_MEAN):
        reduced = fused_psum(leaves, axis_name, mean=True,
                             threshold=threshold)
    return jax.tree_util.tree_unflatten(treedef, reduced)


# ---------------------------------------------------------------------------
# Fusion v2: the reduce-scatter / all-gather pair (the sharded-update wire
# format).  A ring allreduce IS reduce-scatter + all-gather; splitting the
# two phases apart lets the optimizer update run on the 1/N shard in
# between (ZeRO-1) for the same total wire bytes.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ReduceScatterPlan:
    """Static (hashable) description of one fusion walk over a fixed leaf
    list, including the per-bucket padding to an axis-size multiple.

    Built once at trace (or setup) time from leaf shapes; the plan is what
    makes ``fused_reduce_scatter`` -> ``fused_all_gather`` a lossless round
    trip, and what :mod:`horovod_tpu.parallel.zero` uses to keep gradient
    shards, parameter shards and optimizer-state shards aligned.

    Bucket membership is expressed as **spans** ``(leaf, start, stop)`` —
    element ranges of the flattened leaf — so one oversized leaf (or one
    oversized multi-leaf bucket) can be CHUNKED across several buckets
    (``HOROVOD_MAX_BUCKET_BYTES``).  ``lowrank`` marks bucket indices the
    requesting wire codec claimed as whole-leaf low-rank buckets
    (:mod:`horovod_tpu.ops.compression`); those are never chunked.
    """
    buckets: Tuple[Tuple[Tuple[int, int, int], ...], ...]  # spans per bucket
    shapes: Tuple[Tuple[int, ...], ...]        # per-leaf shapes
    dtypes: Tuple[str, ...]                    # per-leaf dtype names
    axis_size: int
    lowrank: Tuple[int, ...] = ()              # codec-claimed bucket indices

    # -- static geometry ---------------------------------------------------
    def leaf_size(self, i: int) -> int:
        return int(np.prod(self.shapes[i]))

    def bucket_size(self, b: int) -> int:
        """Unpadded element count of bucket ``b``."""
        return sum(stop - start for _, start, stop in self.buckets[b])

    def padded_size(self, b: int) -> int:
        """Bucket size rounded up to a multiple of ``axis_size``."""
        n, a = self.bucket_size(b), self.axis_size
        return -(-n // a) * a if n else a  # empty bucket still scatters

    def shard_size(self, b: int) -> int:
        return self.padded_size(b) // self.axis_size

    def pad_elems(self, b: int) -> int:
        return self.padded_size(b) - self.bucket_size(b)

    def bucket_dtype(self, b: int):
        return jnp.dtype(self.dtypes[self.buckets[b][0][0]])

    def bucket_leaf_shape(self, b: int) -> Optional[Tuple[int, ...]]:
        """The original leaf shape when bucket ``b`` is exactly one WHOLE
        leaf (the low-rank codec needs the 2-D geometry back), else None."""
        spans = self.buckets[b]
        if len(spans) != 1:
            return None
        i, start, stop = spans[0]
        if start != 0 or stop != self.leaf_size(i):
            return None
        return self.shapes[i]

    @property
    def n_leaves(self) -> int:
        return len(self.shapes)

    def total_pad_bytes(self) -> int:
        return sum(self.pad_elems(b) * self.bucket_dtype(b).itemsize
                   for b in range(len(self.buckets)))

    def total_padded_bytes(self) -> int:
        """Per-rank logical payload of one reduce-scatter (or all-gather)
        pass over every bucket at wire dtype == bucket dtype."""
        return sum(self.padded_size(b) * self.bucket_dtype(b).itemsize
                   for b in range(len(self.buckets)))

    # -- flat-buffer plumbing ---------------------------------------------
    def concat(self, leaves) -> List[jax.Array]:
        """Leaves -> one padded 1-D buffer per bucket (trace-safe)."""
        if len(leaves) != self.n_leaves:
            raise ValueError(f"plan describes {self.n_leaves} leaves, got "
                             f"{len(leaves)}")
        flats = []
        for b, spans in enumerate(self.buckets):
            parts = []
            for i, start, stop in spans:
                flat_leaf = leaves[i].reshape(-1)
                parts.append(flat_leaf if stop - start == self.leaf_size(i)
                             else flat_leaf[start:stop])
            pad = self.pad_elems(b)
            if pad or not parts:
                parts.append(jnp.zeros((pad if parts else self.padded_size(b),),
                                       self.bucket_dtype(b)))
            flats.append(parts[0] if len(parts) == 1
                         else jnp.concatenate(parts))
        return flats

    def split(self, flats) -> List[jax.Array]:
        """Padded per-bucket 1-D buffers -> leaves in ORIGINAL order."""
        if len(flats) != len(self.buckets):
            raise ValueError(f"plan has {len(self.buckets)} buckets, got "
                             f"{len(flats)} buffers")
        pieces: List[List[Tuple[int, jax.Array]]] = [
            [] for _ in range(self.n_leaves)]
        for b, spans in enumerate(self.buckets):
            flat = flats[b][:self.bucket_size(b)]
            sizes = [stop - start for _, start, stop in spans]
            offsets = np.cumsum(sizes[:-1]).tolist()
            for (i, start, _), part in zip(spans, jnp.split(flat, offsets)):
                pieces[i].append((start, part))
        out: List = []
        for i, segs in enumerate(pieces):
            segs = [part for _, part in sorted(segs, key=lambda t: t[0])]
            flat = segs[0] if len(segs) == 1 else jnp.concatenate(segs)
            out.append(flat.reshape(self.shapes[i]))
        return out

    def shard_slice(self, b: int, flat, index):
        """This rank's shard of bucket ``b``'s full padded buffer (``index``
        may be a traced ``lax.axis_index``)."""
        s = self.shard_size(b)
        return lax.dynamic_slice_in_dim(flat, index * s, s, axis=0)


def _resolve_axis_size(axis_name, axis_size: Optional[int]) -> int:
    if axis_size is not None:
        return int(axis_size)
    names = (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)
    return int(np.prod([lax.axis_size(a) for a in names]))


def _chunk_spans(spans, itemsize: int, cap: int):
    """Split one bucket's span list into chunks of at most ``cap`` bytes
    (element-granular: a span larger than the cap is cut mid-leaf)."""
    cap_elems = max(1, cap // itemsize)
    chunks, cur, cur_elems = [], [], 0
    for leaf, start, stop in spans:
        pos = start
        while pos < stop:
            take = min(stop - pos, cap_elems - cur_elems)
            cur.append((leaf, pos, pos + take))
            pos += take
            cur_elems += take
            if cur_elems == cap_elems:
                chunks.append(cur)
                cur, cur_elems = [], 0
    if cur:
        chunks.append(cur)
    return chunks or [list(spans)]


def make_reduce_scatter_plan(leaves, axis_size: int,
                             threshold: int | None = None,
                             codec=None,
                             cap: int | None = None) -> ReduceScatterPlan:
    """Run the fusion bucketing walk over ``leaves`` (arrays or
    ShapeDtypeStructs) and freeze it, with per-bucket padding geometry for
    an ``axis_size``-way reduce-scatter.

    Buckets larger than ``cap`` bytes (``HOROVOD_MAX_BUCKET_BYTES``,
    default 32 MB, 0 disables) are chunked into multiple buckets — the
    64 MB payload cliff in BENCH_eager.json means several medium
    collectives pipeline better than one giant one.  ``codec`` (a
    :class:`horovod_tpu.ops.compression.BucketCodec`-shaped object) may
    claim whole leaves as dedicated low-rank buckets via its
    ``solo_leaf(shape, dtype)`` hook; claimed buckets are exempt from
    chunking and listed in ``plan.lowrank``.
    """
    leaves = list(leaves)
    threshold = fusion_threshold_bytes() if threshold is None else threshold
    cap = max_bucket_bytes() if cap is None else cap
    solo = [i for i, l in enumerate(leaves)
            if codec is not None
            and codec.solo_leaf(tuple(int(d) for d in l.shape),
                                jnp.dtype(l.dtype))]
    rest = [l for i, l in enumerate(leaves) if i not in solo]
    rest_idx = [i for i in range(len(leaves)) if i not in solo]
    walk = _bucket_leaves(rest, threshold)
    span_buckets = [[(rest_idx[j], 0, int(np.prod(leaves[rest_idx[j]].shape)))
                     for j in bucket] for bucket in walk]
    chunked = 0
    if cap:
        out_buckets = []
        for spans in span_buckets:
            itemsize = jnp.dtype(leaves[spans[0][0]].dtype).itemsize
            nbytes = sum((stop - start) * itemsize for _, start, stop in spans)
            if nbytes > cap:
                chunks = _chunk_spans(spans, itemsize, cap)
                if len(chunks) > 1:
                    chunked += 1
                out_buckets.extend(chunks)
            else:
                out_buckets.append(spans)
        span_buckets = out_buckets
    if chunked and telemetry.enabled():
        telemetry.counter(
            "hvd_fusion_chunked_buckets_total",
            "Fusion buckets split because they exceeded "
            "HOROVOD_MAX_BUCKET_BYTES").inc(chunked)
    lowrank = tuple(range(len(span_buckets), len(span_buckets) + len(solo)))
    for i in solo:
        span_buckets.append([(i, 0, int(np.prod(leaves[i].shape)))])
    return ReduceScatterPlan(
        buckets=tuple(tuple(b) for b in span_buckets),
        shapes=tuple(tuple(int(d) for d in l.shape) for l in leaves),
        dtypes=tuple(str(jnp.dtype(l.dtype)) for l in leaves),
        axis_size=int(axis_size),
        lowrank=lowrank)


def fused_reduce_scatter(tensors: Sequence[jax.Array], axis_name,
                         mean: bool = True, threshold: int | None = None,
                         plan: Optional[ReduceScatterPlan] = None,
                         axis_size: Optional[int] = None):
    """Reduce-scatter a list of (traced) tensors with bucketed fusion.

    Each dtype/vma-homogeneous bucket is flattened, padded to an axis-size
    multiple and ``lax.psum_scatter``-ed, so the caller keeps only this
    rank's ``1/axis_size`` shard of each bucket — half of a ring allreduce,
    wire-byte-wise.  Returns ``(shards, plan)``; feed both to
    :func:`fused_all_gather` to re-materialize the full tensors (the other
    half), or run a sharded optimizer update in between
    (:mod:`horovod_tpu.parallel.zero`).

    ``mean=True`` divides by the axis size (applied on the 1/N shard, where
    it is N-times cheaper than on the full buffer).
    """
    tensors = list(tensors)
    if plan is None:
        n = _resolve_axis_size(axis_name, axis_size)
        plan = make_reduce_scatter_plan(tensors, n, threshold)
    if not tensors:
        return [], plan
    _record_plan("reduce_scatter", plan)
    record_collective_bytes("reduce_scatter", "none",
                            plan.total_padded_bytes())
    shards = []
    inv = 1.0 / plan.axis_size
    for b, flat in enumerate(plan.concat(tensors)):
        shard = lax.psum_scatter(flat, axis_name, scatter_dimension=0,
                                 tiled=True)
        if mean:
            shard = shard * jnp.asarray(inv, shard.dtype)
        shards.append(shard)
    return shards, plan


def fused_hierarchical_reduce_scatter(
        tensors: Sequence[jax.Array], ici_axis: str, dcn_axis: str,
        mean: bool = True, threshold: int | None = None,
        plan: Optional[ReduceScatterPlan] = None,
        axis_size: Optional[int] = None):
    """Two-level reduce-scatter: intra-slice ``psum_scatter`` over
    ``ici_axis`` then a ``psum`` of the 1/ici shard over ``dcn_axis``, so
    the DCN leg carries 1/ici_size of every bucket's bytes (the mesh twin
    of ``NCCLHierarchicalAllreduce``'s local-RS + cross-allreduce prefix).

    The plan is built over the ICI axis size only — shards stay
    ici-sharded, replicated over DCN — so the returned ``(shards, plan)``
    pair feeds :func:`fused_all_gather` with ``axis_name=ici_axis`` (an
    intra-slice gather; no DCN traffic on the way back).  That makes this
    a drop-in for :func:`fused_reduce_scatter` in ZeRO-1: optimizer state
    is partitioned 1/ici-way per slice, and only the reduce leg crosses
    hosts.  ``mean=True`` folds the full two-level divide into one
    ``1/(ici*dcn)`` multiply on the shard.
    """
    tensors = list(tensors)
    ici = _resolve_axis_size(ici_axis, axis_size)
    dcn = _resolve_axis_size(dcn_axis, None)
    if plan is None:
        plan = make_reduce_scatter_plan(tensors, ici, threshold)
    if not tensors:
        return [], plan
    _record_plan("hier_reduce_scatter", plan)
    record_collective_bytes("hier_reduce_scatter", "none",
                            plan.total_padded_bytes(), level="ici")
    record_collective_bytes("hier_reduce_scatter", "none",
                            plan.total_padded_bytes() // max(ici, 1),
                            level="dcn")
    shards = []
    inv = 1.0 / (plan.axis_size * dcn)
    for b, flat in enumerate(plan.concat(tensors)):
        shard = lax.psum_scatter(flat, ici_axis, scatter_dimension=0,
                                 tiled=True)
        shard = lax.psum(shard, dcn_axis)
        if mean:
            shard = shard * jnp.asarray(inv, shard.dtype)
        shards.append(shard)
    return shards, plan


def fused_all_gather(shards: Sequence[jax.Array],
                     plan: ReduceScatterPlan, axis_name):
    """Inverse of :func:`fused_reduce_scatter`: all-gather every bucket's
    per-rank shard back to the full padded buffer, strip the padding and
    split back into tensors in the ORIGINAL leaf order."""
    shards = list(shards)
    if len(shards) != len(plan.buckets):
        raise ValueError(f"plan has {len(plan.buckets)} buckets, got "
                         f"{len(shards)} shards")
    record_collective_bytes("all_gather", "none", plan.total_padded_bytes())
    flats = [lax.all_gather(s, axis_name, axis=0, tiled=True)
             for s in shards]
    return plan.split(flats)
