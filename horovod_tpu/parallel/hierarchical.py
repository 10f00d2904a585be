"""Hierarchical (two-level) collectives: the ICI/DCN twin of Horovod's
LOCAL/CROSS communicator hierarchy.

Reference equivalent: ``NCCLHierarchicalAllreduce`` (intra-node
reduce-scatter -> cross-node allreduce -> intra-node allgather,
``nccl_operations.cc:151-346``) and ``MPIHierarchicalAllgather``
(``mpi_operations.cc:164-321``), built on the LOCAL/CROSS communicators of
``common.h:105-109``.

On TPU the hierarchy is two mesh axes: a fast intra-slice ICI axis and a
slow cross-slice DCN axis (built with
``mesh_utils.create_hybrid_device_mesh`` — see topology.build_mesh, which
derives the ``("dcn", "ici")`` shape from ``hvd.topology()`` when none is
given).  A plain ``psum`` over both axes already lets XLA pick the
schedule; the explicit reduce-scatter/psum/all-gather decomposition below
pins the bandwidth-optimal pattern: each DCN link carries only 1/ici_size
of the payload.

The gather legs go through :func:`_gather_replicated`, which keeps the
result typed replicated under ``check_vma=True``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu.parallel._vma import vma_of


def _gather_replicated(v, axis: str):
    """``lax.all_gather(v, axis)`` whose result is typed *replicated* over
    ``axis``, so it can flow out of a ``check_vma=True`` shard_map through
    a replicated ``P()`` out_spec.

    all_gather's ring moves each byte once ((n-1)/n of the output per
    link), but under vma tracking its output is typed "varying over
    {axis}" even though every shard is bitwise identical, and jax 0.9.0
    has no public cast back (``lax.pcast`` accepts ``to="varying"``,
    ``"reduced"`` and ``"unreduced"`` only).  So the all_gather is used
    where vma is not tracked (``check_vma=False``), and under
    ``check_vma=True`` the gather is spelled as a masked psum — the one
    collective whose output the checker infers as unvarying — at about
    twice the ring's ICI bytes unless XLA folds the one-hot away.
    """
    out = lax.all_gather(v, axis, axis=0, tiled=True)
    if axis not in vma_of(out):
        return out
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    buf = jnp.zeros((n,) + v.shape, v.dtype).at[idx].set(v)
    return lax.psum(buf, axis).reshape((n * v.shape[0],) + v.shape[1:])


def _record(kind: str, nbytes: int, level: str) -> None:
    from horovod_tpu.ops.fusion import record_collective_bytes
    record_collective_bytes(kind, "none", nbytes, level=level)


def hierarchical_allreduce(x, ici_axis: str, dcn_axis: str,
                           average: bool = False):
    """reduce_scatter(ICI) -> psum(DCN) -> all_gather(ICI), flattened.

    Equivalent to ``psum(x, (ici_axis, dcn_axis))`` but with the cross-slice
    leg carrying 1/ici_size of the bytes (the reference's exact trick:
    nccl_operations.cc:151-346).

    ``average=True`` folds the two-level divide into one ``1/(ici*dcn)``
    multiply applied to the DCN-reduced *shard* — before the ICI gather —
    so the scaling touches 1/ici of the elements the reference's
    divide-after-allreduce would.
    """
    ici = lax.axis_size(ici_axis)
    dcn = lax.axis_size(dcn_axis)
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % ici
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    esize = flat.dtype.itemsize
    # Intra-slice reduce-scatter: each chip ends with 1/ici of the sum.
    shard = lax.psum_scatter(flat, ici_axis, scatter_dimension=0, tiled=True)
    _record("hier_allreduce", flat.shape[0] * esize, "ici")
    # Cross-slice allreduce on the small shard (rides DCN).
    shard = lax.psum(shard, dcn_axis)
    _record("hier_allreduce", shard.size * esize, "dcn")
    if average:
        # Hoisted: one multiply on the 1/ici-size shard, covering both
        # levels.  Integer payloads fall back to the post-gather divide
        # (a 1/(ici*dcn) multiply would truncate to zero).
        if jnp.issubdtype(shard.dtype, jnp.inexact):
            shard = shard * (1.0 / (ici * dcn))
            average = False
    # Intra-slice gather restores the full tensor, replicated over ICI.
    full = _gather_replicated(shard, ici_axis).reshape(-1)
    if pad:
        full = full[:n]
    out = full.reshape(x.shape)
    if average:
        out = out / (ici * dcn)
    return out


def hierarchical_pytree_mean(tree, ici_axis: str, dcn_axis: str):
    """Gradient averaging over a 2-level mesh — the multi-slice form of
    :func:`horovod_tpu.ops.fusion.fused_pytree_mean`."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    sizes = [l.size for l in leaves]
    flat = jnp.concatenate([l.reshape(-1) for l in leaves]) if leaves else None
    if flat is None:
        return tree
    red = hierarchical_allreduce(flat, ici_axis, dcn_axis, average=True)
    out, off = [], 0
    for l, n in zip(leaves, sizes):
        out.append(red[off:off + n].reshape(l.shape))
        off += n
    return jax.tree_util.tree_unflatten(treedef, out)


def hierarchical_allgather(x, ici_axis: str, dcn_axis: str):
    """Two-level dim-0 allgather (reference ``MPIHierarchicalAllgather``,
    ``mpi_operations.cc:164-321``: node-local shared-memory gather + one
    cross-node allgather per node leader).

    Mesh form: gather over the fast ICI axis first, then over DCN.
    Concatenation order is (dcn, ici, local dim 0), matching a flat
    allgather over a mesh whose ICI axis is minor.

    Both legs go through :func:`_gather_replicated`: ``lax.all_gather``
    rings under ``check_vma=False``, the masked psum under
    ``check_vma=True``.
    """
    esize = x.dtype.itemsize
    local = _gather_replicated(x, ici_axis)
    _record("hier_allgather", local.size * esize, "ici")
    out = _gather_replicated(local, dcn_axis)
    _record("hier_allgather", out.size * esize, "dcn")
    return out
