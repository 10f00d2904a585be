"""Device-mesh construction — the TPU replacement for communicators.

Horovod's communicator topology is GLOBAL / LOCAL (intra-node) / CROSS
(one-rank-per-node) (reference ``horovod/common/common.h:105-109``,
``mpi_context.h:78-87``), built from MPI ``COMM_TYPE_SHARED`` splits
(``mpi_controller.cc:25-81``).  On TPU the same hierarchy is *mesh axes*:
the fast axis rides ICI within a slice, the slow axis rides DCN across
slices/hosts.  XLA then lowers ``psum`` over either axis to the right
interconnect — the explicit two-level dance of
``NCCLHierarchicalAllreduce`` (``nccl_operations.cc:151-346``) becomes a
sharding annotation.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.experimental import mesh_utils
from jax.sharding import Mesh

from horovod_tpu import telemetry


def build_mesh(axes: Sequence[str] = ("data",),
               shape: Optional[Tuple[int, ...]] = None,
               devices=None) -> Mesh:
    """Build a :class:`jax.sharding.Mesh` over ``devices``.

    * 1 axis, no shape: all devices on one axis (GLOBAL communicator).
    * N axes + shape: lay devices out on that grid.  On chips
      ``mesh_utils`` maps the axes onto the physical topology (for
      devices spanning several slices the leading axis maps to DCN and
      the trailing axes to ICI) and any failure there is raised; only
      CPU devices are reshaped in rank order.
    """
    with telemetry.span("build_mesh", axes=tuple(axes)) as phase:
        mesh = _build_mesh(axes, shape, devices)
        phase.attrs.update(shape=tuple(mesh.devices.shape),
                           devices=int(mesh.devices.size),
                           platform=mesh.devices.flat[0].platform)
        return mesh


def _build_mesh(axes, shape, devices) -> Mesh:
    devices = list(jax.devices()) if devices is None else list(devices)
    n = len(devices)
    axes = tuple(axes)
    if shape is None:
        if axes == ("dcn", "ici"):
            # Derive the hybrid shape from the launcher-discovered
            # topology: dcn = number of hosts, ici = devices per host.
            # hvd.topology() falls back to a single host when the job was
            # not launched through hvdrun, which degenerates to (1, n) —
            # a flat mesh with a unit DCN axis, still valid for the
            # hierarchical collectives (the dcn psum is a no-op).
            from horovod_tpu import basics as _basics
            topo = _basics._topology_unchecked()
            dcn = max(topo.num_hosts, 1)
            if n % dcn != 0:
                raise ValueError(
                    f"cannot derive ('dcn', 'ici') mesh shape: {n} devices "
                    f"do not divide evenly over {dcn} hosts "
                    f"({topo.hosts}); pass shape= explicitly")
            shape = (dcn, n // dcn)
        elif len(axes) != 1:
            raise ValueError(f"shape required for multi-axis mesh {axes}")
        else:
            shape = (n,)
    want = int(np.prod(shape))
    if want < n:
        # Underfilled meshes take a device prefix — the launcher's rank
        # order is contiguous, so a prefix is the natural sub-communicator
        # (mirrors the reference's rank-subset init, ``basics.py:29-61``).
        # Warn loudly: an accidental undersized shape would silently
        # exclude devices from gradient averaging.
        import warnings
        warnings.warn(
            f"build_mesh: shape {shape} covers {want} of {n} available "
            f"devices; using the first {want} (rank-order prefix)",
            stacklevel=3)
        devices = devices[:want]
        n = want
    if want != n:
        raise ValueError(
            f"mesh shape {shape} does not cover {n} devices")

    if devices[0].platform == "cpu":
        # Virtual CPU devices (forced host platform count) carry no
        # topology; a plain reshape preserves the launcher's rank order.
        return Mesh(np.asarray(devices).reshape(shape), axes)
    # Real chips: let mesh_utils lay the axes out on the physical
    # topology, and let its errors surface — a rank-order reshape here
    # would silently put a mesh axis on the wrong links.
    if len(axes) > 1 and len({getattr(d, "slice_index", 0)
                              for d in devices}) > 1:
        # Devices span several slices: the leading axis rides DCN.
        # (Never run on multi-slice hardware; the previous call passed
        # shapes of unequal rank and its error was swallowed.)
        dev_array = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=(1,) + tuple(shape[1:]),
            dcn_mesh_shape=(shape[0],) + (1,) * (len(shape) - 1),
            devices=devices)
    else:
        dev_array = mesh_utils.create_device_mesh(shape, devices=devices)
    return Mesh(dev_array, axes)


def data_axis(mesh: Mesh) -> str:
    """The axis gradients are averaged over (the GLOBAL communicator
    equivalent): by convention the axis named 'data', else the last axis."""
    return "data" if "data" in mesh.axis_names else mesh.axis_names[-1]


def mesh_size(mesh: Mesh, axis=None) -> int:
    if axis is None:
        return int(np.prod(list(mesh.shape.values())))
    if isinstance(axis, (tuple, list)):
        return int(np.prod([mesh.shape[a] for a in axis]))
    return mesh.shape[axis]


def exec_on_tpu(x) -> bool:
    """Whether the mesh actually EXECUTING this computation is TPU.

    ``jax.default_backend()`` is the wrong question inside shard_map: on
    a TPU host driving a CPU/virtual mesh it answers "tpu" and would
    select a TPU-only lowering (Pallas kernel, ragged-all-to-all HLO)
    for a CPU computation.  The abstract mesh attached to the tracer's
    sharding carries the real device kind of the mesh the shard_map runs
    on.  Shared by the flash-attention kernel gates and
    ``alltoall_ragged``'s primitive/dense-twin routing.

    The attribute chain is internal JAX surface (pinned by
    ``tests/test_basics.py::test_exec_on_tpu_attribute_chain`` against
    the jax version ``pyproject.toml`` pins); a JAX that renames a link
    raises ``AttributeError`` here rather than routing on a guess.
    """
    ad = jax.typeof(x).sharding.mesh.abstract_device
    if ad is not None:
        return "tpu" in ad.device_kind.lower()
    # Outside shard_map the abstract mesh is empty and the computation
    # runs on the default backend.
    return jax.default_backend() == "tpu"
