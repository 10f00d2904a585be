"""horovod_tpu — a TPU-native distributed training framework.

A ground-up rebuild of the capabilities of Horovod (reference:
``/root/reference``, see ``SURVEY.md``) designed for TPU hardware:

* Collectives (``allreduce`` / ``allgather`` / ``broadcast`` /
  ``reducescatter`` / ``alltoall``) execute as XLA collectives
  (``lax.psum`` / ``lax.all_gather`` / ``lax.ppermute`` / ``lax.all_to_all``)
  over a :class:`jax.sharding.Mesh` spanning ICI (intra-slice) and DCN
  (cross-slice) axes — not NCCL/MPI rings.
* Under ``jit`` / ``shard_map`` the coordination problem Horovod solves with a
  C++ background thread (reference ``horovod/common/operations.cc:303-498``)
  disappears: SPMD guarantees every device issues the same collectives in the
  same order.  The asynchronous, name-negotiated eager path (for op-by-op
  frameworks like PyTorch) survives as a native C++ runtime with a TCP
  controller — see ``horovod_tpu/native``.
* The user-facing API keeps Horovod's contract
  (reference ``horovod/tensorflow/__init__.py``, ``horovod/torch/__init__.py``):
  ``init``/``rank``/``size``/``local_rank``/``local_size``,
  named collectives, ``DistributedOptimizer``, ``broadcast_parameters``,
  ``Compression`` — so a Horovod user can switch with minimal edits.

Quick start (single host, all local TPU chips)::

    import horovod_tpu as hvd
    hvd.init()
    mesh = hvd.mesh()                       # 1-D 'data' mesh over all chips
    step = hvd.make_training_step(loss_fn, optimizer, mesh)
"""

import time as _time

# The ``import`` phase span (telemetry/spans.py, "Start-up"): this file
# top to bottom, on telemetry.clock, closed at the last line.
_import_t0 = _time.monotonic()

from horovod_tpu import basics as _basics
from horovod_tpu.basics import (
    init,
    shutdown,
    is_initialized,
    rank,
    size,
    local_rank,
    local_size,
    cross_rank,
    cross_size,
    world_epoch,
    num_devices,
    local_devices,
    mesh,
    topology,
    Topology,
    coordinator,
    CoordinatorInfo,
    mpi_threads_supported,
    mpi_built,
    mpi_enabled,
    gloo_built,
    gloo_enabled,
    nccl_built,
    ddl_built,
    mlsl_built,
    tpu_built,
    tpu_enabled,
)
from horovod_tpu.ops.collective import (
    Average,
    Sum,
    Adasum,
    Min,
    Max,
    allreduce,
    allreduce_,
    allreduce_async,
    allreduce_async_,
    grouped_allreduce,
    allgather,
    allgather_async,
    allgather_object,
    broadcast,
    broadcast_,
    broadcast_async,
    broadcast_async_,
    broadcast_object,
    reducescatter,
    alltoall,
    alltoall_ragged,
    synchronize,
    poll,
    join,
    barrier,
    ProcessSet,
    add_process_set,
    global_process_set,
)
from horovod_tpu.ops.compression import Compression, resolve_codec
from horovod_tpu import checkpoint  # noqa: F401  (hvd.checkpoint.save/restore)
from horovod_tpu import telemetry  # noqa: F401  (hvd.telemetry.counter/...)
from horovod_tpu.telemetry import metrics_snapshot, startup_report
from horovod_tpu.parallel.data import (
    DistributedOptimizer,
    DistributedGradientTape,
    make_training_step,
    broadcast_parameters,
    broadcast_optimizer_state,
    broadcast_variables,
)
from horovod_tpu.parallel.data import (
    elastic_shard,
    elastic_continuity,
    elastic_transition,
)
from horovod_tpu.parallel.zero import sharded_optimizer, reshard_state
from horovod_tpu import resilience  # noqa: F401  (hvd.resilience.StepGuard/...)
from horovod_tpu.resilience import StepGuard, warm_restore, report_progress

# Importing the `horovod_tpu.topology` SUBMODULE (here or anywhere) sets the
# package attribute "topology" to the module, shadowing the hvd.topology()
# accessor imported above.  Import the submodule once, then rebind the
# accessor LAST: later `from horovod_tpu.topology import ...` statements
# resolve through sys.modules and do not re-set the attribute.
from horovod_tpu import topology as _topology_mod  # noqa: F401
from horovod_tpu.basics import topology  # noqa: F811

__version__ = "0.5.0"

__all__ = [
    # lifecycle / topology
    "init", "shutdown", "is_initialized",
    "rank", "size", "local_rank", "local_size", "cross_rank", "cross_size",
    "world_epoch",
    "num_devices", "local_devices", "mesh", "topology", "Topology",
    "coordinator", "CoordinatorInfo",
    "mpi_threads_supported",
    "mpi_built", "mpi_enabled", "gloo_built", "gloo_enabled",
    "nccl_built", "ddl_built", "mlsl_built", "tpu_built", "tpu_enabled",
    # collectives
    "Average", "Sum", "Adasum", "Min", "Max",
    "allreduce", "allreduce_", "allreduce_async", "allreduce_async_",
    "grouped_allreduce",
    "allgather", "allgather_async", "allgather_object",
    "broadcast", "broadcast_", "broadcast_async", "broadcast_async_",
    "broadcast_object",
    "reducescatter", "alltoall", "alltoall_ragged",
    "synchronize", "poll", "join",
    # observability
    "telemetry", "metrics_snapshot", "startup_report",
    # training
    "Compression", "resolve_codec", "checkpoint",
    "DistributedOptimizer", "DistributedGradientTape", "make_training_step",
    "sharded_optimizer", "reshard_state",
    "broadcast_parameters", "broadcast_optimizer_state", "broadcast_variables",
    # elastic continuity
    "elastic_shard", "elastic_continuity", "elastic_transition",
    # resilience
    "resilience", "StepGuard", "warm_restore", "report_progress",
]

telemetry.record_phase("import", _import_t0, telemetry.clock())
