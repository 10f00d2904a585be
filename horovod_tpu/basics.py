"""Process / topology state — the ``hvd.init()`` surface.

Horovod equivalent: ``horovod/common/basics.py`` (ctypes ``HorovodBasics``,
reference ``basics.py:22-198``) backed by the C API in
``horovod/common/operations.cc:611-732``.

TPU-native redesign
-------------------
Horovod runs **one process per accelerator** and discovers topology from
MPI/Gloo communicators.  JAX on TPU runs **one process per host**, each owning
several chips, with SPMD executing over all of them.  We therefore keep both
notions first-class:

* ``rank()`` / ``size()`` — *process*-level (controller) rank and world size,
  read from the ``HOROVOD_RANK`` / ``HOROVOD_SIZE`` env contract that the
  launcher sets (the same env names Horovod's gloo path uses, reference
  ``horovod/common/gloo/gloo_context.cc:113-157``).
* ``num_devices()`` — the *chip*-level world size (``len(jax.devices())``
  after multi-process initialization), which is what SPMD collectives span.

Multi-host bootstrap: Horovod's gloo rendezvous (HTTP KV full-mesh TCP
bootstrap, reference ``gloo_context.cc:56-76``) maps to
``jax.distributed.initialize(coordinator_address, ...)`` which bootstraps the
PJRT distributed runtime over DCN; the launcher provides
``HOROVOD_COORDINATOR_ADDR``.
"""

from __future__ import annotations

import atexit
import contextlib
import os
import threading
from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np

from horovod_tpu import config
from horovod_tpu.utils.logging import get_logger

log = get_logger(__name__)

# Error message contract, mirroring reference horovod/common/operations.cc:96-100
NOT_INITIALIZED_ERROR = (
    "horovod_tpu has not been initialized; use hvd.init()."
)


class _State:
    """Per-process global state (Horovod: ``HorovodGlobalState``,
    reference ``horovod/common/global_state.h:42-112``).  In the TPU rebuild
    most of that struct (background thread handle, fusion manager, response
    cache...) lives in the native runtime; the Python side holds topology and
    the mesh cache."""

    def __init__(self):
        self.initialized = False
        self.rank = 0
        self.size = 1
        self.local_rank = 0
        self.local_size = 1
        self.cross_rank = 0
        self.cross_size = 1
        self.ranks: Optional[Sequence[int]] = None
        self.mesh_cache = {}
        self.runtime = None       # native runtime handle (horovod_tpu.native)
        self.lock = threading.Lock()


_state = _State()


def _reset_state_locked() -> None:
    """Restore topology fields to their pre-init defaults (caller holds the
    lock)."""
    _state.rank, _state.size = 0, 1
    _state.local_rank, _state.local_size = 0, 1
    _state.cross_rank, _state.cross_size = 0, 1
    _state.ranks = None
    _state.runtime = None
    _state.mesh_cache.clear()
    _state.initialized = False


def _env_int(name: str, default: int) -> int:
    # Registry-checked read (python -m tools.hvdlint, env-registry rule).
    return config.env_int(name, default)


def init(comm=None, ranks: Optional[Sequence[int]] = None) -> None:
    """Initialize horovod_tpu.

    Mirrors ``hvd.init`` (reference ``basics.py:29-61``): may be called with a
    subset of ranks to restrict the collective group.  ``comm`` (an mpi4py
    communicator in the reference) is accepted for API compatibility and, if
    given, must expose ``Get_rank``/``Get_size`` which override the env.

    Topology resolution order:
      1. explicit ``comm``
      2. ``HOROVOD_RANK``/``HOROVOD_SIZE``/``HOROVOD_LOCAL_RANK``/... env
         (set by the ``hvdrun`` launcher; same contract as reference
         ``run/gloo_run.py:211-254``)
      3. ``jax.process_index()``/``jax.process_count()`` (TPU pod metadata)
    """
    # The start-up's host spans and the compile ledger (telemetry/spans.py,
    # "Start-up"); the ledger's listeners are registered once a process.
    from horovod_tpu import telemetry
    telemetry.listen_to_jax()
    with _state.lock, contextlib.ExitStack() as stack:
        if _state.initialized:
            return
        phase = stack.enter_context(telemetry.span("init"))

        coord = config.env_raw("HOROVOD_COORDINATOR_ADDR")
        if coord and config.env_str("HOROVOD_JAX_DISTRIBUTED", "0") == "1":
            # Multi-host JAX bootstrap (replaces gloo full-mesh rendezvous,
            # reference gloo_context.cc:56-157).  Must run before ANY other
            # jax call that would initialize the XLA backend, so no
            # jax.process_count() guard here.  CPU multi-process testing
            # instead uses the native TCP runtime for data movement.
            with telemetry.span("init/distributed", coordinator=coord):
                jax.distributed.initialize(
                    coordinator_address=coord,
                    num_processes=_env_int("HOROVOD_SIZE", 1),
                    process_id=_env_int("HOROVOD_RANK", 0),
                )

        if comm is not None and hasattr(comm, "Get_rank"):
            _state.rank = comm.Get_rank()
            _state.size = comm.Get_size()
            # Derive the LOCAL/CROSS topology the way the reference does
            # (MPI_Comm_split_type COMM_TYPE_SHARED, mpi_controller.cc:25-81);
            # env overrides win, then an mpi4py shared split, then the
            # single-node assumption.
            local_rank = config.env_raw("HOROVOD_LOCAL_RANK")
            local_size = config.env_raw("HOROVOD_LOCAL_SIZE")
            if local_rank is not None and local_size is not None:
                _state.local_rank = int(local_rank)
                _state.local_size = int(local_size)
            elif hasattr(comm, "Split_type"):
                try:
                    from mpi4py import MPI
                    local = comm.Split_type(MPI.COMM_TYPE_SHARED)
                    _state.local_rank = local.Get_rank()
                    _state.local_size = local.Get_size()
                    local.Free()
                except Exception:
                    _state.local_rank = _state.rank
                    _state.local_size = _state.size
            else:
                _state.local_rank = _state.rank
                _state.local_size = _state.size
            _state.cross_rank = _state.rank // max(_state.local_size, 1)
            _state.cross_size = -(-_state.size // max(_state.local_size, 1))
        else:
            # The launcher's env contract wins; jax.process_index() is
            # consulted only without it, because the call initialises the
            # default backend — which, on a host with chips, claims them
            # for this process (docs/running.md, "Ranks and chips").
            rank = _env_int("HOROVOD_RANK", None)
            size = _env_int("HOROVOD_SIZE", None)
            if rank is None or size is None:
                # The first call that starts the XLA backend, unless the
                # caller's jax.devices() did: the TPU runtime's 7-12 s.
                with telemetry.span("init/backend"):
                    index, count = jax.process_index(), jax.process_count()
            _state.rank = index if rank is None else rank
            _state.size = count if size is None else size
            _state.local_rank = _env_int("HOROVOD_LOCAL_RANK", _state.rank)
            _state.local_size = _env_int("HOROVOD_LOCAL_SIZE", _state.size)
            _state.cross_rank = _env_int("HOROVOD_CROSS_RANK",
                                         _state.rank // max(_state.local_size, 1))
            _state.cross_size = _env_int("HOROVOD_CROSS_SIZE",
                                         -(-_state.size // max(_state.local_size, 1)))

        _state.ranks = tuple(ranks) if ranks is not None else None
        if _state.ranks is not None:
            # Rank-subset init (reference operations.cc:613-622): processes
            # outside the subset become inactive no-op members.
            if _state.rank in _state.ranks:
                _state.size = len(_state.ranks)
                _state.rank = list(_state.ranks).index(_state.rank)
            else:
                _state.size = 1
                _state.rank = 0

        _state.runtime = None
        if _state.size > 1:
            from horovod_tpu import native
            runtime = native.Runtime(
                rank=_state.rank,
                size=_state.size,
                local_rank=_state.local_rank,
                local_size=_state.local_size,
            )
            try:
                with telemetry.span("init/native"):
                    runtime.start()
            except Exception:
                # Leave the process cleanly un-initialized so a corrected
                # re-init is possible (the reference instead falls back to a
                # hard ErrorOp; we surface the error).
                _reset_state_locked()
                raise
            _state.runtime = runtime

        _state.initialized = True
        phase.attrs.update(rank=_state.rank, size=_state.size)
        log.debug("initialized: rank=%d size=%d local_rank=%d local_size=%d",
                  _state.rank, _state.size, _state.local_rank,
                  _state.local_size)

    # Record the coordination epoch this rank is operating under — after a
    # failover the merged metrics must show every rank on the new epoch.
    telemetry.gauge(
        "hvd_coord_epoch",
        "Coordinator lease epoch this process is operating under").set(
        float(config.env_int("HOROVOD_COORD_EPOCH")))

    if config.env_raw("HOROVOD_HEALTH_RPC"):
        # The hvdrun health plane is listening: start pushing heartbeats
        # as soon as the worker has a rank (lazy import keeps resilience
        # out of the minimal init path).
        from horovod_tpu import resilience
        resilience.start_heartbeat(rank=_state.rank)


def shutdown() -> None:
    """Shut down horovod_tpu (reference ``basics.py:63-67`` →
    ``horovod_shutdown``, ``operations.cc:624-629``)."""
    if config.env_raw("HOROVOD_HEALTH_RPC"):
        from horovod_tpu import resilience
        resilience.stop_heartbeat()
    with _state.lock:
        if not _state.initialized:
            return
        if _state.runtime is not None:
            _state.runtime.stop()
            _state.runtime = None
        _state.mesh_cache.clear()
        _state.initialized = False


atexit.register(shutdown)


def is_initialized() -> bool:
    return _state.initialized


def _check_initialized() -> None:
    # Reference CheckInitialized: operations.cc:603-609.
    if not _state.initialized:
        raise ValueError(NOT_INITIALIZED_ERROR)


def rank() -> int:
    """Process rank in the job (reference ``basics.py:110-118``)."""
    _check_initialized()
    return _state.rank


def size() -> int:
    """Number of processes in the job (reference ``basics.py:99-108``)."""
    _check_initialized()
    return _state.size


def local_rank() -> int:
    """Rank within this host (reference ``basics.py:120-129``)."""
    _check_initialized()
    return _state.local_rank


def local_size() -> int:
    """Processes on this host (reference ``basics.py:131-139``)."""
    _check_initialized()
    return _state.local_size


def cross_rank() -> int:
    """Node index (reference LOCAL/CROSS communicators, ``common.h:105-109``)."""
    _check_initialized()
    return _state.cross_rank


def cross_size() -> int:
    _check_initialized()
    return _state.cross_size


def world_epoch() -> int:
    """Membership epoch of the current world: 0 at launch, +1 for every
    in-process reformation this process survived (fail-in-place,
    docs/fault_tolerance.md).  Mirrors the native ``hvd_world_epoch()``
    C API; falls back to ``HOROVOD_WORLD_EPOCH`` when the native
    runtime is not loaded (size-1 worlds)."""
    _check_initialized()
    if _state.runtime is not None:
        epoch = _state.runtime.world_epoch()
        if epoch is not None:
            return int(epoch)
    return config.env_int("HOROVOD_WORLD_EPOCH", 0) or 0


class Topology(NamedTuple):
    """The job's host→slots map plus this rank's place in it — the Python
    face of the launcher's ``HOROVOD_TOPOLOGY`` export (the LOCAL/CROSS
    communicator hierarchy of reference ``common.h:105-109`` as data).

    ``hosts`` is in rank order (host-major allocation); ``leaders`` holds
    the global rank of each host's slot 0 — the one-rank-per-host CROSS
    set — and ``local_group`` the global ranks sharing this rank's host.
    Both planes consume it: the eager data plane's 2-level rings and
    ``topology.build_mesh``'s automatic ``("dcn", "ici")`` shape.
    """
    hosts: Tuple[Tuple[str, int], ...]   # ((hostname, slots), ...)
    hostname: str                        # this rank's host ("" if unknown)
    leaders: Tuple[int, ...]             # global rank of slot 0 per host
    local_group: Tuple[int, ...]         # global ranks on this host
    rank: int
    size: int
    local_rank: int
    local_size: int
    cross_rank: int
    cross_size: int

    @property
    def num_hosts(self) -> int:
        return len(self.hosts)

    @property
    def leader(self) -> int:
        """This host's leader (global rank of local slot 0)."""
        return self.local_group[0] if self.local_group else self.rank

    @property
    def is_leader(self) -> bool:
        return self.local_rank == 0


def _build_topology(rank: int, size: int, local_rank: int, local_size: int,
                    cross_rank: int, cross_size: int) -> Topology:
    """Resolve the host map: the launcher's ``HOROVOD_TOPOLOGY`` when it
    matches the live world size, else a uniform synthesis from the
    LOCAL/CROSS env contract.  The mismatch guard matters for elastic
    jobs: the launcher re-exports the string on every attempt, but a
    worker that mutated HOROVOD_SIZE itself (tests do) must not inherit a
    stale host list."""
    spec = config.env_str("HOROVOD_TOPOLOGY", "").strip()
    hosts: list = []
    if spec:
        for part in spec.split(","):
            part = part.strip()
            if not part:
                continue
            if ":" in part:
                name, slots = part.rsplit(":", 1)
                hosts.append((name, int(slots)))
            else:
                hosts.append((part, 1))
        if sum(s for _, s in hosts) != size:
            hosts = []
    if not hosts:
        # Uniform block synthesis (rank = host*local_size + local_rank):
        # cross_size hosts of local_size slots, last host taking the
        # remainder of a non-divisible world.
        name = config.env_str("HOROVOD_HOSTNAME", "")
        n_hosts = max(cross_size, 1)
        for h in range(n_hosts):
            slots = min(local_size, size - h * local_size) \
                if local_size > 0 else size
            if slots <= 0:
                break
            hosts.append((name, slots))
    leaders, starts = [], []
    base = 0
    for _, slots in hosts:
        leaders.append(base)
        starts.append(base)
        base += slots
    # Locate this rank's host block by rank offset.
    host_idx, host_start, host_slots = 0, 0, size
    for i, (_, slots) in enumerate(hosts):
        if starts[i] <= rank < starts[i] + slots:
            host_idx, host_start, host_slots = i, starts[i], slots
            break
    hostname = hosts[host_idx][0] if hosts else \
        config.env_str("HOROVOD_HOSTNAME", "")
    local_group = tuple(range(host_start, host_start + host_slots))
    return Topology(
        hosts=tuple(hosts), hostname=hostname, leaders=tuple(leaders),
        local_group=local_group, rank=rank, size=size,
        local_rank=local_rank, local_size=local_size,
        cross_rank=cross_rank, cross_size=cross_size)


def topology() -> Topology:
    """The discovered job topology (hosts, leaders, local group) — see
    :class:`Topology`.  Rebuilt on every call from the current state +
    environment, so an elastic restart's re-exported ``HOROVOD_TOPOLOGY``
    is picked up by the re-initialized worker."""
    _check_initialized()
    return _build_topology(_state.rank, _state.size, _state.local_rank,
                           _state.local_size, _state.cross_rank,
                           _state.cross_size)


class CoordinatorInfo(NamedTuple):
    """Identity of the control-plane coordinator as last exported by the
    launcher (``HOROVOD_COORD_RANK`` / ``_EPOCH`` / ``_ELECTIONS``).  After
    a failover the coordinator is no longer rank 0; ``epoch`` increments
    on every re-election so responses from a dead epoch are discardable."""
    rank: int
    epoch: int
    elections: int


def coordinator() -> CoordinatorInfo:
    """The current coordinator identity (rank, lease epoch, election
    count).  Read fresh from the environment on every call — the launcher
    re-exports the trio on each elastic restart attempt, so a worker
    re-initialized after a failover sees the new epoch without any
    collective.  Usable before ``hvd.init()``; defaults to the static
    rank-0 coordinator of a never-failed job."""
    return CoordinatorInfo(
        rank=config.env_int("HOROVOD_COORD_RANK"),
        epoch=config.env_int("HOROVOD_COORD_EPOCH"),
        elections=config.env_int("HOROVOD_COORD_ELECTIONS"))


def _topology_unchecked() -> Topology:
    """Env-only topology probe for callers that may run before
    ``hvd.init()`` (``topology.build_mesh``'s automatic hybrid shape).
    Falls back to a single-host view when nothing is exported."""
    if _state.initialized:
        return topology()
    rank = _env_int("HOROVOD_RANK", 0)
    size = _env_int("HOROVOD_SIZE", 1)
    local_size = _env_int("HOROVOD_LOCAL_SIZE", size)
    return _build_topology(
        rank, size, _env_int("HOROVOD_LOCAL_RANK", rank), local_size,
        _env_int("HOROVOD_CROSS_RANK", rank // max(local_size, 1)),
        _env_int("HOROVOD_CROSS_SIZE",
                 -(-size // max(local_size, 1))))


def num_devices() -> int:
    """Chip-level world size — what SPMD collectives span.  No reference
    equivalent (Horovod is one-process-per-device); on TPU this is the number
    a Horovod user would call ``size()``."""
    _check_initialized()
    return len(jax.devices())


def local_devices():
    _check_initialized()
    return jax.local_devices()


def mesh(axes=None, shape=None):
    """Return (and cache) the device mesh for SPMD collectives.

    Default: a 1-D mesh named ``('data',)`` over all devices — the TPU
    equivalent of Horovod's single global communicator
    (``common.h:105-109`` GLOBAL).  Pass ``axes``/``shape`` for hybrid
    layouts, e.g. ``axes=('replica', 'data')`` with
    ``shape=(num_slices, chips_per_slice)`` — the LOCAL/CROSS (ICI/DCN)
    hierarchy of reference ``nccl_operations.cc:151-346`` expressed as mesh
    axes.  See :mod:`horovod_tpu.parallel.hierarchical`.
    """
    _check_initialized()
    from horovod_tpu.topology import build_mesh
    axes = tuple(axes) if axes is not None else ("data",)
    shape = tuple(shape) if shape is not None else None
    key = (axes, shape)
    m = _state.mesh_cache.get(key)
    if m is None:
        m = build_mesh(axes=axes, shape=shape)
        _state.mesh_cache[key] = m
    return m


def runtime():
    """The native eager runtime, or None in single-process mode."""
    _check_initialized()
    return _state.runtime


# ---------------------------------------------------------------------------
# Build-capability introspection (reference basics.py:141-198,
# operations.cc:651-732).  In this build there is exactly one backend: TPU/XLA.
# ---------------------------------------------------------------------------

def mpi_threads_supported() -> bool:
    return False


def mpi_built() -> bool:
    return False


def mpi_enabled() -> bool:
    return False


def gloo_built() -> bool:
    return False


def gloo_enabled() -> bool:
    return False


def nccl_built() -> bool:
    return False


def ddl_built() -> bool:
    return False


def mlsl_built() -> bool:
    return False


def tpu_built() -> bool:
    """True: XLA/ICI collectives are compiled into this build."""
    return True


def tpu_enabled() -> bool:
    return True
