"""Collective operations: allreduce / allgather / broadcast / reducescatter /
alltoall, in both SPMD (jit) and eager (async, name-negotiated) forms.

Horovod equivalents: the op kernels in ``horovod/tensorflow/mpi_ops.cc:276-463``
and ``horovod/torch/mpi_ops_v2.cc:52-235``, the enqueue API
``EnqueueTensorAllreduce/Allgather/Broadcast``
(``horovod/common/operations.cc:736-843``) and the handle/poll model of
``horovod/torch/handle_manager.{h,cc}``.

TPU-native redesign — the two planes
------------------------------------
* **SPMD plane** (the performance path): when a collective is called on a
  *traced* value — inside ``jit`` / ``shard_map`` / ``pmap`` with a mesh axis
  in scope — it lowers directly to the XLA collective
  (``lax.psum`` / ``lax.all_gather`` / ``lax.psum_scatter`` /
  ``lax.all_to_all``).  No queue, no negotiation, no fusion buffer: XLA
  guarantees identical program order on every device, which is the invariant
  Horovod's whole controller exists to establish (design rationale at
  reference ``operations.cc:281-300``).
* **Eager plane** (the compatibility path): on concrete arrays in a
  multi-process job, ops are enqueued by *name* to the native runtime — a C++
  background thread with a TCP controller that negotiates readiness across
  ranks, fuses small tensors, and executes — the faithful heir of
  ``BackgroundThreadLoop``/``ComputeResponseList``
  (``operations.cc:303-550``, ``controller.cc:54-298``).  In a single-process
  job the eager collectives are local arithmetic (a 1-rank ring), matching
  Horovod's 1-process behavior.

Both planes share one user API; ``hvd.allreduce`` does the right thing in
either context.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu import basics, faults, telemetry
from horovod_tpu.utils.logging import get_logger

log = get_logger(__name__)


# ---------------------------------------------------------------------------
# Telemetry for the 1-process local fast path.  Multi-process eager ops are
# recorded at the native-runtime choke point (native/runtime.py::_wait_read),
# which every route — sync, async, split submit/finish — flows through; the
# rt-is-None branches below bypass the runtime entirely, so they record
# here.  The two sites are mutually exclusive: nothing is double-counted.
# ---------------------------------------------------------------------------

def _tstart() -> float:
    """Timestamp ops only when some telemetry consumer exists — the
    disabled path must not even read the clock."""
    if (telemetry.enabled() or telemetry.timeline() is not None
            or telemetry.spans() is not None):
        return telemetry.clock()
    return 0.0


def _record_local(kind: str, name: str, arr, t0: float) -> None:
    if not t0:
        return
    t1 = telemetry.clock()
    nbytes = int(arr.nbytes)
    telemetry.observe_op(kind, max(t1 - t0, 1e-9), nbytes)
    tl = telemetry.timeline()
    if tl is not None:
        tl.record_op(name, kind, t0, t1, t1, nbytes)
    sp = telemetry.spans()
    if sp is not None:
        # Single-process execution: the whole op is one in-process span.
        # The occurrence counter still ticks per name so repeated steps
        # of the same tensor stay distinguishable in the merged trace.
        sp.record(name, "exec", sp.next_seq(name), t0, t1, nbytes)


# ---------------------------------------------------------------------------
# Reduction ops (reference message.h / later horovod.common Average/Sum/Adasum)
# ---------------------------------------------------------------------------

class ReduceOp:
    def __init__(self, name: str, code: int):
        self.name = name
        self.code = code

    def __repr__(self):
        return f"ReduceOp.{self.name}"


Average = ReduceOp("Average", 0)
Sum = ReduceOp("Sum", 1)
# Real Adasum on the eager plane (scaled-projection butterfly in
# native/cc/src/data_plane.cc; Maleki et al. 2020): identical gradients
# combine to themselves, orthogonal ones add.  The SPMD plane raises —
# a mesh-collective Adasum needs a different design than psum, and
# silently substituting the mean would change training semantics.
Adasum = ReduceOp("Adasum", 2)
Min = ReduceOp("Min", 3)
Max = ReduceOp("Max", 4)

# ---------------------------------------------------------------------------
# Process sets (later-Horovod; the v0.18 reference had only the single
# global group, basics.py:29-61 "rank subset" init).  A ProcessSet is a
# simultaneous sub-communicator: collectives with `process_set=ps` involve
# only its member ranks, negotiated and executed concurrently with global
# (and other sets') traffic on the eager plane.  SPMD-plane code should
# build a sub-mesh instead (jax.sharding.Mesh over a device subset).
# ---------------------------------------------------------------------------

class ProcessSet:
    """A registered subset of ranks (reference: later-Horovod
    ``hvd.ProcessSet``).  Create via :func:`add_process_set`."""

    def __init__(self, ranks, set_id=None):
        self.ranks = sorted(int(r) for r in ranks)
        self.id = set_id   # None until registered

    def included(self) -> bool:
        return basics.rank() in self.ranks

    def size(self) -> int:
        return len(self.ranks)

    def rank(self) -> int:
        """This process's position within the set (its "set rank")."""
        try:
            return self.ranks.index(basics.rank())
        except ValueError:
            raise RuntimeError(
                f"rank {basics.rank()} is not a member of process set "
                f"{self.ranks}")

    def __repr__(self):
        return f"ProcessSet(ranks={self.ranks}, id={self.id})"


class _GlobalProcessSet(ProcessSet):
    """The implicit set of all ranks (id 0); size tracks hvd.size()."""

    def __init__(self):
        self.id = 0

    @property
    def ranks(self):
        return list(range(basics.size()))

    def included(self) -> bool:
        return True

    def size(self) -> int:
        return basics.size()

    def rank(self) -> int:
        return basics.rank()


global_process_set = _GlobalProcessSet()


def add_process_set(ranks) -> ProcessSet:
    """Collectively register a new process set; EVERY rank of the job must
    call this with the same ranks (later-Horovod ``add_process_set``
    contract — registration is a collective over the global set).
    Registering an already-registered member list returns a set with its
    existing id."""
    basics._check_initialized()
    ps = ranks if isinstance(ranks, ProcessSet) else ProcessSet(ranks)
    if ps.id == 0:
        return global_process_set
    rt = basics.runtime()
    if rt is None:
        if ps.ranks != [0]:
            raise ValueError(
                f"process set {ps.ranks} is invalid for a 1-process job")
        ps.id = 0
        return ps
    ps.id = rt.add_process_set(ps.ranks)
    return ps


def _reject_spmd_process_set(process_set, ax):
    """SPMD plane has no process sets — a subset request under a bound
    mesh axis must fail loudly, never silently involve the whole axis."""
    if process_set is not None and process_set.id != 0 and _axis_bound(ax):
        raise ValueError(
            "process_set is an eager-plane concept; under shard_map build "
            "a sub-mesh (jax.sharding.Mesh over the member devices) "
            "instead")


def _set_args(process_set):
    """(set_id, set_size) for the eager plane; validates membership."""
    if process_set is None or process_set.id == 0:
        return 0, basics.size()
    if process_set.id is None:
        raise ValueError(
            f"process set {process_set.ranks} is not registered; call "
            "hvd.add_process_set(...) on every rank first")
    if not process_set.included():
        raise RuntimeError(
            f"rank {basics.rank()} is not a member of process set "
            f"{process_set.ranks} and cannot submit collectives on it")
    return process_set.id, process_set.size()


# Error-message contract (reference horovod/common/common.h:155-158).
DUPLICATE_NAME_ERROR_FMT = (
    "Requested to %s a tensor with the same name as another tensor that is "
    "currently being processed.  If you want to request another tensor, use "
    "a different tensor name. Tensor name: %s"
)


def _is_traced(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _axis_bound(axis_name: str) -> bool:
    """True when ``axis_name`` is a live mesh axis in the current trace
    (i.e. we are under ``shard_map``/``pmap``) — the condition under which
    collectives lower to XLA ops instead of the eager runtime."""
    try:
        lax.axis_size(axis_name)
        return True
    except Exception:
        return False


def _plain_jit_fallback(tensor, kind: str):
    """A tracer with no bound mesh axis: user code under plain ``jit``.
    With one process this degenerates to local semantics (identical to the
    eager 1-rank result); with more we cannot reach the runtime from inside
    a traced program, so fail loudly rather than silently not reducing."""
    basics._check_initialized()
    if basics.size() > 1:
        raise RuntimeError(
            f"hvd.{kind} was traced inside jit without a mesh axis in scope "
            f"in a {basics.size()}-process job. Wrap the computation in "
            f"jax.shard_map over hvd.mesh() (SPMD plane), or call {kind} on "
            f"concrete arrays outside jit (eager plane).")
    return tensor


def _resolve_op(op, average):
    """Reconcile the v0.18 ``average=`` bool with the op enum."""
    if op is not None:
        return op
    if average is None or average:
        return Average
    return Sum


def _default_axis(axis_name):
    return "data" if axis_name is None else axis_name


# ---------------------------------------------------------------------------
# Handle manager for the async eager API
# (reference horovod/torch/handle_manager.{h,cc}: int handle -> Status table)
# ---------------------------------------------------------------------------

class _Handle:
    __slots__ = ("id", "name", "event", "result", "error")

    def __init__(self, hid: int, name: str):
        self.id = hid
        self.name = name
        self.event = threading.Event()
        self.result = None
        self.error: Optional[Exception] = None


class HandleManager:
    def __init__(self):
        self._lock = threading.Lock()
        self._next = 0
        self._handles: Dict[int, _Handle] = {}
        self._inflight_names: set = set()

    def allocate(self, name: str, op_kind: str) -> _Handle:
        with self._lock:
            if name in self._inflight_names:
                raise ValueError(DUPLICATE_NAME_ERROR_FMT % (op_kind, name))
            self._inflight_names.add(name)
            h = _Handle(self._next, name)
            self._next += 1
            self._handles[h.id] = h
        telemetry.gauge("hvd_eager_handle_queue_depth",
                        "Async eager handles allocated and not yet "
                        "completed").inc()
        return h

    def complete(self, h: _Handle, result=None, error: Optional[Exception] = None):
        with self._lock:
            h.result = result
            h.error = error
            self._inflight_names.discard(h.name)
        telemetry.gauge("hvd_eager_handle_queue_depth",
                        "Async eager handles allocated and not yet "
                        "completed").dec()
        h.event.set()

    def get(self, hid) -> _Handle:
        if isinstance(hid, _Handle):
            return hid
        with self._lock:
            h = self._handles.get(hid)
        if h is None:
            raise ValueError(f"Handle {hid} was not created or has been cleared")
        return h

    def clear(self, h: _Handle):
        with self._lock:
            self._handles.pop(h.id, None)


_handles = HandleManager()

_name_lock = threading.Lock()
_name_counter = 0


def _auto_name(kind: str, name: Optional[str]) -> str:
    # Reference: ops get node-name-derived names in TF, handle-derived in
    # torch (mpi_ops.py:58-90); we use a per-process counter.
    global _name_counter
    if name is not None:
        return name
    with _name_lock:
        n = _name_counter
        _name_counter += 1
    return f"{kind}.noname.{n}"


def poll(handle) -> bool:
    """Non-blocking completion check (reference ``horovod_torch_poll``,
    ``torch/mpi_ops_v2.cc:222-226``)."""
    return _handles.get(handle).event.is_set()


def synchronize(handle):
    """Block until the async op completes and return its output (reference
    ``torch/mpi_ops.py:429-445`` → ``wait_and_clear``)."""
    h = _handles.get(handle)
    h.event.wait()
    _handles.clear(h)
    if h.error is not None:
        raise h.error
    return h.result


# ---------------------------------------------------------------------------
# Eager execution (concrete arrays)
# ---------------------------------------------------------------------------

def _check_adasum_dtype(arr) -> None:
    """Adasum's projection is defined for floating tensors only; validate
    at the Python layer so the failure is identical at every world size
    (the native plane re-checks, but a size-1 job short-circuits before
    reaching it)."""
    kind = getattr(arr.dtype, "kind", "")
    if kind != "f" and "float" not in str(arr.dtype):  # bf16 has kind 'V'
        raise NotImplementedError(
            f"Adasum is defined for floating-point tensors only "
            f"(got dtype {arr.dtype})")


def _eager_allreduce(x, op: ReduceOp, name: str, prescale_factor,
                     postscale_factor, set_id=0, set_size=None):
    faults.inject("allreduce", name)
    t0 = _tstart()
    rt = basics.runtime()
    arr = np.asarray(x)
    if op is Adasum:
        _check_adasum_dtype(arr)
    if prescale_factor != 1.0:
        arr = arr * prescale_factor
    if rt is None:
        out = arr.copy()
        _record_local("allreduce", name, arr, t0)
    else:
        out = rt.allreduce(name, arr, op.code, set_id=set_id)
    # Adasum's result is the combined vector itself (the native butterfly
    # already applied the projection coefficients) — no divide.
    if op is Average:
        out = out / (set_size if set_size else basics.size())
    if postscale_factor != 1.0:
        out = out * postscale_factor
    return faults.corrupt_output("allreduce", out, name)


# --- split submit/finish pairs (graph-async bindings: submit is the
#     non-blocking native enqueue; finish blocks in hvd_wait.  The token
#     is (native_token_or_None, fallback_result)). -------------------------

def _eager_allreduce_submit(x, op: ReduceOp, name: str, prescale_factor,
                            set_id=0):
    faults.inject("allreduce", name)
    t0 = _tstart()
    rt = basics.runtime()
    arr = np.asarray(x)
    if op is Adasum:
        _check_adasum_dtype(arr)
    if prescale_factor != 1.0:
        arr = arr * prescale_factor
    if rt is None:
        _record_local("allreduce", name, arr, t0)
        return (None, arr.copy())
    return (rt.allreduce_submit(name, arr, op.code, set_id=set_id), None)


def _eager_allreduce_finish(tok, op: ReduceOp, postscale_factor,
                            set_size=None):
    native, done = tok
    out = done if native is None else basics.runtime().allreduce_finish(
        native)
    if op is Average:  # Adasum: combined vector as-is (see _eager_allreduce)
        out = out / (set_size if set_size else basics.size())
    if postscale_factor != 1.0:
        out = out * postscale_factor
    return faults.corrupt_output("allreduce", out)


def _eager_allgather_submit(x, name: str, set_id=0):
    faults.inject("allgather", name)
    t0 = _tstart()
    rt = basics.runtime()
    arr = np.asarray(x)
    if rt is None:
        _record_local("allgather", name, arr, t0)
        return (None, arr.copy())
    return (rt.allgather_submit(name, arr, set_id=set_id), None)


def _eager_allgather_finish(tok):
    native, done = tok
    out = done if native is None else basics.runtime().allgather_finish(
        native)
    return faults.corrupt_output("allgather", out)


def _eager_broadcast_submit(x, root_rank: int, name: str, set_id=0):
    faults.inject("broadcast", name)
    t0 = _tstart()
    rt = basics.runtime()
    arr = np.asarray(x)
    if rt is None:
        if root_rank != 0:
            raise ValueError(
                f"broadcast root_rank {root_rank} out of range for size 1")
        _record_local("broadcast", name, arr, t0)
        return (None, arr.copy())
    return (rt.broadcast_submit(name, arr, root_rank, set_id=set_id), None)


def _eager_broadcast_finish(tok):
    native, done = tok
    out = done if native is None else basics.runtime().broadcast_finish(
        native)
    return faults.corrupt_output("broadcast", out)


def _eager_alltoall_submit(x, splits, name: str, set_id=0):
    faults.inject("alltoall", name)
    rt = basics.runtime()
    if rt is None:
        return (None, _eager_alltoall(x, splits, name, set_id=set_id))
    arr = np.asarray(x)
    return (rt.alltoall_submit(name, arr, splits, set_id=set_id), None)


def _eager_alltoall_finish(tok):
    """Returns (output, received_splits)."""
    native, done = tok
    if native is None:
        return done  # local path already went through corrupt_output
    out, received = basics.runtime().alltoall_finish(native)
    return faults.corrupt_output("alltoall", out), received


def _check_reducescatter_op(op: ReduceOp) -> None:
    """Choke point for EVERY reducescatter route (incl. the torch/TF
    bindings that bypass :func:`reducescatter`): the native plane's ring
    reduce phase would execute Adasum/Min/Max chunks as Sum — fail loudly
    instead of silently substituting (same contract as the reference's
    Sum/Average-only reducescatter)."""
    if op is not Average and op is not Sum:
        raise NotImplementedError(
            f"reducescatter supports op=Average/Sum only (got {op})")


def _eager_reducescatter_submit(x, op: ReduceOp, name: str, set_id=0):
    faults.inject("reducescatter", name)
    t0 = _tstart()
    _check_reducescatter_op(op)
    rt = basics.runtime()
    arr = np.asarray(x)
    if rt is None:
        _record_local("reducescatter", name, arr, t0)
        return (None, arr.copy())
    return (rt.reducescatter_submit(name, arr, op.code, set_id=set_id),
            None)


def _eager_reducescatter_finish(tok, op: ReduceOp, set_size=None):
    native, done = tok
    out = (done if native is None
           else basics.runtime().reducescatter_finish(native))
    if op is Average:
        out = out / (set_size or basics.size())
    return faults.corrupt_output("reducescatter", out)


def _eager_allgather(x, name: str, set_id=0):
    faults.inject("allgather", name)
    t0 = _tstart()
    rt = basics.runtime()
    arr = np.asarray(x)
    if rt is None:
        _record_local("allgather", name, arr, t0)
        return faults.corrupt_output("allgather", arr.copy(), name)
    return faults.corrupt_output(
        "allgather", rt.allgather(name, arr, set_id=set_id), name)


def _eager_broadcast(x, root_rank: int, name: str, set_id=0):
    faults.inject("broadcast", name)
    t0 = _tstart()
    rt = basics.runtime()
    arr = np.asarray(x)
    if rt is None:
        if root_rank != 0:
            raise ValueError(
                f"broadcast root_rank {root_rank} out of range for size 1")
        _record_local("broadcast", name, arr, t0)
        return faults.corrupt_output("broadcast", arr.copy(), name)
    return faults.corrupt_output(
        "broadcast", rt.broadcast(name, arr, root_rank, set_id=set_id),
        name)


def _eager_alltoall(x, splits, name: str, set_id=0):
    """Returns ``(output, received_splits)``; received_splits[r] = dim-0
    rows that came from rank r (later-Horovod alltoall contract)."""
    faults.inject("alltoall", name)
    t0 = _tstart()
    rt = basics.runtime()
    arr = np.asarray(x)
    if rt is None:
        if arr.ndim == 0:
            arr = arr.reshape(1)
        rows = arr.shape[0] if arr.ndim else 1
        if splits is not None:
            sp = np.asarray(splits, np.int64).ravel()
            if sp.size != 1 or sp.sum() != rows:
                raise ValueError(
                    f"alltoall splits {sp.tolist()} do not match first "
                    f"dimension {rows} for size-1 job")
        _record_local("alltoall", name, arr, t0)
        return (faults.corrupt_output("alltoall", arr.copy(), name),
                np.array([rows], np.int64))
    out, received = rt.alltoall(name, arr, splits, set_id=set_id)
    return faults.corrupt_output("alltoall", out, name), received


def _eager_reducescatter(x, op: ReduceOp, name: str, set_id=0,
                         set_size=None):
    faults.inject("reducescatter", name)
    t0 = _tstart()
    _check_reducescatter_op(op)
    rt = basics.runtime()
    arr = np.asarray(x)
    if rt is None:
        _record_local("reducescatter", name, arr, t0)
        out = (arr / (set_size or basics.size()) if op is Average
               else arr.copy())
        return faults.corrupt_output("reducescatter", out, name)
    out = rt.reducescatter(name, arr, op.code, set_id=set_id)
    if op is Average:
        out = out / (set_size or basics.size())
    return faults.corrupt_output("reducescatter", out, name)


_executor = None
_executor_lock = threading.Lock()


def _get_executor():
    """A small shared pool, not thread-per-op: the moral equivalent of the
    single background thread servicing the queue in the reference
    (``operations.cc:303-498``).  A few workers let independent named tensors
    overlap, mirroring multi-stream dispatch."""
    global _executor
    with _executor_lock:
        if _executor is None:
            from concurrent.futures import ThreadPoolExecutor
            _executor = ThreadPoolExecutor(
                max_workers=4, thread_name_prefix="hvd-eager")
        return _executor


def _async_dispatch(fn, kind: str, name: str, to_jnp=True):
    """Submit ``fn`` to the eager worker pool, completing a handle — the
    Python face of the enqueue-with-callback contract (reference
    ``operations.cc:736-843``: enqueue returns immediately, callback fires
    from the background loop)."""
    h = _handles.allocate(name, kind)

    def work():
        try:
            out = fn()
            _handles.complete(h, jnp.asarray(out) if to_jnp else out)
        except Exception as e:  # delivered via synchronize(), like statuses
            _handles.complete(h, error=e)

    _get_executor().submit(work)
    return h


# ---------------------------------------------------------------------------
# Public collectives
# ---------------------------------------------------------------------------

def allreduce(tensor, average=None, name=None, op=None,
              prescale_factor=1.0, postscale_factor=1.0,
              compression=None, axis_name=None, process_set=None):
    """Allreduce across all workers/devices.

    SPMD plane: ``lax.psum``/``pmean`` over ``axis_name`` (default ``'data'``).
    Eager plane: name-negotiated runtime allreduce
    (reference ``EnqueueTensorAllreduce``, ``operations.cc:736-775``).

    ``compression`` (see :class:`horovod_tpu.ops.compression.Compression`)
    casts before the wire and back after, as in reference
    ``tensorflow/__init__.py:38-83``.
    """
    rop = _resolve_op(op, average)
    if compression is not None:
        tensor, ctx = compression.compress(tensor)
    else:
        ctx = None
    ax = _default_axis(axis_name)
    _reject_spmd_process_set(process_set, ax)
    if _axis_bound(ax):
        t = tensor * prescale_factor if prescale_factor != 1.0 else tensor
        if rop is Adasum:
            raise NotImplementedError(
                "op=Adasum is implemented on the eager plane only (native "
                "scaled-projection butterfly); inside an SPMD axis use "
                "op=Average, or run the Adasum reduction through the "
                "eager hvd.allreduce path")
        if rop is Average:
            out = lax.pmean(t, ax)
        elif rop is Sum:
            out = lax.psum(t, ax)
        elif rop is Min:
            out = lax.pmin(t, ax)
        elif rop is Max:
            out = lax.pmax(t, ax)
        else:
            raise ValueError(f"unknown op {rop}")
        if postscale_factor != 1.0:
            out = out * postscale_factor
    elif _is_traced(tensor):
        out = _plain_jit_fallback(tensor, "allreduce")
        scale = prescale_factor * postscale_factor
        if scale != 1.0:
            out = out * scale
    else:
        basics._check_initialized()
        set_id, set_size = _set_args(process_set)
        nm = _auto_name("allreduce", name)
        out = jnp.asarray(_eager_allreduce(
            tensor, rop, nm, prescale_factor, postscale_factor,
            set_id=set_id, set_size=set_size))
    if ctx is not None:
        out = compression.decompress(out, ctx)
    return out


def allreduce_(tensor, average=None, name=None, op=None, **kw):
    """In-place-flavored alias.  JAX arrays are immutable, so this returns the
    reduced value; kept for API parity with reference ``torch/mpi_ops.py``."""
    return allreduce(tensor, average=average, name=name, op=op, **kw)


def allreduce_async(tensor, average=None, name=None, op=None,
                    prescale_factor=1.0, postscale_factor=1.0):
    """Asynchronous eager allreduce returning a handle for
    :func:`synchronize`/:func:`poll` (reference ``torch/mpi_ops.py:58-116``)."""
    basics._check_initialized()
    rop = _resolve_op(op, average)
    nm = _auto_name("allreduce", name)
    return _async_dispatch(
        lambda: _eager_allreduce(np.asarray(tensor), rop, nm,
                                 prescale_factor, postscale_factor),
        "allreduce", nm)


def allreduce_async_(tensor, average=None, name=None, op=None, **kw):
    return allreduce_async(tensor, average=average, name=name, op=op, **kw)


def grouped_allreduce(tensors, average=None, name=None, op=None, axis_name=None,
                      prescale_factor=1.0, postscale_factor=1.0,
                      process_set=None):
    """Reduce a list of tensors as one logical request.  SPMD plane: a single
    fused ``psum`` over the flattened concatenation (the moral equivalent of
    the fusion buffer, reference ``fusion_buffer_manager.{h,cc}``).

    ``prescale_factor``/``postscale_factor``/``process_set`` follow
    :func:`allreduce`: scaling is applied inside the fused path (once per
    flat bucket, around the wire reduction); process sets are an eager-plane
    concept and are rejected inside an SPMD axis exactly like ``allreduce``.
    """
    rop = _resolve_op(op, average)
    if not tensors:
        return []
    ax = _default_axis(axis_name)
    _reject_spmd_process_set(process_set, ax)
    if _axis_bound(ax):
        if rop is Adasum:
            raise NotImplementedError(
                "op=Adasum is implemented on the eager plane only; see "
                "hvd.allreduce")
        from horovod_tpu.ops.fusion import fused_psum
        return fused_psum(tensors, ax, mean=rop is Average,
                          prescale_factor=prescale_factor,
                          postscale_factor=postscale_factor)
    if any(_is_traced(t) for t in tensors):
        out = [_plain_jit_fallback(t, "grouped_allreduce") for t in tensors]
        scale = prescale_factor * postscale_factor
        if scale != 1.0:
            out = [t * scale for t in out]
        return out
    return [allreduce(t, name=f"{_auto_name('grouped', name)}.{i}", op=rop,
                      prescale_factor=prescale_factor,
                      postscale_factor=postscale_factor,
                      process_set=process_set)
            for i, t in enumerate(tensors)]


def allgather(tensor, name=None, axis_name=None, process_set=None):
    """Concatenate each worker's tensor along dim 0 (reference TF op shape fn
    ``tensorflow/mpi_ops.cc:369-391``: first dims may differ, others must
    match).  SPMD plane: ``lax.all_gather(..., tiled=True)``."""
    ax = _default_axis(axis_name)
    _reject_spmd_process_set(process_set, ax)
    if _axis_bound(ax):
        return lax.all_gather(tensor, ax, axis=0, tiled=True)
    if _is_traced(tensor):
        return _plain_jit_fallback(tensor, "allgather")
    basics._check_initialized()
    set_id, _ = _set_args(process_set)
    nm = _auto_name("allgather", name)
    return jnp.asarray(_eager_allgather(tensor, nm, set_id=set_id))


def allgather_async(tensor, name=None):
    basics._check_initialized()
    nm = _auto_name("allgather", name)
    return _async_dispatch(lambda: _eager_allgather(np.asarray(tensor), nm),
                           "allgather", nm)


def allgather_object(obj, name=None):
    """Pickle-based object allgather (parity with later-Horovod
    ``allgather_object``; built on the same variable-dim-0 gather)."""
    import pickle
    basics._check_initialized()
    data = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
    nm = _auto_name("allgather_object", name)
    sizes = _eager_allgather(np.array([data.size], np.int64), nm + ".size")
    gathered = _eager_allgather(data, nm)
    out, off = [], 0
    for s in np.asarray(sizes).ravel():
        out.append(pickle.loads(gathered[off:off + int(s)].tobytes()))
        off += int(s)
    return out


def broadcast(tensor, root_rank=0, name=None, axis_name=None,
              process_set=None):
    """Broadcast from ``root_rank`` to all (reference
    ``EnqueueTensorBroadcast``, ``operations.cc:806-843``).

    SPMD plane: implemented as a masked ``psum`` (``lax`` has no explicit
    collective-broadcast primitive).  Cost note: a ring all-reduce moves
    ~2N bytes per link where an optimal broadcast moves ~N, so this is at
    most 2x the optimal wire cost; in SPMD training broadcast appears
    only at initialization/restore (params are replicated thereafter), so
    the one-time factor is irrelevant in practice, and inside ``jit``
    XLA may simplify the select further.  Steady-state broadcast traffic
    belongs on the eager plane, whose native fan-out broadcast is
    wire-optimal (``data_plane.cc``)."""
    ax = _default_axis(axis_name)
    _reject_spmd_process_set(process_set, ax)
    if _axis_bound(ax):
        idx = lax.axis_index(ax)
        masked = jnp.where(idx == root_rank, tensor,
                           jnp.zeros_like(tensor))
        # psum promotes bool -> int32; restore the caller's dtype so the
        # result aval matches the input (donation/apply_updates safety).
        return lax.psum(masked, ax).astype(jnp.asarray(tensor).dtype)
    if _is_traced(tensor):
        return _plain_jit_fallback(tensor, "broadcast")
    basics._check_initialized()
    set_id, _ = _set_args(process_set)
    nm = _auto_name("broadcast", name)
    return jnp.asarray(_eager_broadcast(tensor, root_rank, nm,
                                        set_id=set_id))


def broadcast_(tensor, root_rank=0, name=None, **kw):
    return broadcast(tensor, root_rank=root_rank, name=name, **kw)


def broadcast_async(tensor, root_rank=0, name=None):
    basics._check_initialized()
    nm = _auto_name("broadcast", name)
    return _async_dispatch(
        lambda: _eager_broadcast(np.asarray(tensor), root_rank, nm),
        "broadcast", nm)


def broadcast_async_(tensor, root_rank=0, name=None):
    return broadcast_async(tensor, root_rank=root_rank, name=name)


def broadcast_object(obj, root_rank=0, name=None):
    """Pickle-based broadcast, used for optimizer state / RNG / config
    (reference ``torch/__init__.py:287-403`` wraps scalars in tensors; we
    ship pickled bytes with a size prologue)."""
    import pickle
    basics._check_initialized()
    nm = _auto_name("broadcast_object", name)
    if basics.rank() == root_rank:
        data = np.frombuffer(pickle.dumps(obj), dtype=np.uint8)
        sz = np.array([data.size], np.int64)
    else:
        data = np.zeros(0, np.uint8)
        sz = np.zeros(1, np.int64)
    sz = _eager_broadcast(sz, root_rank, nm + ".size")
    n = int(np.asarray(sz).ravel()[0])
    if basics.rank() != root_rank:
        data = np.zeros(n, np.uint8)
    data = _eager_broadcast(data, root_rank, nm)
    return pickle.loads(np.asarray(data).tobytes())


def reducescatter(tensor, op=None, name=None, axis_name=None,
                  process_set=None):
    """Reduce then scatter along dim 0.  SPMD plane: ``lax.psum_scatter``.
    Not in the v0.18 reference (its collectives are only
    allreduce/allgather/broadcast, ``message.h:47-49``) but the clean
    collective layer exposes it since XLA provides it natively."""
    rop = _resolve_op(op, None)
    if rop not in (Average, Sum):
        raise ValueError(f"reducescatter supports Average/Sum, got {rop}")
    ax = _default_axis(axis_name)
    _reject_spmd_process_set(process_set, ax)
    if _axis_bound(ax):
        out = lax.psum_scatter(tensor, ax, scatter_dimension=0, tiled=True)
        if rop is Average:
            out = out / lax.axis_size(ax)
        return out
    if _is_traced(tensor):
        return _plain_jit_fallback(tensor, "reducescatter")
    basics._check_initialized()
    set_id, set_size = _set_args(process_set)
    nm = _auto_name("reducescatter", name)
    return jnp.asarray(_eager_reducescatter(tensor, rop, nm, set_id=set_id,
                                            set_size=set_size))


def alltoall(tensor, splits=None, name=None, axis_name=None,
             process_set=None):
    """Exchange dim-0 chunks between workers (the EP/MoE primitive; absent
    from the v0.18 reference, present in later Horovod).  SPMD plane:
    ``lax.all_to_all(tiled=True)`` with equal splits."""
    ax = _default_axis(axis_name)
    _reject_spmd_process_set(process_set, ax)
    if _axis_bound(ax):
        if splits is not None:
            raise NotImplementedError(
                "uneven splits under jit need a STATIC output capacity "
                "(XLA shapes); use hvd.alltoall_ragged(tensor, splits, "
                "output_size) which returns (padded output, received "
                "counts), or the eager path outside jit")
        return lax.all_to_all(tensor, ax, split_axis=0, concat_axis=0,
                              tiled=True)
    if _is_traced(tensor):
        out = _plain_jit_fallback(tensor, "alltoall")
        if splits is not None:
            # Keep the tuple contract under a plain-jit trace too (size-1
            # identity: everything came from self).
            return out, jnp.asarray(np.asarray([out.shape[0]], np.int64))
        return out
    basics._check_initialized()
    set_id, _ = _set_args(process_set)
    nm = _auto_name("alltoall", name)
    out, received = _eager_alltoall(tensor, splits, nm, set_id=set_id)
    if splits is not None:
        # Later-Horovod contract: with explicit splits the caller gets the
        # received row counts back (needed to slice the uneven output).
        return jnp.asarray(out), jnp.asarray(received)
    return jnp.asarray(out)


def alltoall_ragged(tensor, splits, output_size: int, axis_name=None,
                    use_primitive=None):
    """Uneven (ragged) all-to-all INSIDE the SPMD plane — the MoE/EP
    exchange with per-destination row counts, jit-compatible via a
    STATIC output capacity (closes the sharp edge the plain
    ``alltoall(splits=...)`` guard documents; later-Horovod has only the
    eager equivalent, ``horovod/common/ops/...alltoall``).

    ``tensor``: ``[N, ...]`` this shard's rows, grouped by destination
    (rows for peer 0 first, then peer 1, ...).  ``splits``: ``[S]`` rows
    to send to each peer (may be traced).  ``output_size``: static row
    capacity of the result — the caller's bound on ``sum(received)``
    (e.g. MoE capacity x experts); rows beyond it are DROPPED, matching
    a capacity-factor router's semantics.  Returns ``(out, received)``:
    ``out[output_size, ...]`` holds each source's rows concatenated in
    source order (unwritten tail rows are zeros), ``received[S]`` is the
    per-source row count each peer SENT (pre-drop; ``min`` it against
    the remaining capacity to count what landed).

    Routing follows the flash-kernel pattern: on a TPU mesh the XLA
    ``ragged-all-to-all`` primitive moves exactly the ragged bytes; on
    CPU/virtual meshes (where XLA has no such HLO) an exact dense twin —
    pad-to-N regular all_to_all + scatter-compact — computes the same
    answer, so tests and the dryrun certify the semantics everywhere.

    Differentiation: the dense twin has full AD support with the
    expected semantics (rows that land somewhere receive their
    cotangent, dropped/slack rows receive zero — gated by
    ``test_alltoall_ragged_gradient``); the primitive path's AD is
    ``lax.ragged_all_to_all``'s own (jax 0.9.0 registers its JVP and
    transpose rules; never differentiated on a chip — ROADMAP R2).
    """
    ax = _default_axis(axis_name)
    if not _axis_bound(ax):
        raise ValueError(
            "alltoall_ragged is the SPMD-plane API (call it inside "
            "shard_map with the axis bound); the eager plane's "
            "hvd.alltoall(tensor, splits=...) already supports uneven "
            "splits directly")
    size = lax.axis_size(ax)
    me = lax.axis_index(ax)
    sp = jnp.asarray(splits, jnp.int32)
    n = tensor.shape[0]
    trailing = tensor.shape[1:]

    in_off = jnp.concatenate([jnp.zeros(1, jnp.int32),
                              jnp.cumsum(sp)[:-1].astype(jnp.int32)])
    # ONE metadata collective serves both routes: m[s, d] = rows s -> d.
    m = lax.all_gather(sp, ax, axis=0).astype(jnp.int32)   # [S, S]
    recv = m[:, me]                                        # rows j -> me

    primitive = (use_primitive if use_primitive is not None
                 else _exec_on_tpu_spmd(tensor))
    if primitive:
        # Sender-side offsets into each RECEIVER's buffer: my block lands
        # after every lower-ranked sender's contribution to that peer.
        mask = (jnp.arange(size) < me)[:, None]
        out_off = jnp.sum(m * mask, axis=0).astype(jnp.int32)
        # Enforce the capacity-drop contract on the WIRE: clamp each
        # block to the room left at its receiver (every rank derives the
        # same clamps from the same gathered matrix), so the primitive
        # never updates past the static buffer.  `recv` is still the
        # PRE-clamp per-source count (callers min with capacity).
        send_sz = jnp.clip(output_size - out_off, 0, sp)
        off_at_me = jnp.concatenate(
            [jnp.zeros(1, jnp.int32),
             jnp.cumsum(recv)[:-1].astype(jnp.int32)])
        recv_sz = jnp.clip(output_size - off_at_me, 0, recv)
        out = jnp.zeros((output_size,) + trailing, tensor.dtype)
        out = lax.ragged_all_to_all(
            tensor, out, in_off, send_sz,
            jnp.minimum(out_off, output_size), recv_sz, axis_name=ax)
        return out, recv

    # Dense twin: pad each destination block to N rows (worst case: one
    # peer gets everything), exchange, scatter-compact into the capacity
    # buffer.  Moves S x the ragged bytes — fine for the CPU/test plane,
    # which is why the TPU mesh takes the primitive above.
    idx = jnp.arange(n)
    cum = jnp.cumsum(sp)
    dest = jnp.searchsorted(cum, idx, side="right").astype(jnp.int32)
    slot = idx - in_off[jnp.clip(dest, 0, size - 1)]
    valid_in = idx < cum[-1]
    buf = jnp.zeros((size, n) + trailing, tensor.dtype)
    # Rows beyond sum(splits) scatter to an out-of-bounds destination and
    # are dropped (mode="drop") — never overwriting a real slot.
    buf = buf.at[jnp.where(valid_in, dest, size), slot].set(
        tensor, mode="drop")
    ex = lax.all_to_all(buf, ax, split_axis=0, concat_axis=0)  # [S, n, ...]
    cum_recv = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                jnp.cumsum(recv)[:-1].astype(jnp.int32)])
    pos = cum_recv[:, None] + jnp.arange(n)[None, :]
    valid = jnp.arange(n)[None, :] < recv[:, None]
    pos = jnp.where(valid, pos, output_size)        # overflow/pad -> dump
    out = jnp.zeros((output_size + 1,) + trailing, tensor.dtype)
    out = out.at[pos.reshape(-1)].set(
        ex.reshape((size * n,) + trailing), mode="drop")[:output_size]
    return out, recv


def _exec_on_tpu_spmd(x) -> bool:
    from horovod_tpu.topology import exec_on_tpu
    return exec_on_tpu(x)


def barrier(name=None, process_set=None) -> None:
    """Block until every member has arrived (later-Horovod ``hvd.barrier``;
    the negotiation round itself is the barrier on the eager plane)."""
    basics._check_initialized()
    rt = basics.runtime()
    nm = _auto_name("barrier", name)
    faults.inject("barrier", nm)
    if rt is None:
        return
    set_id, _ = _set_args(process_set)
    rt.barrier(nm, set_id=set_id)


def join() -> int:
    """Signal this rank has no more work; returns the last joining rank.
    (Parity with later-Horovod ``join``; the v0.18 reference instead shuts
    down via the shutdown bit, ``message.h:110-122``.)"""
    basics._check_initialized()
    rt = basics.runtime()
    if rt is None:
        return 0
    return rt.join()
