"""ctypes binding to the native runtime ``libhorovod_tpu.so``.

Loading strategy mirrors reference ``horovod/common/basics.py:22-28`` (find
the shared library next to the package, ``ctypes.CDLL``).  The C ABI is a
small surface (``hvd_init`` / ``hvd_enqueue_*`` / ``hvd_wait`` / ...); see
``horovod_tpu/native/cc/c_api.h`` for the contract, which matches the shape
of the reference C API (``horovod/common/operations.cc:611-732``) plus the
enqueue layer (``operations.cc:736-843``).
"""

from __future__ import annotations

import ctypes
import os
import threading
import time
import weakref
from typing import Optional

import numpy as np

from horovod_tpu import config, faults, telemetry
from horovod_tpu.utils.logging import get_logger

log = get_logger(__name__)

# hvd_enqueue op code -> metric label (matches the op-type comment on the
# hvd_enqueue binding below).
_OP_NAMES = {0: "allreduce", 1: "allgather", 2: "broadcast", 3: "alltoall",
             4: "reducescatter", 5: "barrier", 6: "join", 7: "process_set"}

# hvd_transport_counter index labels (transport.h Backend/Level enums).
_TRANSPORT_BACKENDS = ("socket", "shm", "striped")
_TRANSPORT_LEVELS = ("flat", "local", "cross")


class _TraceSpan(ctypes.Structure):
    """Mirror of ``hvd_trace_span_t`` (c_api.h): 72 bytes of char arrays
    followed by four int64s, no padding."""
    _fields_ = [("name", ctypes.c_char * 56),
                ("phase", ctypes.c_char * 16),
                ("seq", ctypes.c_longlong),
                ("start_us", ctypes.c_longlong),
                ("end_us", ctypes.c_longlong),
                ("bytes", ctypes.c_longlong)]


class EagerStallError(RuntimeError):
    """An eager op outlived HOROVOD_EAGER_OP_TIMEOUT — the Python-boundary
    mirror of the native stall watchdog (reference ``stall_inspector.cc``):
    the message names the stuck tensor and the suspected missing ranks."""


# StatusCode::kMembershipChanged (hvd_common.h) as returned by hvd_wait.
_MEMBERSHIP_CHANGED_RC = 6


class MembershipChangedError(RuntimeError):
    """The collective world changed underneath this op: a peer died and
    ``HOROVOD_ON_RANK_FAILURE`` allows in-process reformation.  Retryable
    — the caller (``resilience.reform_world``) tears down the old world,
    re-inits against the launcher's reformation spec and replays from the
    warm-restore ladder instead of letting the process exit."""


def _env_float(name: str, default: Optional[float]) -> Optional[float]:
    # Registry-checked read (python -m tools.hvdlint, env-registry rule).
    return config.env_float(name, default)

_LIB_NAME = "libhorovod_tpu.so"

# np dtype -> wire dtype code (must match native/cc/include/types.h DataType)
_DTYPE_CODES = {
    np.dtype(np.uint8): 0,
    np.dtype(np.int8): 1,
    np.dtype(np.uint16): 2,
    np.dtype(np.int16): 3,
    np.dtype(np.int32): 4,
    np.dtype(np.int64): 5,
    np.dtype(np.float16): 6,
    np.dtype(np.float32): 7,
    np.dtype(np.float64): 8,
    np.dtype(bool): 9,
}
try:
    import ml_dtypes
    _DTYPE_CODES[np.dtype(ml_dtypes.bfloat16)] = 10
except ImportError:  # pragma: no cover
    pass


def _find_library() -> str:
    here = os.path.dirname(os.path.abspath(__file__))
    candidates = [
        os.path.join(here, _LIB_NAME),
        os.path.join(here, "cc", "build", _LIB_NAME),
    ]
    env = config.env_raw("HOROVOD_TPU_NATIVE_LIB")
    if env:
        # An explicit override must be honored or fail loudly — never
        # silently substituted with the default build.
        if not os.path.exists(env):
            raise RuntimeError(
                f"HOROVOD_TPU_NATIVE_LIB={env} does not exist")
        return env
    for c in candidates:
        if os.path.exists(c):
            return c
    # Sources ship with the package and g++ is cheap: build on demand
    # (mirrors the reference's install-time extension build).
    try:
        from horovod_tpu.native.build import ensure_built
        return ensure_built()
    except Exception as e:
        raise RuntimeError(
            f"{_LIB_NAME} not found (searched {candidates}) and on-demand "
            f"build failed: {e}. Build it with: "
            f"python -m horovod_tpu.native.build")


class Runtime:
    """Handle to the per-process native runtime (Horovod:
    ``HorovodGlobalState`` + background thread, reference
    ``global_state.h:42-112``, ``operations.cc:303-498``)."""

    def __init__(self, rank: int, size: int, local_rank: int = 0,
                 local_size: int = 1):
        self.rank = rank
        self.size = size
        self.local_rank = local_rank
        self.local_size = local_size
        self._lib = None
        # handle -> (input buffer, tensor name): the native thread reads
        # the enqueued pointer asynchronously, so the array must stay
        # referenced from enqueue until the wait completes; the name feeds
        # the Python-side stall report.
        self._inflight: dict = {}
        self._stalled: list = []   # quarantined entries of timed-out ops
        self._inflight_lock = threading.Lock()
        # Eager-plane deadline (docs/fault_tolerance.md): unset -> waits
        # stay unbounded-blocking (zero overhead) but a background
        # watchdog logs a stall report for any op older than
        # HOROVOD_EAGER_OP_WARN_SECONDS (default 60; 0 disables the
        # watchdog); set -> the wait itself polls and RAISES
        # EagerStallError after that many seconds.
        self._op_timeout = _env_float("HOROVOD_EAGER_OP_TIMEOUT", None)
        self._op_warn = _env_float("HOROVOD_EAGER_OP_WARN_SECONDS", 60.0)
        self._watchdog_stop: Optional[threading.Event] = None
        self._watchdog_thread: Optional[threading.Thread] = None
        # Zero-copy result reads (HOROVOD_EAGER_ZERO_COPY=0 restores the
        # copying hvd_read_output path): the returned ndarray wraps the
        # native output buffer directly and releases it when garbage
        # collected.  Skips one full-payload copy into cold pages per op.
        self._zero_copy = config.env_str(
            "HOROVOD_EAGER_ZERO_COPY", "1") not in ("0", "false", "")
        # Rank-agreed autotuned fusion threshold, latched ONLY inside the
        # sync_tuned_config() collective.  The raw hvd_tuned_* atomics
        # move at each rank's own cycle tick; feeding them straight into
        # trace-time bucketing would let two ranks bucket the same step
        # with different thresholds and trace divergent fused programs
        # (a hang).  None = never synced -> bucketing stays on the
        # env/default path, which is rank-agreed by construction.
        self._agreed_fusion_threshold: Optional[int] = None

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> None:
        lib = ctypes.CDLL(_find_library())
        lib.hvd_init.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_int,
                                 ctypes.c_int, ctypes.c_char_p, ctypes.c_int]
        lib.hvd_init.restype = ctypes.c_int
        lib.hvd_shutdown.argtypes = []
        lib.hvd_shutdown.restype = None
        lib.hvd_enqueue.argtypes = [
            ctypes.c_int,            # op type (0=allreduce,1=allgather,2=bcast,3=alltoall,4=reducescatter,5=barrier,6=join)
            ctypes.c_char_p,         # tensor name
            ctypes.c_void_p,         # input data
            ctypes.POINTER(ctypes.c_longlong),  # shape
            ctypes.c_int,            # ndim
            ctypes.c_int,            # dtype code
            ctypes.c_int,            # reduce-op code / root rank
            ctypes.POINTER(ctypes.c_longlong),  # alltoall splits (or None)
            ctypes.c_int,            # number of splits
            ctypes.c_int,            # process set id (0 = global)
        ]
        lib.hvd_enqueue.restype = ctypes.c_longlong   # handle, <0 on error
        lib.hvd_poll.argtypes = [ctypes.c_longlong]
        lib.hvd_poll.restype = ctypes.c_int
        lib.hvd_wait.argtypes = [ctypes.c_longlong]
        lib.hvd_wait.restype = ctypes.c_int           # status code
        lib.hvd_output_size.argtypes = [ctypes.c_longlong]
        lib.hvd_output_size.restype = ctypes.c_longlong
        lib.hvd_read_output.argtypes = [ctypes.c_longlong, ctypes.c_void_p,
                                        ctypes.c_longlong]
        lib.hvd_read_output.restype = ctypes.c_int
        lib.hvd_read_splits.argtypes = [ctypes.c_longlong,
                                        ctypes.POINTER(ctypes.c_longlong),
                                        ctypes.c_int]
        lib.hvd_read_splits.restype = ctypes.c_int
        lib.hvd_release.argtypes = [ctypes.c_longlong]
        lib.hvd_release.restype = None
        lib.hvd_last_error.argtypes = []
        lib.hvd_last_error.restype = ctypes.c_char_p
        addr = config.env_str("HOROVOD_RENDEZVOUS_ADDR", "127.0.0.1")
        self._hier_fn = getattr(lib, "hvd_hierarchical_enabled", None)
        self._hier_ag_fn = getattr(
            lib, "hvd_hierarchical_allgather_enabled", None)
        # Optional symbols (getattr: tolerate a stale prebuilt library).
        self._output_ptr_fn = getattr(lib, "hvd_output_ptr", None)
        if self._output_ptr_fn is not None:
            self._output_ptr_fn.argtypes = [ctypes.c_longlong]
            self._output_ptr_fn.restype = ctypes.c_void_p
        # Adaptive-control-plane introspection (stall reports + telemetry).
        self._tuned_cycle_fn = getattr(lib, "hvd_tuned_cycle_time_ms", None)
        if self._tuned_cycle_fn is not None:
            self._tuned_cycle_fn.restype = ctypes.c_double
        self._tuned_fusion_fn = getattr(
            lib, "hvd_tuned_fusion_threshold", None)
        if self._tuned_fusion_fn is not None:
            self._tuned_fusion_fn.restype = ctypes.c_longlong
        self._tuned_chunk_fn = getattr(lib, "hvd_tuned_chunk_bytes", None)
        if self._tuned_chunk_fn is not None:
            self._tuned_chunk_fn.restype = ctypes.c_longlong
        self._exploring_fn = getattr(lib, "hvd_autotune_exploring", None)
        self._cache_enabled_fn = getattr(lib, "hvd_cache_enabled", None)
        self._cache_lookups_fn = getattr(lib, "hvd_cache_lookups", None)
        if self._cache_lookups_fn is not None:
            self._cache_lookups_fn.restype = ctypes.c_longlong
        self._cache_hits_fn = getattr(lib, "hvd_cache_hits", None)
        if self._cache_hits_fn is not None:
            self._cache_hits_fn.restype = ctypes.c_longlong
        # Collective-schedule contract verifier (HOROVOD_SCHEDULE_CHECK).
        self._sched_check_fn = getattr(
            lib, "hvd_schedule_check_enabled", None)
        self._sched_subs_fn = getattr(
            lib, "hvd_schedule_check_submissions", None)
        if self._sched_subs_fn is not None:
            self._sched_subs_fn.restype = ctypes.c_longlong
        self._sched_div_fn = getattr(
            lib, "hvd_schedule_check_divergences", None)
        if self._sched_div_fn is not None:
            self._sched_div_fn.restype = ctypes.c_longlong
        self._sched_published = {}  # sym -> last value already inc'd
        # Tree coordination (HOROVOD_COORD_TREE): 1 when the two-level
        # member/leader/master wiring is active on this rank.
        self._coord_tree_fn = getattr(lib, "hvd_coord_tree", None)
        # Hierarchical-plane introspection (per-level byte/latency
        # counters + topology availability), all optional symbols.
        self._hier_avail_fn = getattr(
            lib, "hvd_hierarchical_available", None)
        self._hier_counter_fns = {}
        for sym in ("hvd_hier_local_bytes", "hvd_hier_cross_bytes",
                    "hvd_hier_local_us", "hvd_hier_cross_us",
                    "hvd_hier_allreduce_ops", "hvd_flat_allreduce_bytes",
                    "hvd_flat_allreduce_ops", "hvd_hier_ag_local_bytes",
                    "hvd_hier_ag_cross_bytes", "hvd_hier_ag_ops"):
            fn = getattr(lib, sym, None)
            if fn is not None:
                fn.restype = ctypes.c_longlong
                self._hier_counter_fns[sym] = fn
        self._hier_published = {}   # sym -> last value already inc'd
        # Transport-backend introspection (transport.h): the counter
        # matrix indexed by (backend, level, kind), link-topology flags
        # and the per-link describe lines for stall reports.
        self._transport_counter_fn = getattr(
            lib, "hvd_transport_counter", None)
        if self._transport_counter_fn is not None:
            self._transport_counter_fn.argtypes = [ctypes.c_int,
                                                   ctypes.c_int,
                                                   ctypes.c_int]
            self._transport_counter_fn.restype = ctypes.c_longlong
        self._transport_shm_fn = getattr(
            lib, "hvd_transport_shm_links", None)
        self._transport_striped_fn = getattr(
            lib, "hvd_transport_striped_links", None)
        self._transport_stripes_fn = getattr(
            lib, "hvd_transport_stripes", None)
        self._tuned_stripes_fn = getattr(
            lib, "hvd_tuned_transport_stripes", None)
        self._tuned_shm_granule_fn = getattr(
            lib, "hvd_tuned_shm_granule", None)
        if self._tuned_shm_granule_fn is not None:
            self._tuned_shm_granule_fn.restype = ctypes.c_longlong
        self._transport_describe_fn = getattr(
            lib, "hvd_transport_describe", None)
        if self._transport_describe_fn is not None:
            self._transport_describe_fn.argtypes = [ctypes.c_char_p,
                                                    ctypes.c_int]
            self._transport_describe_fn.restype = ctypes.c_int
        self._transport_published = {}  # (b, l, kind) -> last value
        # Distributed tracing (HOROVOD_TRACE): the native plane buffers
        # its spans in C++ and Python drains them here (watchdog + stop).
        self._trace_enabled_fn = getattr(lib, "hvd_trace_enabled", None)
        self._trace_drain_fn = getattr(lib, "hvd_trace_drain", None)
        if self._trace_drain_fn is not None:
            self._trace_drain_fn.argtypes = [ctypes.POINTER(_TraceSpan),
                                             ctypes.c_int]
            self._trace_drain_fn.restype = ctypes.c_int
        self._trace_dropped_fn = getattr(lib, "hvd_trace_dropped", None)
        if self._trace_dropped_fn is not None:
            self._trace_dropped_fn.restype = ctypes.c_longlong
        self._trace_dropped_seen = 0
        # Fail-in-place introspection: the membership epoch this world
        # was initialized under and the peer-death latch (set natively
        # BEFORE any waiter observes a kMembershipChanged status).
        self._world_epoch_fn = getattr(lib, "hvd_world_epoch", None)
        if self._world_epoch_fn is not None:
            self._world_epoch_fn.restype = ctypes.c_longlong
        self._membership_changed_fn = getattr(
            lib, "hvd_membership_changed", None)
        # The telemetry at-exit export can run before basics.shutdown()
        # (atexit LIFO) — give it a hook to pull the native buffer while
        # this runtime is still alive.
        telemetry.register_span_flush_hook(self._drain_native_spans)
        port = config.env_int("HOROVOD_RENDEZVOUS_PORT", 0)
        rc = lib.hvd_init(self.rank, self.size, self.local_rank,
                          self.local_size, addr.encode(), port)
        if rc != 0:
            raise RuntimeError(
                f"native runtime init failed (rank {self.rank}): "
                f"{lib.hvd_last_error().decode()}")
        self._lib = lib
        # Feed the ops-layer bucketing the tuned fusion threshold.  The
        # provider serves the sync_tuned_config()-latched value, never
        # the raw atomic — see the rank-agreement contract in
        # ops/fusion.py.  (Import here, not at module top: runtime is
        # below the ops layer.)
        from horovod_tpu.ops import fusion as _fusion
        _fusion.set_live_threshold_provider(self._live_fusion_threshold)
        # The telemetry at-exit export can run before basics.shutdown()
        # (atexit LIFO); the hook guarantees the final gauge/counter
        # deltas reach the snapshot even for jobs shorter than the
        # watchdog's first publish tick.
        telemetry.register_metrics_flush_hook(self._publish_autotune_gauges)
        if self._op_warn:
            self._watchdog_stop = threading.Event()
            self._watchdog_thread = threading.Thread(
                target=self._watchdog, name="hvd-eager-watchdog",
                daemon=True)
            self._watchdog_thread.start()

    def stop(self) -> None:
        if self._watchdog_stop is not None:
            self._watchdog_stop.set()
            self._watchdog_thread.join(timeout=5.0)
            self._watchdog_stop = None
            self._watchdog_thread = None
        if self._lib is not None:
            # Final gauge snapshot BEFORE shutdown zeroes the native state,
            # so the metrics summary records the config the job ended on.
            self._publish_autotune_gauges()
            self._drain_native_spans()
            telemetry.unregister_metrics_flush_hook(
                self._publish_autotune_gauges)
            telemetry.unregister_span_flush_hook(self._drain_native_spans)
            from horovod_tpu.ops import fusion as _fusion
            _fusion.set_live_threshold_provider(None)
            self._lib.hvd_shutdown()
            self._lib = None

    def _live_fusion_threshold(self) -> Optional[int]:
        """The threshold served to trace-time bucketing: the last value
        latched by the sync_tuned_config() collective — i.e. a value
        every rank agreed on at the same program point — or None (fall
        back to the env path) before the first sync.  Deliberately NOT
        the hvd_tuned_fusion_threshold atomic: ranks apply TunedParams
        at unsynchronized wall-clock moments, so the raw value can
        differ across ranks mid-trial and bucketing with it would trace
        divergent fused programs."""
        if self._lib is None:
            return None
        return self._agreed_fusion_threshold

    def hierarchical_enabled(self) -> bool:
        """True when the bootstrap agreement enabled the 2-level
        allreduce (tests/CI assert the path under test is engaged)."""
        return bool(self._hier_fn and self._hier_fn())

    def hierarchical_allgather_enabled(self) -> bool:
        """True when the bootstrap agreement enabled the 2-level
        allgather (HOROVOD_HIERARCHICAL_ALLGATHER)."""
        return bool(self._hier_ag_fn and self._hier_ag_fn())

    def world_epoch(self) -> int:
        """The membership epoch this world was initialized under
        (HOROVOD_WORLD_EPOCH; bumped by the launcher once per in-process
        reformation, 0 for a first init)."""
        if self._world_epoch_fn is None or self._lib is None:
            return 0
        return int(self._world_epoch_fn())

    def membership_changed(self) -> bool:
        """True once a peer death latched a pending membership change
        under a shrink-capable HOROVOD_ON_RANK_FAILURE policy.  Set
        natively before any waiter observes a kMembershipChanged status,
        so a wait that drained with a generic abort can still tell the
        two cases apart."""
        if self._membership_changed_fn is None or self._lib is None:
            return False
        return bool(self._membership_changed_fn())

    def coord_tree_enabled(self) -> bool:
        """True when tree coordination is active (HOROVOD_COORD_TREE=1
        with a usable multi-host HOROVOD_TOPOLOGY): members negotiate
        through their host leader, leaders through the master — so the
        coordinator's per-cycle fan-in is O(hosts + local_size) instead
        of O(world).  False in flat mode, including the schedule-check
        and bad-topology fallbacks."""
        return bool(self._coord_tree_fn and self._coord_tree_fn())

    # -- transport-backend introspection -----------------------------------

    def transport_counters(self) -> dict:
        """The native transport counter matrix as
        ``{(backend, level): {"bytes", "seconds", "ops", "retransmits",
        "crc_errors", "failovers", "degraded"}}``, omitting all-zero
        cells.  Backends: socket/shm/striped; levels mirror the
        hierarchical routing (flat/local/cross).  Counters are monotonic
        since process start (``degraded`` is a gauge of currently-
        degraded links); the np=2 CI gates assert engagement and
        self-healing from them (shm bytes > 0 intra-host; failovers /
        retransmits nonzero under transport chaos)."""
        fn = self._transport_counter_fn
        if fn is None or self._lib is None:
            return {}
        out = {}
        for b, backend in enumerate(_TRANSPORT_BACKENDS):
            for lv, level in enumerate(_TRANSPORT_LEVELS):
                by = int(fn(b, lv, 0))
                us = int(fn(b, lv, 1))
                ops = int(fn(b, lv, 2))
                retx = max(int(fn(b, lv, 3)), 0)
                crc = max(int(fn(b, lv, 4)), 0)
                fo = max(int(fn(b, lv, 5)), 0)
                deg = max(int(fn(b, lv, 6)), 0)
                if by or us or ops or retx or crc or fo or deg:
                    out[(backend, level)] = {
                        "bytes": by, "seconds": us / 1e6, "ops": ops,
                        "retransmits": retx, "crc_errors": crc,
                        "failovers": fo, "degraded": deg}
        return out

    def transport_describe(self) -> str:
        """Per-link state lines from the native transport registry
        ("peer N shm: tx ..B left"); empty without links or on an old
        library.  Feeds stall reports."""
        if self._transport_describe_fn is None or self._lib is None:
            return ""
        buf = ctypes.create_string_buffer(8192)
        n = self._transport_describe_fn(buf, len(buf))
        return buf.raw[:max(n, 0)].decode("utf-8", "replace")

    # -- adaptive-control-plane introspection ------------------------------

    def tuned_config(self) -> dict:
        """The live control-plane configuration: the latest TunedParams
        applied from the response stream (env-configured defaults when
        autotuning is off), plus the response-cache counters.  Empty dict
        when the runtime is stopped or the library predates the
        introspection exports."""
        if self._lib is None or self._tuned_cycle_fn is None:
            return {}
        lookups = int(self._cache_lookups_fn())  \
            if self._cache_lookups_fn is not None else 0
        hits = int(self._cache_hits_fn())  \
            if self._cache_hits_fn is not None else 0
        return {
            "cycle_time_ms": float(self._tuned_cycle_fn()),
            "fusion_threshold_bytes": int(self._tuned_fusion_fn())
            if self._tuned_fusion_fn is not None else -1,
            "chunk_bytes": int(self._tuned_chunk_fn())
            if self._tuned_chunk_fn is not None else -1,
            "exploring": bool(self._exploring_fn())
            if self._exploring_fn is not None else False,
            "cache_enabled": bool(self._cache_enabled_fn())
            if self._cache_enabled_fn is not None else False,
            "cache_lookups": lookups,
            "cache_hits": hits,
            "cache_hit_ratio": (hits / lookups) if lookups else 0.0,
            # Hierarchical routing as the data plane currently runs it —
            # env defaults until the autotuner flips the knobs through
            # the response stream.
            "hier_allreduce": self.hierarchical_enabled(),
            "hier_allgather": self.hierarchical_allgather_enabled(),
            "hier_available": bool(self._hier_avail_fn
                                   and self._hier_avail_fn()),
            # Transport backends as the data plane negotiated them, plus
            # the live (possibly autotuned) knobs.  0 = knob untouched.
            "transport_shm": bool(self._transport_shm_fn
                                  and self._transport_shm_fn()),
            "transport_striped": bool(self._transport_striped_fn
                                      and self._transport_striped_fn()),
            "transport_stripes": int(self._tuned_stripes_fn())
            if self._tuned_stripes_fn is not None else 0,
            "shm_granule_bytes": int(self._tuned_shm_granule_fn())
            if self._tuned_shm_granule_fn is not None else 0,
        }

    def sync_tuned_config(self) -> dict:
        """Collectively agree on the tuned config and latch it for
        trace-time consumers (the ops/fusion.py bucketer).

        The native plane applies TunedParams at the same response-stream
        position on every rank, but framework threads read the mirrors at
        arbitrary wall-clock moments — mid-trial, two ranks can observe
        different values.  A fused SPMD program bucketed under different
        thresholds differs per rank, which hangs the job, so the Python
        bucketer only ever follows the tuner through this COLLECTIVE: a
        Min-allreduce over each rank's locally observed values whose
        result is identical everywhere.  Must be called by ALL ranks at
        the same program point (it is a native allreduce) — a natural
        spot is between steps, next to checkpointing or eval.

        Returns the agreed ``{"fusion_threshold_bytes", "chunk_bytes"}``
        (empty when the runtime is stopped or the library predates the
        introspection exports).  Non-positive agreed values (old library,
        tuner off) leave the latch untouched.
        """
        cfg = self.tuned_config()
        if not cfg:
            return {}
        local = np.array([cfg["fusion_threshold_bytes"],
                          cfg["chunk_bytes"],
                          1 if cfg.get("hier_allreduce") else 0,
                          1 if cfg.get("hier_allgather") else 0,
                          cfg.get("transport_stripes", 0),
                          cfg.get("shm_granule_bytes", 0)],
                         dtype=np.int64)
        self._sync_seq = getattr(self, "_sync_seq", 0) + 1
        # 3 = ReduceOp Min (ops/collective.py; hvd_common.h kMin) — any
        # deterministic reduction works, consistency is the point.  For
        # the boolean hier knobs Min is AND: a rank that has not yet
        # applied the enabling TunedParams reports the conservative
        # answer, so the agreed view only says "on" once EVERY rank
        # routes hierarchically.
        agreed = np.asarray(self.allreduce(
            f"hvd.autotune.sync.{self._sync_seq}", local, 3)).ravel()
        fusion_bytes, chunk_bytes = int(agreed[0]), int(agreed[1])
        if fusion_bytes > 0:
            self._agreed_fusion_threshold = fusion_bytes
        out = {"fusion_threshold_bytes": fusion_bytes,
               "chunk_bytes": chunk_bytes}
        if agreed.size >= 4:   # old peers may still send 2-wide payloads
            out["hier_allreduce"] = bool(agreed[2])
            out["hier_allgather"] = bool(agreed[3])
        if agreed.size >= 6:   # transport knobs ride positions 4 and 5
            out["transport_stripes"] = int(agreed[4])
            out["shm_granule_bytes"] = int(agreed[5])
        return out

    def _publish_autotune_gauges(self) -> None:
        """Mirror the tuned config into telemetry gauges (merged into the
        hvdrun --metrics-file summary; docs/metrics.md)."""
        if not telemetry.enabled():
            return
        self._publish_schedule_check_metrics()
        cfg = self.tuned_config()
        if not cfg:
            return
        telemetry.gauge(
            "hvd_autotune_cycle_time_ms",
            "Active coordination cycle time (latest TunedParams)",
        ).set(cfg["cycle_time_ms"])
        telemetry.gauge(
            "hvd_autotune_fusion_threshold_bytes",
            "Active fusion threshold (latest TunedParams)",
        ).set(float(cfg["fusion_threshold_bytes"]))
        telemetry.gauge(
            "hvd_autotune_chunk_bytes",
            "Active pipelined-transport chunk size (0 = monolithic)",
        ).set(float(cfg["chunk_bytes"]))
        telemetry.gauge(
            "hvd_autotune_cache_hit_ratio",
            "Response-cache hit ratio for this rank's announcements",
        ).set(cfg["cache_hit_ratio"])
        telemetry.gauge(
            "hvd_autotune_hier_allreduce",
            "1 while the 2-level eager allreduce routing is active",
        ).set(1.0 if cfg.get("hier_allreduce") else 0.0)
        telemetry.gauge(
            "hvd_autotune_hier_allgather",
            "1 while the 2-level eager allgather routing is active",
        ).set(1.0 if cfg.get("hier_allgather") else 0.0)
        telemetry.gauge(
            "hvd_autotune_transport_stripes",
            "Active stripes per striped cross-host link (0 = no striped "
            "links)",
        ).set(float(cfg.get("transport_stripes", 0)))
        telemetry.gauge(
            "hvd_autotune_shm_granule_bytes",
            "Active shm push granule (0 = whole-slot pushes)",
        ).set(float(cfg.get("shm_granule_bytes", 0)))
        self._publish_hier_metrics()
        self._publish_transport_metrics()

    def _drain_native_spans(self) -> None:
        """Move buffered native spans (trace.cc) into the Python span
        recorder.  steady_clock and time.monotonic() share Linux's
        CLOCK_MONOTONIC, so the native microsecond timestamps convert to
        recorder seconds with a plain divide — no per-plane offset."""
        sp = telemetry.spans()
        if (sp is None or self._lib is None
                or self._trace_drain_fn is None
                or not (self._trace_enabled_fn
                        and self._trace_enabled_fn())):
            return
        batch = (_TraceSpan * 256)()
        while True:
            n = self._trace_drain_fn(batch, 256)
            for i in range(n):
                s = batch[i]
                sp.record(s.name.decode("utf-8", "replace"),
                          s.phase.decode("utf-8", "replace"), int(s.seq),
                          s.start_us / 1e6, s.end_us / 1e6, int(s.bytes))
            if n < 256:
                break
        if self._trace_dropped_fn is not None:
            d = int(self._trace_dropped_fn())
            if d > self._trace_dropped_seen:
                sp.dropped += d - self._trace_dropped_seen
                self._trace_dropped_seen = d

    def _publish_schedule_check_metrics(self) -> None:
        """``hvd_schedule_check_*`` series (docs/metrics.md): whether the
        collective-schedule contract verifier is armed, how many
        submissions this rank folded into its schedule stream, and
        whether a coordinator divergence abort was observed.  Native
        counters are monotonic; each publish adds the delta."""
        if self._sched_check_fn is None or self._lib is None:
            return
        telemetry.gauge(
            "hvd_schedule_check_enabled",
            "1 while HOROVOD_SCHEDULE_CHECK verification is active",
        ).set(1.0 if self._sched_check_fn() else 0.0)

        def delta(sym: str, fn) -> int:
            if fn is None:
                return 0
            now = int(fn())
            d = now - self._sched_published.get(sym, 0)
            self._sched_published[sym] = now
            return max(d, 0)

        d = delta("submissions", self._sched_subs_fn)
        if d:
            telemetry.counter(
                "hvd_schedule_check_submissions_total",
                "Collective submissions folded into this rank's verified "
                "schedule stream",
            ).inc(d)
        d = delta("divergences", self._sched_div_fn)
        if d:
            telemetry.counter(
                "hvd_schedule_check_divergence_total",
                "Coordinator-reported schedule divergence aborts observed "
                "by this rank",
            ).inc(d)

    def _publish_hier_metrics(self) -> None:
        """Mirror the native per-level counters into telemetry.

        The native atomics are monotonic since init while telemetry
        counters only support inc(), so each publish adds the DELTA since
        the previous one (``self._hier_published``).  Two series come out:
        ``hvd_hier_*`` (per-level payload/latency, the operator-facing
        breakdown) and ``hvd_collective_bytes_total{plane="eager",level}``
        — the same metric name the SPMD plane uses, so the np=4 CI gate
        can assert cross-host bytes == flat/local_size from ONE merged
        metrics file regardless of plane."""
        if not telemetry.enabled() or not self._hier_counter_fns:
            return

        def delta(sym: str) -> int:
            fn = self._hier_counter_fns.get(sym)
            if fn is None:
                return 0
            now = int(fn())
            d = now - self._hier_published.get(sym, 0)
            self._hier_published[sym] = now
            return max(d, 0)

        def bump(name: str, help_: str, d: int, **labels) -> None:
            if d:
                telemetry.counter(name, help_, **labels).inc(d)

        bytes_help = ("Per-level payload bytes of eager hierarchical "
                      "collectives (allreduce: logical payload; "
                      "allgather: wire sends)")
        secs_help = "Per-level wall seconds inside eager hierarchical ops"
        wire_help = ("Logical wire payload bytes of SPMD collectives "
                     "(trace-time)")
        bump("hvd_hier_bytes_total", bytes_help,
             delta("hvd_hier_local_bytes"), level="local", op="allreduce")
        cross_b = delta("hvd_hier_cross_bytes")
        bump("hvd_hier_bytes_total", bytes_help, cross_b,
             level="cross", op="allreduce")
        bump("hvd_hier_bytes_total", bytes_help,
             delta("hvd_hier_ag_local_bytes"), level="local",
             op="allgather")
        cross_ag = delta("hvd_hier_ag_cross_bytes")
        bump("hvd_hier_bytes_total", bytes_help, cross_ag,
             level="cross", op="allgather")
        local_us = delta("hvd_hier_local_us")
        cross_us = delta("hvd_hier_cross_us")
        if local_us:
            telemetry.counter("hvd_hier_seconds_total", secs_help,
                              level="local").inc(local_us / 1e6)
        if cross_us:
            telemetry.counter("hvd_hier_seconds_total", secs_help,
                              level="cross").inc(cross_us / 1e6)
        bump("hvd_hier_allreduce_ops_total",
             "Eager allreduces routed through the 2-level path",
             delta("hvd_hier_allreduce_ops"))
        bump("hvd_hier_allgather_ops_total",
             "Eager allgathers routed through the 2-level path",
             delta("hvd_hier_ag_ops"))
        flat_b = delta("hvd_flat_allreduce_bytes")
        bump("hvd_flat_allreduce_ops_total",
             "Eager allreduces that took the flat O(world) ring",
             delta("hvd_flat_allreduce_ops"))
        # Cross-plane merged series (same name as ops/fusion.py's):
        bump("hvd_collective_bytes_total", wire_help, flat_b,
             plane="eager", kind="allreduce", codec="none", level="flat")
        bump("hvd_collective_bytes_total", wire_help, cross_b,
             plane="eager", kind="allreduce", codec="none", level="cross")
        bump("hvd_collective_bytes_total", wire_help, cross_ag,
             plane="eager", kind="allgather", codec="none", level="cross")

    def _publish_transport_metrics(self) -> None:
        """``hvd_transport_*`` series (docs/metrics.md): bytes,
        thread-CPU pump seconds and pump rounds per (backend, level)
        from the native counter matrix.  Like the hier counters, the
        native values are monotonic and telemetry counters only inc(),
        so each publish adds the delta since the previous one."""
        if not telemetry.enabled() or self._transport_counter_fn is None \
                or self._lib is None:
            return
        fn = self._transport_counter_fn

        def bump(name, help_text, kind, scale, b, lv, backend, level):
            now = int(fn(b, lv, kind))
            key = (b, lv, kind)
            d = now - self._transport_published.get(key, 0)
            if d > 0:
                self._transport_published[key] = now
                telemetry.counter(name, help_text, backend=backend,
                                  level=level).inc(d * scale)

        for b, backend in enumerate(_TRANSPORT_BACKENDS):
            for lv, level in enumerate(_TRANSPORT_LEVELS):
                bump("hvd_transport_bytes_total",
                     "Payload bytes moved per transport backend and "
                     "hierarchical level", 0, 1.0, b, lv, backend, level)
                bump("hvd_transport_seconds_total",
                     "Thread-CPU seconds the transport pumps spent "
                     "moving bytes per backend and level",
                     1, 1e-6, b, lv, backend, level)
                bump("hvd_transport_ops_total",
                     "Transport pump rounds that moved bytes (socket "
                     "drains, shm slot pushes, stripe pumps)",
                     2, 1.0, b, lv, backend, level)
                bump("hvd_transport_retransmits_total",
                     "Wire frames resent after a NAK (self-healing "
                     "transport retransmit ladder)",
                     3, 1.0, b, lv, backend, level)
                bump("hvd_transport_crc_errors_total",
                     "Frames or shm slots rejected by the CRC32C "
                     "integrity check", 4, 1.0, b, lv, backend, level)
                bump("hvd_transport_failovers_total",
                     "Link failovers: stripe deaths absorbed plus "
                     "backend degrades to the mesh socket",
                     5, 1.0, b, lv, backend, level)
                # Currently-degraded links is a gauge (re-promotion
                # takes links back out), so publish the level, not a
                # delta.
                deg = int(fn(b, lv, 6))
                if deg > 0 or (b, lv, 6) in self._transport_published:
                    self._transport_published[(b, lv, 6)] = deg
                    telemetry.gauge(
                        "hvd_transport_degraded_links_total",
                        "Links currently degraded off their preferred "
                        "backend (gauge; falls on re-promotion)",
                        backend=backend, level=level).set(max(deg, 0))

    # -- collectives -------------------------------------------------------

    def _submit(self, op: int, name: str, arr: np.ndarray, arg: int = 0,
                splits=None, set_id: int = 0) -> int:
        faults.inject("native_submit", name, rank=self.rank)
        t_submit = time.monotonic()
        arr = np.ascontiguousarray(arr)
        code = _DTYPE_CODES.get(arr.dtype)
        if code is None:
            raise ValueError(f"unsupported dtype for eager collective: {arr.dtype}")
        shape = (ctypes.c_longlong * max(arr.ndim, 1))(*arr.shape)
        if splits is not None:
            sp = np.ascontiguousarray(splits, dtype=np.int64).ravel()
            csplits = (ctypes.c_longlong * sp.size)(*sp)
            nsplits = sp.size
        else:
            csplits, nsplits = None, 0
        h = self._lib.hvd_enqueue(
            op, name.encode(), arr.ctypes.data_as(ctypes.c_void_p),
            shape, arr.ndim, code, arg, csplits, nsplits, set_id)
        if h < 0:
            raise RuntimeError(self._lib.hvd_last_error().decode())
        t_enqueued = time.monotonic()
        # Distributed tracing: the Python occurrence counter ticks once
        # per submit, mirroring the native counter in TensorQueue::Add —
        # same names in the same per-name order on both sides, so the
        # (name, seq) correlation key lines up without a native readback.
        sp = telemetry.spans()
        trace_seq = sp.next_seq(name) if sp is not None else -1
        if sp is not None:
            sp.record(name, "submit", trace_seq, t_submit, t_enqueued,
                      int(arr.nbytes))
        with self._inflight_lock:
            # [buffer, name, submit time, last warn time, op kind,
            #  nbytes, trace seq]
            self._inflight[h] = [arr, name, t_enqueued, 0.0,
                                 _OP_NAMES.get(op, str(op)), arr.nbytes,
                                 trace_seq]
        tl = telemetry.timeline()
        if tl is not None:
            tl.span(name, f"SUBMIT_{_OP_NAMES.get(op, str(op)).upper()}",
                    t_submit, t_enqueued,
                    args={"op": _OP_NAMES.get(op, str(op)),
                          "bytes": int(arr.nbytes)})
        return h

    def _op_name(self, h: int) -> str:
        with self._inflight_lock:
            entry = self._inflight.get(h)
        return entry[1] if entry else f"<handle {h}>"

    def _stall_report(self, name: str, elapsed: float) -> str:
        """The Python-boundary mirror of the native stall inspector
        (reference ``stall_inspector.cc:29-82``): this rank submitted the
        op and its completion never arrived, so the suspects are exactly
        the peers whose readiness the coordinator is still missing."""
        suspects = [r for r in range(self.size) if r != self.rank]
        # Name the control-plane config the op ran under: a stall that
        # appears right after the autotuner moved the cycle time or chunk
        # size points at the tuner, and the report should say so.
        cfg = self.tuned_config()
        cfg_note = ""
        if cfg:
            cfg_note = (
                f" Active control-plane config: cycle_time="
                f"{cfg['cycle_time_ms']:.2f}ms, fusion_threshold="
                f"{cfg['fusion_threshold_bytes']} bytes, chunk_bytes="
                f"{cfg['chunk_bytes']}"
                + (", autotuner exploring" if cfg["exploring"] else "")
                + ".")
        # Name the active transport backends and per-link/stripe state: a
        # stall with a parked stripe or a backpressured shm ring points
        # at the transport, and the report should show it directly.
        transport_note = ""
        desc = self.transport_describe()
        if desc:
            backends = [b for b, flag in (
                ("shm", cfg.get("transport_shm")),
                ("striped", cfg.get("transport_striped"))) if flag]
            transport_note = (
                " Active transport backends: "
                + (", ".join(backends) if backends else "socket")
                + ". " + desc.replace("\n", "; ").strip())
        sched_note = ""
        if not (self._sched_check_fn is not None and self._sched_check_fn()):
            sched_note = (
                " If a divergent submission order is suspected, rerun "
                "with HOROVOD_SCHEDULE_CHECK=1: the coordinator then "
                "verifies every rank's submission stream and aborts at "
                "the first divergence naming both ranks, the call index "
                "and the mismatched field instead of stalling here.")
        # Name the coordination plane: after a failover the coordinator is
        # no longer rank 0, and a stall right after an election points at
        # ranks still talking to the dead epoch.
        coord_note = (
            f" Coordination plane: coordinator rank "
            f"{config.env_int('HOROVOD_COORD_RANK')}, lease epoch "
            f"{config.env_int('HOROVOD_COORD_EPOCH')}, elections so far "
            f"{config.env_int('HOROVOD_COORD_ELECTIONS')}.")
        return (
            f"Stalled eager op '{name}': submitted by rank {self.rank} "
            f"but not completed after {elapsed:.1f}s. One or more ranks "
            f"likely never reached this collective — suspected missing "
            f"ranks: {suspects} (every peer of rank {self.rank}; the "
            f"coordinator's stall watchdog, HOROVOD_STALL_CHECK_TIME_"
            f"SECONDS, reports the authoritative list on rank 0). "
            f"Possible causes: a crashed or hung peer, a deadlocked "
            f"submission order, or a network partition." + coord_note
            + cfg_note + transport_note + sched_note)

    def _watchdog(self) -> None:
        """Background stall reporter for the default (no hard timeout)
        configuration: any op inflight past HOROVOD_EAGER_OP_WARN_SECONDS
        gets a warning naming it, repeated each interval — without adding
        a single instruction to the op completion path."""
        warn = self._op_warn
        interval = min(warn, 5.0)
        while not self._watchdog_stop.wait(interval):
            # Keep the autotune gauges fresh while the job runs — the
            # watchdog is the one periodic thread the runtime already has.
            try:
                self._publish_autotune_gauges()
                self._drain_native_spans()
            except Exception:   # never let telemetry kill the watchdog
                pass
            now = time.monotonic()
            reports = []
            with self._inflight_lock:
                for entry in self._inflight.values():
                    name, t0, last = entry[1], entry[2], entry[3]
                    if now - t0 >= warn and now - last >= warn:
                        entry[3] = now
                        reports.append((name, now - t0))
            for name, elapsed in reports:
                if telemetry.enabled():
                    telemetry.counter(
                        "hvd_eager_stall_warnings_total",
                        "Watchdog warnings for eager ops inflight past "
                        "HOROVOD_EAGER_OP_WARN_SECONDS").inc()
                log.warning("%s", self._stall_report(name, elapsed))

    def _wait_bounded(self, h: int) -> int:
        """hvd_wait with the eager-plane deadline.

        Default (no HOROVOD_EAGER_OP_TIMEOUT): the plain blocking
        hvd_wait, which releases the GIL — stall visibility comes from
        the watchdog thread at zero completion-path cost.  With a hard
        timeout: a poll loop with escalating sleep (brief spin for the
        common sub-millisecond completion, then 1ms doubling to a 50ms
        cap) that raises EagerStallError at the deadline."""
        timeout = self._op_timeout
        if timeout is None:
            return self._lib.hvd_wait(h)
        poll = self._lib.hvd_poll
        for _ in range(200):          # spin: catches already-done ops
            if poll(h):
                return self._lib.hvd_wait(h)
        start = time.monotonic()
        deadline = start + timeout
        sleep = 0.001
        while not poll(h):
            now = time.monotonic()
            if now >= deadline:
                name = self._op_name(h)
                raise EagerStallError(self._stall_report(name, now - start))
            time.sleep(min(sleep, max(deadline - now, 0.001)))
            sleep = min(sleep * 2.0, 0.05)
        return self._lib.hvd_wait(h)

    def _wait_read(self, h: int, dtype, trailing_shape,
                   read_splits: bool = False):
        """Wait, (optionally) read received splits, read output, release.

        With ``read_splits`` returns ``(output, received_splits)`` —
        splits must be read BEFORE hvd_read_output, which releases the
        native table entry (c_api.h contract)."""
        faults.inject("native_wait", self._op_name(h), rank=self.rank)
        t_wait = time.monotonic()
        try:
            rc = self._wait_bounded(h)
        except EagerStallError:
            # The op is STILL IN FLIGHT natively — the background thread
            # may yet read the enqueued input pointer, so the buffer must
            # outlive this error: quarantine the entry instead of freeing
            # it (a bounded leak, paid only on a stall that is about to
            # tear the job down).  The handle is deliberately NOT
            # released: releasing a pending entry would race the native
            # completion path.
            with self._inflight_lock:
                entry = self._inflight.pop(h, None)
                if entry is not None:
                    self._stalled.append(entry)
            if telemetry.enabled():
                telemetry.counter(
                    "hvd_eager_stalls_total",
                    "Eager ops that raised EagerStallError at the "
                    "HOROVOD_EAGER_OP_TIMEOUT deadline",
                    op=entry[4] if entry else "unknown").inc()
            raise
        with self._inflight_lock:
            entry = self._inflight.pop(h, None)
        t_done = time.monotonic()
        op_kind = entry[4] if entry else "unknown"
        if rc != 0:
            if telemetry.enabled():
                telemetry.counter(
                    "hvd_eager_op_errors_total",
                    "Eager ops completed with a native error status",
                    op=op_kind).inc()
            err = self._lib.hvd_last_error().decode()
            self._lib.hvd_release(h)   # drop the native table entry
            # Fail-in-place: ops drained by a peer death under a shrink
            # policy carry the retryable kMembershipChanged code.  The
            # latch check also catches ops that raced the detection and
            # drained with a generic abort — once the flag is up, EVERY
            # failed wait means "the world changed", not "the op broke".
            if rc == _MEMBERSHIP_CHANGED_RC or self.membership_changed():
                raise MembershipChangedError(err)
            raise RuntimeError(err)
        if entry is not None:
            name, t0, nbytes = entry[1], entry[2], entry[5]
            sp = telemetry.spans()
            if sp is not None and len(entry) > 6 and entry[6] >= 0:
                sp.record(name, "wait", entry[6], t_wait, t_done, nbytes)
            telemetry.observe_op(op_kind, max(t_done - t0, 1e-9), nbytes)
            if telemetry.enabled():
                telemetry.histogram(
                    "hvd_native_wait_seconds",
                    "Time blocked in hvd_wait on the native runtime",
                    bounds=telemetry.DEFAULT_TIME_BUCKETS,
                    op=op_kind).observe(max(t_done - t_wait, 0.0))
            tl = telemetry.timeline()
            if tl is not None:
                tl.span(name, f"WAIT_{op_kind.upper()}", t_wait, t_done)
                tl.instant(name, "FINISH", t_done, args={"op": op_kind})
            log.trace("eager %s '%s' done: %.3f ms (%d bytes, wait "
                      "%.3f ms)", op_kind, name, (t_done - t0) * 1e3,
                      nbytes, (t_done - t_wait) * 1e3)
        received = None
        if read_splits:
            recv = (ctypes.c_longlong * self.size)()
            n_src = self._lib.hvd_read_splits(h, recv, self.size)
            if n_src < 0:
                err = self._lib.hvd_last_error().decode()
                self._lib.hvd_release(h)
                raise RuntimeError(err)
            # n_src = the source count (process-set size for subset ops).
            received = np.array(recv[:n_src], dtype=np.int64)
        n = self._lib.hvd_output_size(h)
        out = None
        nbytes = int(n) * np.dtype(dtype).itemsize
        if self._zero_copy and self._output_ptr_fn is not None and nbytes:
            ptr = self._output_ptr_fn(h)
            if ptr:
                # Wrap the native buffer directly; the finalizer returns
                # it to the warm pool when the LAST view dies (reshapes
                # below keep `out` alive as their base).  hvd_release is
                # null-state-safe, so a GC after shutdown is fine; and
                # handle ids carry an init epoch (tensor_queue
                # SeedHandles), so a finalizer surviving an elastic
                # re-init can never release a recycled id in the new
                # runtime's table.
                cbuf = (ctypes.c_byte * nbytes).from_address(ptr)
                out = np.frombuffer(cbuf, dtype=dtype)
                weakref.finalize(out, self._lib.hvd_release, h)
        if out is None:
            out = np.empty(int(n), dtype=dtype)
            rc = self._lib.hvd_read_output(
                h, out.ctypes.data_as(ctypes.c_void_p), n)
            if rc != 0:
                err = self._lib.hvd_last_error().decode()
                self._lib.hvd_release(h)
                raise RuntimeError(err)
        if trailing_shape:
            inner = int(np.prod(trailing_shape)) or 1
            out = out.reshape((int(n) // inner,) + tuple(trailing_shape))
        return (out, received) if read_splits else out

    def discard(self, tok) -> None:
        """Wait out and drop an un-read submit token (``(h, dtype,
        shape)`` as returned by the ``*_submit`` methods).

        Stale-token reaping for the TF1 async path: a pruned sync node's
        collective still completed (enqueues are rank-symmetric), so the
        handle only needs its table entry + result buffer freed.  Errors
        are swallowed — nobody is left to observe them."""
        h = int(tok[0])
        self._lib.hvd_wait(h)
        with self._inflight_lock:
            self._inflight.pop(h, None)
        self._lib.hvd_release(h)

    # -- split submit/finish surface (true async: submit is the native
    #    enqueue and returns immediately; finish blocks in hvd_wait, which
    #    releases the GIL.  The TF graph binding rides this so N tensors
    #    negotiate concurrently with zero extra Python threads). ---------

    def allreduce_submit(self, name, arr, op_code, set_id=0):
        arr = np.asarray(arr)
        h = self._submit(0, name, arr, op_code, set_id=set_id)
        return (h, arr.dtype, arr.shape)

    def allreduce_finish(self, tok):
        h, dtype, shape = tok
        return self._wait_read(h, dtype, shape[1:]).reshape(shape)

    def allgather_submit(self, name, arr, set_id=0):
        arr = np.asarray(arr)
        if arr.ndim == 0:
            arr = arr.reshape(1)
        h = self._submit(1, name, arr, set_id=set_id)
        return (h, arr.dtype, arr.shape)

    def allgather_finish(self, tok):
        h, dtype, shape = tok
        return self._wait_read(h, dtype, shape[1:])

    def broadcast_submit(self, name, arr, root, set_id=0):
        arr = np.asarray(arr)
        h = self._submit(2, name, arr, root, set_id=set_id)
        return (h, arr.dtype, arr.shape)

    broadcast_finish = allreduce_finish

    def alltoall_submit(self, name, arr, splits=None, set_id=0):
        arr = np.asarray(arr)
        if arr.ndim == 0:
            arr = arr.reshape(1)
        h = self._submit(3, name, arr, 0, splits=splits, set_id=set_id)
        return (h, arr.dtype, arr.shape)

    def alltoall_finish(self, tok):
        h, dtype, shape = tok
        return self._wait_read(h, dtype, shape[1:], read_splits=True)

    def reducescatter_submit(self, name, arr, op_code, set_id=0):
        arr = np.asarray(arr)
        h = self._submit(4, name, arr, op_code, set_id=set_id)
        return (h, arr.dtype, arr.shape)

    reducescatter_finish = allgather_finish

    def allreduce(self, name: str, arr: np.ndarray, op_code: int,
                  set_id: int = 0) -> np.ndarray:
        return self.allreduce_finish(
            self.allreduce_submit(name, arr, op_code, set_id))

    def allgather(self, name: str, arr: np.ndarray,
                  set_id: int = 0) -> np.ndarray:
        return self.allgather_finish(
            self.allgather_submit(name, arr, set_id=set_id))

    def broadcast(self, name: str, arr: np.ndarray, root: int,
                  set_id: int = 0) -> np.ndarray:
        return self.broadcast_finish(
            self.broadcast_submit(name, arr, root, set_id=set_id))

    def alltoall(self, name: str, arr: np.ndarray,
                 splits: Optional[np.ndarray] = None, set_id: int = 0):
        """Returns ``(output, received_splits)`` — the concatenated blocks
        and the dim-0 row count received from each source (position within
        the process set; parity with later-Horovod received_splits)."""
        return self.alltoall_finish(
            self.alltoall_submit(name, arr, splits, set_id=set_id))

    def reducescatter(self, name: str, arr: np.ndarray, op_code: int,
                      set_id: int = 0) -> np.ndarray:
        return self.reducescatter_finish(
            self.reducescatter_submit(name, arr, op_code, set_id=set_id))

    def barrier(self, name: str = "hvd.barrier", set_id: int = 0) -> None:
        """Native barrier: the negotiation round IS the barrier (all
        members must announce before the coordinator responds)."""
        arr = np.zeros(1, np.int32)
        h = self._submit(5, name, arr, set_id=set_id)
        self._wait_read(h, arr.dtype, ())

    def add_process_set(self, ranks) -> int:
        """Collectively register a rank-subset group; returns its id.

        Every rank of the job must call this with the SAME sorted ranks
        list (later-Horovod ``add_process_set`` is likewise a collective
        over the global set); registering an existing list returns its
        existing id."""
        ranks = sorted(int(r) for r in ranks)
        # The wire name is a per-rank REGISTRATION SEQUENCE NUMBER, not
        # the member list: every rank must call add_process_set in the
        # same order (the collective contract), and a common name is what
        # lets the coordinator DETECT a mismatched proposal as a clean
        # error — member-list-derived names would just stall, each rank
        # waiting on a name the others never submit.
        self._ps_seq = getattr(self, "_ps_seq", 0) + 1
        name = f"hvd.process_set.{self._ps_seq}"
        arr = np.zeros(1, np.int32)
        h = self._submit(7, name, arr,
                         splits=np.asarray(ranks, np.int64))
        out = self._wait_read(h, np.dtype(np.int32), ())
        return int(np.asarray(out).ravel()[0])

    def join(self) -> int:
        """Signal that this rank has no more work (uneven final batches).

        Reference Join semantics: while blocked here, this rank's
        background thread keeps participating — with zero payloads — in
        collectives still issued by active ranks, so ranks with more
        batches never deadlock.  Only Sum reductions are allowed while
        ranks are joined (zeros are the Sum identity; Average would
        deflate by the full world size, and a joined broadcast root or
        alltoall is a coordinated error).  Returns the rank that joined
        LAST, as observed by the coordinator."""
        arr = np.zeros(1, np.int32)
        h = self._submit(6, "hvd.join", arr)
        out = self._wait_read(h, np.dtype(np.int32), ())
        return int(out.ravel()[0])
