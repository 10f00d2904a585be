"""Declarative registry of every ``HOROVOD_*`` environment variable.

The reference configures itself through dozens of ad-hoc ``getenv``
calls scattered across Python and C++ (env_parser.cc plus per-module
reads); after nine PRs this rebuild had grown ~70 of its own.  This
module is the single source of truth: one entry per variable with its
type, documented default, one-line doc and whether the native runtime
(``native/cc``) also reads it.  ``basics.py``, ``runner/`` and
``native/runtime.py`` read the environment through the typed accessors
below, and ``tools/hvdlint``'s env-registry checker fails the build on

* any ``os.environ``/``getenv`` read of a ``HOROVOD_*`` name that has
  no entry here,
* any entry whose name appears nowhere in the code (orphan), and
* drift between the ``native=True`` flags and the actual
  ``EnvInt``/``EnvStr``/``EnvBool``/``EnvDouble`` reads in
  ``native/cc/src``.

Run it with ``python -m tools.hvdlint`` (or ``make lint``); rule docs in
``docs/static_analysis.md``.

This module is imported by ``tools/hvdlint`` standalone (via
``importlib`` file loading, without executing ``horovod_tpu/__init__``),
so it must stay stdlib-only: no jax, no sibling imports.

``python -m horovod_tpu.config`` prints the registry as a reference
table.
"""

from __future__ import annotations

import os
from typing import Any, Dict, NamedTuple, Optional


class EnvVar(NamedTuple):
    name: str
    type: str          # "str" | "int" | "float" | "bool"
    default: Any       # documented default; None = unset / derived
    doc: str           # one-line description (keep it one line: hvdlint
    #                    and the --describe table both render it as one)
    native: bool = False   # also read by native/cc (EnvInt/EnvStr/...)


REGISTRY: Dict[str, EnvVar] = {}


def _var(name: str, type_: str, default: Any, doc: str,
         native: bool = False) -> None:
    assert name not in REGISTRY, f"duplicate registry entry {name}"
    REGISTRY[name] = EnvVar(name, type_, default, doc, native)


# ---------------------------------------------------------------------------
# Rank / topology contract (exported by the hvdrun launcher; reference
# run/gloo_run.py:211-254)
# ---------------------------------------------------------------------------
_var("HOROVOD_RANK", "int", None,
     "This process's global rank; unset falls back to jax.process_index()",
     native=True)
_var("HOROVOD_SIZE", "int", None,
     "World size; unset falls back to jax.process_count()")
_var("HOROVOD_LOCAL_RANK", "int", None,
     "Rank within this host (default: the global rank)")
_var("HOROVOD_LOCAL_SIZE", "int", None,
     "Ranks on this host (default: the world size)")
_var("HOROVOD_CROSS_RANK", "int", None,
     "This host's index among hosts (default: rank // local_size)")
_var("HOROVOD_CROSS_SIZE", "int", None,
     "Number of hosts (default: ceil(size / local_size))")
_var("HOROVOD_HOSTNAME", "str", "",
     "Launcher-assigned host name used in topology and stall reports",
     native=True)
_var("HOROVOD_TOPOLOGY", "str", "",
     "host:slots,... map exported per elastic attempt; drives "
     "hvd.topology(), hierarchical routing and the native tree-"
     "coordination host blocks", native=True)
_var("HOROVOD_CONTROLLER", "str", "tcp",
     "Reference-compat marker exported by the launcher (always tcp here)")
_var("HOROVOD_CPU_OPERATIONS", "str", "tcp",
     "Reference-compat marker exported by the launcher (always tcp here)")

# ---------------------------------------------------------------------------
# Bootstrap / rendezvous / security
# ---------------------------------------------------------------------------
_var("HOROVOD_COORDINATOR_ADDR", "str", None,
     "host:port of the jax.distributed coordinator (multi-host SPMD "
     "bootstrap)")
_var("HOROVOD_JAX_DISTRIBUTED", "bool", False,
     "1 = call jax.distributed.initialize() inside hvd.init()")
_var("HOROVOD_RENDEZVOUS_ADDR", "str", "127.0.0.1",
     "Native control-plane rendezvous address (rank 0 listens here)")
_var("HOROVOD_RENDEZVOUS_PORT", "int", 0,
     "Native rendezvous port; 0 lets rank 0 bind an ephemeral port")
_var("HOROVOD_SECRET_KEY", "str", None,
     "Base64 HMAC key authenticating the RPC + native control planes",
     native=True)
_var("HOROVOD_SSH_CMD", "str", "ssh",
     "Remote-shell command used to spawn ranks (CI points it at "
     "ci/fake_ssh.sh)")
_var("HOROVOD_NETWORK_INTERFACE", "str", "",
     "Comma-separated NIC allowlist for the native data plane",
     native=True)
_var("HOROVOD_SOCKET_BUFFER", "int", -1,
     "SO_SNDBUF/SO_RCVBUF request for ring sockets; -1 keeps the OS "
     "default", native=True)
_var("HOROVOD_TPU_NATIVE_LIB", "str", None,
     "Absolute path overriding the built libhorovod_tpu.so")

# ---------------------------------------------------------------------------
# Eager plane behavior
# ---------------------------------------------------------------------------
_var("HOROVOD_EAGER_OP_TIMEOUT", "float", None,
     "Seconds after which a blocked eager wait raises EagerStallError "
     "(unset = wait forever, watchdog still warns)")
_var("HOROVOD_EAGER_OP_WARN_SECONDS", "float", 60.0,
     "Python-side wait warning cadence for slow eager ops")
_var("HOROVOD_EAGER_ZERO_COPY", "bool", True,
     "0 restores the copying hvd_read_output result path")
_var("HOROVOD_EAGER_CHUNK_BYTES", "int", 1024 * 1024,
     "Pipelined-transport granule for oversized ring exchanges; 0 "
     "disables chunking", native=True)
_var("HOROVOD_STALL_CHECK_TIME_SECONDS", "float", 60.0,
     "Coordinator stall-inspector warning deadline; 0 disables",
     native=True)
_var("HOROVOD_STALL_SHUTDOWN_TIME_SECONDS", "float", 0.0,
     "Coordinator stall deadline after which the job aborts; 0 disables",
     native=True)
_var("HOROVOD_SCHEDULE_CHECK", "bool", False,
     "1 arms the collective-schedule contract verifier: the coordinator "
     "matches every rank's submission records by name and aborts at the "
     "first divergence (rank, call index, field) instead of stalling",
     native=True)
_var("HOROVOD_SCHEDULE_CHECK_QUIET_SECONDS", "float", 2.0,
     "schedule-verifier quiet window: with the check armed, abort when "
     "every rank has an unmatched submission and no rank has announced "
     "anything for this long (raise on very bursty async pipelines)",
     native=True)
_var("HOROVOD_CYCLE_TIME", "float", 1.0,
     "Coordination loop cycle time in ms (autotune may override)",
     native=True)
_var("HOROVOD_CACHE_CAPACITY", "int", 1024,
     "Response-cache capacity in entries; 0 disables the steady-state "
     "fast path", native=True)

# ---------------------------------------------------------------------------
# Fusion / compression / hierarchical routing
# ---------------------------------------------------------------------------
_var("HOROVOD_FUSION_THRESHOLD", "int", 64 * 1024 * 1024,
     "Fusion bucket byte threshold (size grammar: 64mb/32MiB/0.5; "
     "autotune may override)", native=True)
_var("HOROVOD_MAX_BUCKET_BYTES", "int", 32 * 1024 * 1024,
     "Cap above which fusion-v2 buckets are chunked; 0 disables")
_var("HOROVOD_COMPRESSION", "str", "none",
     "Wire codec: none|bf16|fp16|int8|powersgd[:rank]")
_var("HOROVOD_HIERARCHICAL_ALLREDUCE", "bool", False,
     "1 routes eager allreduces through the 2-level "
     "local-RS/leader-ring/local-AG plane", native=True)
_var("HOROVOD_HIERARCHICAL_ALLGATHER", "bool", False,
     "1 routes eager allgathers through the 2-level plane", native=True)
_var("HOROVOD_HIERARCHICAL_ALLREDUCE_THRESHOLD", "int", 262144,
     "Payload bytes below which hier-routed allreduces stay on the flat "
     "ring", native=True)

# ---------------------------------------------------------------------------
# Transport backends (native/cc/src/{shm,striped}_transport.cc,
# docs/performance.md "Transport backends")
# ---------------------------------------------------------------------------
_var("HOROVOD_TRANSPORT", "str", "auto",
     "Data-plane backend selection: auto (shm intra-host, striped "
     "cross-host when stripes>1, else socket) | shm | striped | socket",
     native=True)
_var("HOROVOD_TRANSPORT_STRIPES", "int", 0,
     "Parallel TCP connections per cross-host peer link (0/1 = single "
     "socket; capped at 16; autotune may lower the active count)",
     native=True)
_var("HOROVOD_SHM_DIR", "str", "",
     "Per-job shared-memory namespace for intra-host rings (provisioned "
     "and swept by hvdrun; empty disables the shm backend)", native=True)
_var("HOROVOD_SHM_SLOTS", "int", 16,
     "Slots per shm ring direction (min 2)", native=True)
_var("HOROVOD_SHM_SLOT_BYTES", "int", 1024 * 1024,
     "Payload bytes per shm ring slot (min 4096)", native=True)
_var("HOROVOD_SHM_GRANULE_BYTES", "int", 0,
     "Shm push granule; 0 = whole-slot pushes (autotune may override)",
     native=True)
_var("HOROVOD_TRANSPORT_CODECS", "str", "",
     "Per-link-level codec overrides, e.g. 'cross:fp16,local:none' — "
     "cross-host traffic may compress harder than intra-host shm")
_var("HOROVOD_TRANSPORT_CHECKSUM", "str", "auto",
     "CRC32C wire integrity on data-plane frames and shm slots: "
     "auto (on) | on | off (off restores the unframed fast path)",
     native=True)
_var("HOROVOD_LINK_RETRIES", "int", 4,
     "Bounded retransmits per corrupted frame offset before the link "
     "fails hard instead of looping", native=True)
_var("HOROVOD_SHM_STALL_MS", "int", 5000,
     "Shm ring progress silence past this degrades the link to the "
     "socket backend mid-job", native=True)
_var("HOROVOD_LINK_PROBE_SECONDS", "float", 30.0,
     "Seconds a degraded link waits before probing a rebuild of its "
     "preferred backend", native=True)

# ---------------------------------------------------------------------------
# Autotuner
# ---------------------------------------------------------------------------
_var("HOROVOD_AUTOTUNE", "bool", False,
     "1 enables the online Bayesian autotuner", native=True)
_var("HOROVOD_AUTOTUNE_LOG", "str", None,
     "CSV trace path for autotune trials (phase column: "
     "explore/pin/reopen)", native=True)
_var("HOROVOD_AUTOTUNE_WARMUP_SAMPLES", "int", 3,
     "Discarded warm-up samples before scoring starts", native=True)
_var("HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE", "int", 10,
     "Coordination cycles folded into one autotune sample", native=True)
_var("HOROVOD_AUTOTUNE_SAMPLES", "int", 5,
     "Samples per Bayesian trial", native=True)
_var("HOROVOD_AUTOTUNE_BAYES_TRIALS", "int", 20,
     "Bayesian trials before pinning the best configuration",
     native=True)
_var("HOROVOD_AUTOTUNE_DRIFT_RATIO", "float", 0.5,
     "Monitored-score ratio vs the pin anchor that re-opens exploration",
     native=True)
_var("HOROVOD_AUTOTUNE_DRIFT_WINDOWS", "int", 2,
     "Consecutive drifted monitoring windows required to re-open",
     native=True)

# ---------------------------------------------------------------------------
# Telemetry / timeline
# ---------------------------------------------------------------------------
_var("HOROVOD_METRICS", "bool", False,
     "1 turns metric collection on without any export path")
_var("HOROVOD_METRICS_PORT", "int", None,
     "Prometheus scrape port base (per-rank = base + local_rank; 0 = "
     "ephemeral)")
_var("HOROVOD_METRICS_FILE", "str", None,
     "Per-rank at-exit JSON dump path; under hvdrun also the merged "
     "summary")
_var("HOROVOD_METRICS_RPC", "str", None,
     "launcher host:port the at-exit snapshot is pushed to (set by "
     "hvdrun)")
_var("HOROVOD_EAGER_TIMELINE", "str", None,
     "Chrome-tracing JSON path for the eager-plane timeline")
_var("HOROVOD_TRACE", "bool", False,
     "1 turns cross-rank distributed tracing on (set by hvdrun --trace)",
     native=True)
_var("HOROVOD_TRACE_DIR", "str", None,
     "Directory for the per-rank span-log file fallback "
     "(spans.rank<k>.json)")
_var("HOROVOD_TRACE_RPC", "str", None,
     "launcher host:port span documents are pushed to (set by hvdrun)")
_var("HOROVOD_TRACE_SAMPLE", "int", 1,
     "Trace 1-in-N collective occurrences (1 = every one); pure in the "
     "occurrence index, so sampling stays rank-consistent", native=True)
_var("HOROVOD_TRACE_BUFFER", "int", 65536,
     "Per-rank span buffer capacity; overflow drops spans and counts "
     "hvd_trace_spans_dropped_total", native=True)
_var("HOROVOD_TIMELINE", "str", "",
     "Native coordinator timeline path (rank 0)", native=True)
_var("HOROVOD_TIMELINE_MARK_CYCLES", "bool", False,
     "1 adds per-cycle markers to the native timeline", native=True)
_var("HOROVOD_LOG_LEVEL", "str", "warning",
     "Log severity: trace|debug|info|warning|error", native=True)
_var("HOROVOD_LOG_HIDE_TIME", "bool", False,
     "1 strips timestamps from log lines (stable test output)",
     native=True)

# ---------------------------------------------------------------------------
# Resilience / elastic / fleet
# ---------------------------------------------------------------------------
_var("HOROVOD_FAULT_SPEC", "str", None,
     "Deterministic chaos injection spec "
     "(rank=,site=,after=,kind=[,attempt=]); site=transport kinds are "
     "consumed natively by the data plane", native=True)
_var("HOROVOD_STEP_GUARD", "str", "off",
     "In-graph NaN/Inf step-guard policy: off|skip|rollback|abort")
_var("HOROVOD_GUARD_NAN_BURST", "int", 1,
     "Consecutive bad steps before the guard restores last-known-good")
_var("HOROVOD_LKG_INTERVAL", "int", 1,
     "Steps between last-known-good snapshot commits")
_var("HOROVOD_SENTINEL_INTERVAL", "int", 0,
     "Steps between divergence-sentinel digest checks; 0 disables")
_var("HOROVOD_SPILL_DIR", "str", None,
     "Host-local scratch dir for warm-restart peer spills (provisioned "
     "by hvdrun)")
_var("HOROVOD_SPILL_INTERVAL", "int", 1,
     "LKG commits between peer-spill writes")
_var("HOROVOD_ELASTIC_BATCH_POLICY", "str", "lr_scale",
     "World-size-change continuity policy: lr_scale|accumulate")
_var("HOROVOD_ELASTIC_PREV_SIZE", "int", None,
     "Previous world size injected by the launcher across an elastic "
     "restart")
_var("HOROVOD_RESTART_ATTEMPT", "int", 0,
     "Elastic attempt counter injected by the launcher", native=True)
_var("HOROVOD_ON_RANK_FAILURE", "str", "restart",
     "Rank-death policy: restart (today's elastic relaunch), shrink "
     "(survivors reform the world in-process), shrink-then-restart "
     "(fall back to relaunch if reformation fails or the world would "
     "drop below --min-np)", native=True)
_var("HOROVOD_WORLD_EPOCH", "int", 0,
     "Membership epoch, bumped by the launcher once per in-process "
     "reformation; stale reformation specs are discarded against it",
     native=True)
_var("HOROVOD_REFORM_TIMEOUT", "float", 60.0,
     "Seconds a survivor waits for the launcher's reformation spec "
     "before falling back to the restart path")
_var("HOROVOD_TERMINATE_GRACE_SECONDS", "float", 30.0,
     "Grace between SIGTERM and SIGKILL when tearing ranks down")
_var("HOROVOD_HEALTH_RPC", "str", None,
     "launcher host:port of the heartbeat health plane (set by hvdrun)")
_var("HOROVOD_HEARTBEAT_INTERVAL", "float", 2.0,
     "Rank-side heartbeat push cadence; unset disables the health plane")
_var("HOROVOD_HEARTBEAT_DEADLINE", "float", None,
     "Silence past this marks a rank dead (default 5x the interval)")
_var("HOROVOD_HANG_DEADLINE", "float", 0.0,
     "Step-progress stall past this marks a rank hung; 0 disables")
_var("HOROVOD_FLEET_JOB", "str", None,
     "Job name injected by the fleet controller (labels metric exports)")

# ---------------------------------------------------------------------------
# Coordination plane (horovod_tpu/coordination.py, docs/control_plane.md)
# ---------------------------------------------------------------------------
_var("HOROVOD_COORD_TREE", "bool", False,
     "1 coordinates through the two-level host/leader tree instead of "
     "the flat rank-0 star (O(log N) control fan-in)", native=True)
_var("HOROVOD_COORD_EPOCH", "int", 0,
     "Coordinator lease epoch, bumped by the launcher on each "
     "re-election; stale-epoch control messages are discarded",
     native=True)
_var("HOROVOD_COORD_RANK", "int", 0,
     "Global rank currently holding the coordinator lease (injected by "
     "the launcher after failover)", native=True)
_var("HOROVOD_COORD_ELECTIONS", "int", 0,
     "Coordinator elections so far this job (launcher-injected; "
     "surfaces in stall reports and hvd_coord_elections_total)",
     native=True)
_var("HOROVOD_COORD_LEASE_SECONDS", "float", 10.0,
     "Coordinator lease term: heartbeats renew it, expiry triggers the "
     "deterministic re-election of the lowest healthy leader host")
_var("HOROVOD_COORD_MSG_RETRIES", "int", 4,
     "Bounded retransmits per control message (jittered exponential "
     "backoff between attempts)")
_var("HOROVOD_COORD_MSG_DEADLINE", "float", 10.0,
     "Total per-control-message deadline across all retransmits")
_var("HOROVOD_PARTITION_GRACE_SECONDS", "float", 30.0,
     "Launcher silence past this fences the rank (exit 75) as the "
     "partitioned side rather than a re-election trigger")
_var("HOROVOD_RPC_CONNECT_DEADLINE", "float", 60.0,
     "Total cap across all connect_with_retry dials; per-dial retries "
     "alone could otherwise stretch unbounded under chaos")

# ---------------------------------------------------------------------------
# Kernels / frameworks / misc knobs
# ---------------------------------------------------------------------------
_var("HOROVOD_FLASH_INTERPRET", "bool", False,
     "1 runs the flash-attention Pallas kernel in interpret mode")
_var("HOROVOD_FLASH_AUTO_MIN_T", "int", 1024,
     "Sequence length above which attention='auto' picks the flash "
     "kernel")
_var("HOROVOD_TF1_ASYNC", "bool", False,
     "1 enables TF1-session async collectives with pruned-sync reaping")
_var("HOROVOD_TF_SYNC_COLLECTIVES", "bool", False,
     "1 forces synchronous execution of the TF binding's collectives")
_var("HOROVOD_HIER_GATE_DIR", "str", None,
     "Scratch dir handshake for the np=4 hierarchical CI gate "
     "(tests/distributed/hierarchical_np4.py only)")

# ---------------------------------------------------------------------------
# Serving plane (horovod_tpu/serving/, docs/serving.md)
# ---------------------------------------------------------------------------
_var("HOROVOD_SERVING_MAX_BATCH", "int", 8,
     "Continuous-batching cap: max sequences per replica decode step")
_var("HOROVOD_SERVING_QUOTA", "int", 64,
     "Default per-tenant quota (queued + in-flight requests) when the "
     "TenantConfig leaves it unset")
_var("HOROVOD_SERVING_SLO_MS", "float", 0.0,
     "Default per-tenant SLO for admission control: reject when the "
     "estimated queue wait exceeds this; 0 disables")
_var("HOROVOD_SERVING_STATS", "str", None,
     "Path where the router publishes its stats snapshot (injected by "
     "the fleet controller for type=serving jobs; drives autoscaling)")
_var("HOROVOD_SERVING_STATS_INTERVAL", "float", 1.0,
     "Seconds between router stats-file publishes in Router.serve")
_var("HOROVOD_SERVING_GATE_DIR", "str", None,
     "Scratch dir handshake for the serving CI gates "
     "(tests/distributed/serving_*.py only)")


# ---------------------------------------------------------------------------
# Typed accessors: the read path basics.py / runner/ / native/runtime.py
# use.  Reading an unregistered name raises — the runtime complement of
# the hvdlint env-registry rule.
# ---------------------------------------------------------------------------

_UNSET = object()


class UnknownEnvVar(KeyError):
    """Raised when code reads a HOROVOD_* name absent from REGISTRY."""


def _entry(name: str) -> EnvVar:
    try:
        return REGISTRY[name]
    except KeyError:
        raise UnknownEnvVar(
            f"{name} is not in the horovod_tpu.config registry; add an "
            f"entry (python -m tools.hvdlint enforces this)") from None


def env_raw(name: str) -> Optional[str]:
    """The raw environment string, or None when unset (registered names
    only)."""
    _entry(name)
    return os.environ.get(name)


def env_str(name: str, default: Any = _UNSET) -> Any:
    e = _entry(name)
    v = os.environ.get(name)
    return (e.default if default is _UNSET else default) if v is None else v


def env_int(name: str, default: Any = _UNSET) -> Any:
    e = _entry(name)
    v = os.environ.get(name)
    if v is None or v.strip() == "":
        return e.default if default is _UNSET else default
    return int(v)


def env_float(name: str, default: Any = _UNSET) -> Any:
    e = _entry(name)
    v = os.environ.get(name)
    if v is None or v.strip() == "":
        return e.default if default is _UNSET else default
    return float(v)


def env_bool(name: str, default: Any = _UNSET) -> Any:
    """Mirror of the native EnvBool: unset/empty -> default, then "0"
    and case-insensitive "false" are False, anything else True."""
    e = _entry(name)
    v = os.environ.get(name)
    if v is None or v.strip() == "":
        return e.default if default is _UNSET else default
    return v.strip() not in ("0",) and v.strip().lower() != "false"


def describe() -> str:
    """The registry as a fixed-width reference table (also the
    ``python -m horovod_tpu.config`` output)."""
    rows = [(e.name, e.type, "native" if e.native else "py",
             "" if e.default is None else repr(e.default), e.doc)
            for e in sorted(REGISTRY.values())]
    w0 = max(len(r[0]) for r in rows)
    w3 = max(len(r[3]) for r in rows)
    out = []
    for name, type_, scope, dflt, doc in rows:
        out.append(f"{name:<{w0}}  {type_:<5} {scope:<6} "
                   f"{dflt:<{w3}}  {doc}")
    return "\n".join(out)


if __name__ == "__main__":
    print(describe())
