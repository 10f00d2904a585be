"""Per-rank distributed span recorder (``HOROVOD_TRACE``).

Every collective (and serving request) gets a correlation key
``(trace_id, span_id)`` that is identical on every rank WITHOUT any wire
change: the collective-schedule contract guarantees every rank submits
the same tensor names in the same order, so the pair
``(tensor name, per-name occurrence index)`` already names one logical
step of one collective globally.  ``trace_id`` is a deterministic hash
of that pair — two ranks recording spans for occurrence 17 of
``grad/dense0`` compute the same id with zero coordination, and the
launcher's merger correlates them by value.

The recorder is a bounded append-only buffer guarded by one lock taken
only on the *enabled* path; the disabled path is the telemetry no-op
contract — ``telemetry.spans()`` returns ``None`` and call sites are
written as::

    sp = telemetry.spans()
    if sp is not None:
        sp.record(name, "wait", seq, t0, t1, nbytes)

so tracing off costs one function call and an identity test (asserted by
``tests/test_spans.py``).  Sampling (``HOROVOD_TRACE_SAMPLE=N``) keeps
every Nth occurrence *per tensor name* — the decision is a pure function
of the occurrence index, so every rank samples the same steps and the
merged trace never shows half a collective.

Timestamps are ``time.monotonic()`` seconds.  The native plane's
``steady_clock`` is the same CLOCK_MONOTONIC domain on Linux, so drained
native spans interleave directly with Python spans per host; cross-host
correction happens at collection time via the launcher's RTT-halving
time-sync handshake (``runner/rpc.py:measure_clock_offset``), whose
result rides in the exported document as ``clock_offset``.

Start-up (docs/timeline.md, "Start-up")
---------------------------------------
The same module holds the host side of a process's way to its first
step, which needs no switch: :func:`span` (the one host-span primitive:
``import``, ``init`` and its children, ``build_mesh``,
``make_train_step``), the running totals of a layer's parts as they are
traced (:func:`part_traced`), and the **compile ledger**, one row a
program and stage from JAX's own monitoring events
(:func:`listen_to_jax`, registered once by ``hvd.init()``).
:func:`startup_report` returns all three as plain data.  They are kept
in bounded memory (:class:`_Kept`) and not in the :class:`SpanRecorder`,
which exists only under ``HOROVOD_TRACE``: a job's start-up is over
before an operator knows they wanted it traced.  With the recorder on a
phase span is forwarded to it as well (phase ``startup``, with the
optional fields ``parent`` and ``attrs``), so ``hvdrun --trace`` and
``tools/hvdtrace`` show it in the document they already merge.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import json
import os
import socket
import sys
import threading
from typing import Dict, List, Optional

# The package is half-imported when this module loads (its front door
# imports it); ``clock`` and the registry accessors are read at call time.
from horovod_tpu import telemetry
from horovod_tpu.telemetry import scopes

SCHEMA = "horovod_tpu.trace.v1"

# Request-scoped spans (serving, RPC) have no occurrence stream — they
# correlate by unique name alone and use this fixed sequence number.
REQUEST_SEQ = 0

_FNV_OFFSET = 0xCBF29CE484222325
_FNV_PRIME = 0x100000001B3
_MASK64 = (1 << 64) - 1
# Fibonacci multiplier spreads small sequence numbers across the id
# space so trace ids never collide on low bits alone.
_SEQ_MIX = 0x9E3779B97F4A7C15


def trace_id(name: str, seq: int) -> str:
    """Deterministic 64-bit correlation id for occurrence ``seq`` of
    tensor ``name`` — identical on every rank by construction (FNV-1a of
    the name xor the mixed occurrence index)."""
    h = _FNV_OFFSET
    for b in name.encode("utf-8", "replace"):
        h = ((h ^ b) * _FNV_PRIME) & _MASK64
    return f"{(h ^ ((seq * _SEQ_MIX) & _MASK64)) & _MASK64:016x}"


def _as_dict(span_id, name, phase, seq, t0, t1, nbytes, *phase_fields):
    out = {"name": name, "phase": phase, "seq": seq,
           "trace_id": trace_id(name, seq), "span_id": span_id,
           "t0": t0, "t1": t1, "bytes": nbytes}
    if phase_fields:        # a phase span's optional two
        out["parent"], out["attrs"] = phase_fields
    return out


class SpanRecorder:
    """Bounded, thread-safe span buffer for one rank."""

    def __init__(self, rank: int = 0, sample: int = 1,
                 capacity: int = 65536):
        self.rank = rank
        self.sample = max(int(sample), 1)
        self.capacity = max(int(capacity), 1)
        self.dropped = 0
        self.clock_offset: Optional[float] = None
        self.clock_rtt: Optional[float] = None
        self._lock = threading.Lock()
        self._seq: Dict[str, int] = {}
        # (name, phase, seq, t0, t1, bytes[, parent, attrs]) tuples;
        # dict-ified at export.
        self._spans: List[tuple] = []
        self._closed = False

    # -- hot path ----------------------------------------------------------

    def next_seq(self, name: str) -> int:
        """Allocate the next occurrence index for ``name`` (0-based).
        Counts EVERY occurrence, sampled or not, so the stream stays
        aligned with the other ranks' counters."""
        with self._lock:
            s = self._seq.get(name, -1) + 1
            self._seq[name] = s
        return s

    def sampled(self, seq: int) -> bool:
        """Record occurrence ``seq``?  Pure function of the index, hence
        identical on every rank (HOROVOD_TRACE_SAMPLE=N keeps seq%N==0)."""
        return self.sample <= 1 or (seq % self.sample) == 0

    def record(self, name: str, phase: str, seq: int, t0: float,
               t1: float, nbytes: int = 0) -> None:
        """Append one span; silently dropped (and counted) past
        capacity, after close, or when the occurrence is sampled out."""
        if self._closed or not self.sampled(seq):
            return
        self._append((str(name), str(phase), int(seq),
                      float(t0), float(t1), int(nbytes)))

    def record_phase(self, name: str, seq: int, t0: float, t1: float,
                     parent: Optional[int], attrs: dict) -> None:
        """A phase span of :func:`span` (phase :data:`STARTUP_PHASE`,
        ``seq`` its id in this process, ``parent`` its parent's): never
        sampled out, there are a dozen a process."""
        if not self._closed:
            self._append((str(name), STARTUP_PHASE, int(seq), float(t0),
                          float(t1), 0, parent, dict(attrs)))

    def _append(self, row: tuple) -> None:
        with self._lock:
            if self._closed:
                return
            if len(self._spans) >= self.capacity:
                self.dropped += 1
                return
            self._spans.append(row)

    def event(self, name: str, phase: str, t0: float, t1: float,
              nbytes: int = 0) -> None:
        """Request-scoped span: correlated by unique name alone (serving
        requests, RPC rounds), recorded under :data:`REQUEST_SEQ`."""
        self.record(name, phase, REQUEST_SEQ, t0, t1, nbytes)

    # -- export ------------------------------------------------------------

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)

    def document(self) -> dict:
        """The rank's span log (``horovod_tpu.trace.v1``): every span
        with its computed correlation ids, plus the attribution and
        clock metadata the merger needs."""
        with self._lock:
            spans = list(self._spans)
            dropped = self.dropped
        spans.sort(key=lambda s: s[3])
        return {
            "schema": SCHEMA,
            "rank": self.rank,
            "size": int(os.environ.get("HOROVOD_SIZE", "1") or 1),
            "host": socket.gethostname(),
            "pid": os.getpid(),
            "clock": "monotonic",
            # launcher_clock - rank_clock seconds (None = unmeasured;
            # merger treats it as 0, which is exact for same-host jobs).
            "clock_offset": self.clock_offset,
            "clock_sync_rtt": self.clock_rtt,
            "sample": self.sample,
            "dropped": dropped,
            "spans": [_as_dict(i, *row) for i, row in enumerate(spans)],
        }

    def close(self) -> None:
        with self._lock:
            self._closed = True


# ---------------------------------------------------------------------------
# At-exit export (mirrors the metrics exporter's push + file fallback)
# ---------------------------------------------------------------------------

def rank_log_path(dir_path: str, rank: int) -> str:
    return os.path.join(dir_path, f"spans.rank{rank}.json")


def write_rank_log(recorder: SpanRecorder, dir_path: str) -> str:
    """Atomic per-rank span-log dump (the launcher's fallback source for
    ranks whose RPC push never arrived)."""
    os.makedirs(dir_path, exist_ok=True)
    path = rank_log_path(dir_path, recorder.rank)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(recorder.document(), f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


def push_to_launcher(recorder: SpanRecorder, endpoint: str) -> bool:
    """Push the span log to ``hvdrun``'s trace collector over the
    authenticated RPC plane.  Collection failures are swallowed — the
    file fallback (and the job's exit code) must survive a dead
    launcher."""
    try:
        from horovod_tpu.runner import rpc
        addr, port = endpoint.rsplit(":", 1)
        key = rpc.job_key_bytes(os.environ.get("HOROVOD_SECRET_KEY"))
        reply = rpc.rpc_call(addr, int(port),
                             {"kind": "trace_report",
                              "report": recorder.document()},
                             key, timeout=10.0, retries=1)
        return bool(isinstance(reply, dict) and reply.get("ok"))
    except Exception:
        return False


def export_at_exit(recorder: SpanRecorder) -> None:
    """The recorder's exit hook: measure this rank's clock offset
    against the launcher (RTT-halving handshake), mirror the recorder
    totals into telemetry counters, push the span log over RPC, and
    always leave the file fallback behind."""
    endpoint = os.environ.get("HOROVOD_TRACE_RPC", "").strip()
    if endpoint:
        try:
            from horovod_tpu.runner import rpc
            addr, port = endpoint.rsplit(":", 1)
            key = rpc.job_key_bytes(os.environ.get("HOROVOD_SECRET_KEY"))
            sync = rpc.measure_clock_offset(addr, int(port), key)
            if sync is not None:
                recorder.clock_offset, recorder.clock_rtt = sync
        except Exception:
            pass
    if telemetry.enabled():
        n = len(recorder)
        if n:
            telemetry.counter(
                "hvd_trace_spans_total",
                "Span records captured by this rank's trace recorder",
            ).inc(n)
        if recorder.dropped:
            telemetry.counter(
                "hvd_trace_spans_dropped_total",
                "Span records dropped at the recorder's capacity bound",
            ).inc(recorder.dropped)
    pushed = endpoint and push_to_launcher(recorder, endpoint)
    dir_path = os.environ.get("HOROVOD_TRACE_DIR", "").strip()
    if dir_path:
        try:
            write_rank_log(recorder, dir_path)
        except OSError:
            pass  # exit path: an unwritable target must not mask the rc
    elif not pushed:
        pass  # nowhere to export; the in-process document remains readable
    recorder.close()


def configured_recorder() -> Optional[SpanRecorder]:
    """Build a recorder from the environment, or None when tracing is
    off (the telemetry front door calls this once at configure time)."""
    enabled = os.environ.get("HOROVOD_TRACE", "").strip() not in (
        "", "0", "false")
    if not (enabled or os.environ.get("HOROVOD_TRACE_DIR", "").strip()
            or os.environ.get("HOROVOD_TRACE_RPC", "").strip()):
        return None
    try:
        sample = int(os.environ.get("HOROVOD_TRACE_SAMPLE", "1") or 1)
    except ValueError:
        sample = 1
    try:
        cap = int(os.environ.get("HOROVOD_TRACE_BUFFER", "65536") or 65536)
    except ValueError:
        cap = 65536
    return SpanRecorder(
        rank=int(os.environ.get("HOROVOD_RANK", "0") or 0),
        sample=sample, capacity=cap)


# ---------------------------------------------------------------------------
# Start-up: phase spans, the parts' tracing totals, the compile ledger
# ---------------------------------------------------------------------------

STARTUP_PHASE = "startup"

# The bounds.  A process opens a dozen phase spans on its way to the first
# step (import, init and its children, a build_mesh and a make_train_step a
# mesh), and an elastic job that re-forms its world opens them again: 256
# holds twenty such start-ups.  A program is three ledger rows; the
# benchmark's cells make 7 to 21 programs a set-up, 22 to 68 rows (PERF.md,
# PR 49), and a job that computes eagerly between steps makes one a helper
# and shape: 2048 holds thirty such set-ups in about 0.5 MB.  Past a bound
# the first half stays (the start-up) and the second half holds the most
# recent rows (the recompile at step 40,000); what fell between is counted.
PHASE_SPANS_KEPT = 256
LEDGER_ROWS_KEPT = 2048


class _Kept:
    """A bounded list: the first ``bound // 2`` rows, then the most
    recent ``bound - bound // 2``; ``dropped`` counts what left."""

    def __init__(self, bound: int):
        self._first = bound // 2
        self._head: list = []
        self._tail: collections.deque = collections.deque(
            maxlen=bound - self._first)
        self._lock = threading.Lock()
        self.dropped = 0

    def append(self, row) -> None:
        with self._lock:
            if len(self._head) < self._first:
                self._head.append(row)
                return
            if len(self._tail) == self._tail.maxlen:
                self.dropped += 1
            self._tail.append(row)

    def rows(self) -> list:
        with self._lock:
            return self._head + list(self._tail)

    def clear(self) -> None:
        with self._lock:
            self._head.clear()
            self._tail.clear()
            self.dropped = 0


_phases = _Kept(PHASE_SPANS_KEPT)
_ledger = _Kept(LEDGER_ROWS_KEPT)
_parts: Dict[str, List[float]] = {}     # part -> [times traced, seconds]
_nested: Dict[str, List[float]] = {}    # function traced inside a stage
_parts_lock = threading.Lock()
_cache: Optional[dict] = None           # what enable_compile_cache() found
_ids = itertools.count(1)
_thread = threading.local()             # open spans and stages, cache state
_listening = False
_listen_lock = threading.Lock()


def _this_threads(what: str) -> list:
    """This thread's list of open ``spans`` or open ``stages``."""
    found = getattr(_thread, what, None)
    if found is None:
        found = []
        setattr(_thread, what, found)
    return found


class span(contextlib.ContextDecorator):
    """The host-span primitive: ``with span("build_mesh", axes=axes):``
    (or ``@span(...)`` on a function) keeps one record ``name, t0, t1,
    parent, attrs`` on :data:`telemetry.clock`, ``parent`` being the id of
    the span that was open on this thread when it began.  Under a
    ``jax.profiler`` session it is also the event ``hvd:<name>`` of
    ``/host:CPU``, on the device trace's clock.  ``attrs`` may be added to
    until the span closes (``with span("init") as sp: sp.attrs[...] =``).

    For a program's phases, a dozen a process: nothing inside a step, a
    kernel wrapper or any per-step path opens one."""

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs

    def _recreate_cm(self):
        return span(self.name, **self.attrs)

    def __enter__(self):
        stack = _this_threads("spans")
        self.id = next(_ids)
        self.parent = stack[-1].id if stack else None
        stack.append(self)
        # No import of jax from here: without it no profiler can be on.
        profiler = sys.modules.get("jax.profiler")
        self._annotation = (
            profiler.TraceAnnotation("hvd:" + self.name)
            if profiler is not None else contextlib.nullcontext())
        self._annotation.__enter__()
        self.t0 = telemetry.clock()
        return self

    def __exit__(self, *exc):
        t1 = telemetry.clock()
        self._annotation.__exit__(*exc)
        _this_threads("spans").remove(self)
        record_phase(self.name, self.t0, t1, self.attrs, self.parent,
                     self.id)
        return False


def record_phase(name: str, t0: float, t1: float,
                 attrs: Optional[dict] = None, parent: Optional[int] = None,
                 span_id: Optional[int] = None) -> None:
    """Keep a phase span that has ended (what :class:`span` does on exit;
    called with two clock reads where no ``with`` can be put: the
    package's own import)."""
    span_id = next(_ids) if span_id is None else span_id
    attrs = attrs or {}
    _phases.append((span_id, name, t0, t1, parent, attrs))
    recorder = telemetry.spans()
    if recorder is not None:
        recorder.record_phase(name, span_id, t0, t1, parent, attrs)
    _count_phase(name, t1 - t0)


def _count_phase(phase: str, seconds: float) -> None:
    if telemetry.enabled():
        telemetry.counter(
            "hvd_startup_seconds",
            "Seconds inside the program's start-up phases, by phase",
            phase=phase).inc(max(seconds, 0.0))


def part_traced(part: str, seconds: float) -> None:
    """One more trace of a layer's part (``models/parts.py``): a running
    total by part name, not a record a call (a step traces a part some
    hundred times)."""
    with _parts_lock:
        total = _parts.setdefault(part, [0, 0.0])
        total[0] += 1
        total[1] += seconds
    _count_phase("trace_part/" + part, seconds)


# JAX's monitoring events (jax/_src/dispatch.py, compiler.py): the three
# stages of making a program, each with ``fun_name``, and the persistent
# cache's events, which fire inside the last on the compiling thread.
_STAGES = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "mlir",
    "/jax/core/compile/backend_compile_duration": "backend_compile",
}
# A request that goes to the cache is a miss until the hit is reported
# (``cache_misses`` itself fires only where the entry is written).
_CACHE_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "miss",
    "/jax/compilation_cache/cache_hits": "hit",
}
_CACHE_READ = "/jax/compilation_cache/cache_retrieval_time_sec"


def _function_of(fun_name: str) -> str:
    """``jit(f)`` and ``pmap(f)`` (the module's name, which the lowering
    and the compile report) as ``f`` (what the trace reports)."""
    if fun_name.endswith(")"):
        head, _, inner = fun_name.partition("(")
        if head in ("jit", "pmap"):
            return inner[:-1]
    return fun_name


def _on_event(event: str, **_kwargs) -> None:
    state = _CACHE_EVENTS.get(event)
    if state is not None:
        _thread.cache = state


def _on_scalar(event: str, _value, **_kwargs) -> None:
    # JAX reports a stage's start as a scalar of the stage's own name.
    if event in _STAGES:
        _this_threads("stages").append(telemetry.clock())


def _on_duration(event: str, duration: float, **kwargs) -> None:
    stage = _STAGES.get(event)
    if stage is None:
        if event == _CACHE_READ:
            _thread.cache_read_s = duration
        return
    t1 = telemetry.clock()
    fun = _function_of(str(kwargs.get("fun_name", "?")))
    open_stages = _this_threads("stages")
    t0 = open_stages.pop() if open_stages else t1 - duration
    if open_stages and stage == "trace":
        # Traced inside another program's stage (a function jitted
        # inside the step: ``add``, ``matmul``, a kernel's wrapper, some
        # thousand times a step): a running total by name, not a row.
        with _parts_lock:
            total = _nested.setdefault(fun, [0, 0.0])
            total[0] += 1
            total[1] += duration
        return
    cache, cache_read_s = "none", 0.0
    if stage == "backend_compile":
        cache = getattr(_thread, "cache", "none")
        cache_read_s = getattr(_thread, "cache_read_s", 0.0)
        _thread.cache, _thread.cache_read_s = "none", 0.0
    spans_open = _this_threads("spans")
    _ledger.append((fun, stage, t0, t1, cache, cache_read_s,
                    spans_open[-1].id if spans_open else None,
                    "step" if fun in scopes.STEP_NAMES else None))
    if telemetry.enabled():
        telemetry.counter(
            "hvd_compile_seconds",
            "Seconds JAX spent making programs, by function and stage",
            fun=fun, stage=stage).inc(max(t1 - t0, 0.0))
        if stage == "backend_compile":
            telemetry.counter(
                "hvd_compiles_total",
                "Programs compiled or read from the persistent cache",
                fun=fun, cache=cache).inc()


def listen_to_jax() -> None:
    """Start the compile ledger: register its three listeners with
    ``jax.monitoring``, once a process however often it is called
    (``hvd.init()`` calls it)."""
    global _listening
    with _listen_lock:
        if _listening:
            return
        import jax.monitoring
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_scalar_listener(_on_scalar)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _listening = True


def cache_found(directory: Optional[str], cap_bytes: Optional[int]) -> dict:
    """What the persistent compile cache holds as the process turns it on
    (``utils/compile_cache.enable_compile_cache``): bytes, entries and
    the cap, from one ``os.scandir``."""
    global _cache
    nbytes = entries = 0
    try:
        with os.scandir(directory) as listing:
            for item in listing:
                if not item.is_file():
                    continue
                nbytes += item.stat().st_size
                # JAX's LRU cache keeps a "-atime" file beside an entry.
                entries += not item.name.endswith("-atime")
    except (OSError, TypeError):
        pass  # no directory yet: an empty cache
    _cache = {"dir": directory, "bytes": nbytes, "entries": entries,
              "cap_bytes": cap_bytes}
    if telemetry.enabled():
        telemetry.gauge(
            "hvd_compile_cache_bytes",
            "Bytes in the persistent compile cache's directory when the "
            "process turned it on").set(nbytes)
        telemetry.gauge(
            "hvd_compile_cache_entries",
            "Programs in the persistent compile cache when the process "
            "turned it on").set(entries)
        if cap_bytes is not None:
            telemetry.gauge(
                "hvd_compile_cache_cap_bytes",
                "The persistent compile cache's size limit "
                "(jax_compilation_cache_max_size)").set(cap_bytes)
    return _cache


def startup_report() -> dict:
    """The phase spans, the parts' tracing totals and the compile ledger
    as plain data (``hvd.startup_report()``), at any time: after the first
    step for the start-up, after a slow step for the recompile that made
    it slow.  Seconds on :data:`telemetry.clock`, ``now`` being the
    moment of the call.  ``compiles`` holds one row a program and stage;
    what was traced inside another program's stage is a total by name
    under ``nested_traces``, and lies inside that stage's row.  A span's
    children lie inside it and threads run beside one another, so a sum
    over spans or rows is a union of intervals, never an addition."""
    return {
        "clock": "monotonic",
        "now": telemetry.clock(),
        "spans": [
            {"id": i, "name": n, "t0": t0, "t1": t1, "parent": parent,
             "attrs": dict(attrs)}
            for i, n, t0, t1, parent, attrs in sorted(
                _phases.rows(), key=lambda row: row[2])],
        "parts": {name: {"count": int(count), "seconds": seconds}
                  for name, (count, seconds) in sorted(_parts.items())},
        "nested_traces": {fun: {"count": int(count), "seconds": seconds}
                          for fun, (count, seconds) in sorted(
                              _nested.items())},
        "compiles": [
            {"fun_name": fun, "stage": stage, "t0": t0, "t1": t1,
             "cache": cache, "cache_read_s": read_s, "parent": parent,
             "role": role}
            for fun, stage, t0, t1, cache, read_s, parent, role
            in _ledger.rows()],
        "cache": dict(_cache) if _cache else None,
        "dropped": {"spans": _phases.dropped, "compiles": _ledger.dropped},
    }


def reset_startup_for_tests() -> None:
    """Forget every phase span, total and ledger row (test-only; the
    listeners stay registered)."""
    global _cache
    _phases.clear()
    _ledger.clear()
    with _parts_lock:
        _parts.clear()
        _nested.clear()
    _cache = None


__all__ = ["SCHEMA", "REQUEST_SEQ", "SpanRecorder", "trace_id",
           "rank_log_path", "write_rank_log", "push_to_launcher",
           "export_at_exit", "configured_recorder", "STARTUP_PHASE", "span",
           "record_phase", "part_traced", "listen_to_jax", "cache_found",
           "startup_report"]
