"""Unified telemetry: metrics registry, exporters, eager timeline.

The observability base the reference never had (its story was the rank-0
Chrome timeline plus stall warnings): every layer of this rebuild — the
eager collectives, the native wait paths, the fusion bucketer, the RPC
plane, the elastic launcher and the checkpointer — records counters,
gauges and latency histograms here, and three export paths read them:

* ``HOROVOD_METRICS_PORT=9090`` — Prometheus text format on a stdlib
  HTTP server (per-rank port = base + local rank);
* ``HOROVOD_METRICS_FILE=/path/m.json`` — at-exit JSON dump per rank;
  under ``hvdrun`` the launcher also collects every rank's snapshot over
  the RPC plane and writes one merged, per-rank-attributed summary;
* ``hvd.metrics_snapshot()`` — the in-process API.

Separately, ``HOROVOD_EAGER_TIMELINE=/path/t.json`` enables the
eager-plane Chrome-tracing writer (per-tensor SUBMIT/WAIT/FINISH rows,
same dialect as the native timeline — see ``eager_timeline.py``).

The no-op contract
------------------
With every telemetry variable unset, instrumented hot paths must cost
one function call and a boolean test — nothing else.  Call sites are
written as::

    if telemetry.enabled():
        telemetry.counter("hvd_eager_ops_total", op="allreduce").inc()

and :func:`counter`/:func:`gauge`/:func:`histogram` additionally return
the shared :data:`NOOP` object when disabled, so even an unguarded call
allocates nothing and mutates nothing (asserted by
``tests/test_telemetry.py::test_disabled_path_is_noop``).
``HOROVOD_METRICS=1`` turns collection on without any export path (for
``hvd.metrics_snapshot()`` users).
"""

from __future__ import annotations

import atexit
import importlib
import os
import time
from typing import Dict, Optional

from horovod_tpu.telemetry.registry import (  # noqa: F401  (re-export)
    DEFAULT_BANDWIDTH_BUCKETS,
    DEFAULT_BYTE_BUCKETS,
    DEFAULT_TIME_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)

clock = time.monotonic   # one clock for every duration metric + timeline

# The span module by importlib, not ``from ... import spans``: the
# :func:`spans` accessor below shadows the submodule as a package
# attribute, so an attribute-based import would grab the function.  It
# imports this package back, for ``clock`` above and the accessors below,
# which it reads at call time.
_spans_module = importlib.import_module("horovod_tpu.telemetry.spans")
# The start-up's host side (spans.py, "Start-up"): the one host-span
# primitive and the one report, kept whatever the environment says.
span = _spans_module.span
startup_report = _spans_module.startup_report
# What feeds the report from the few sites that cannot open a span.
record_phase = _spans_module.record_phase
part_traced = _spans_module.part_traced
listen_to_jax = _spans_module.listen_to_jax
cache_found = _spans_module.cache_found

_ENV_VARS = ("HOROVOD_METRICS", "HOROVOD_METRICS_PORT",
             "HOROVOD_METRICS_FILE", "HOROVOD_METRICS_RPC")
# Span tracing (HOROVOD_TRACE / _DIR / _RPC) is configured alongside but
# independently of metrics, like the eager timeline: telemetry.spans()
# returns None when every trace variable is unset.


class _Noop:
    """Shared do-nothing metric: accepts every mutator of Counter, Gauge
    and Histogram.  Identity-comparable (``is telemetry.NOOP``) so tests
    can assert the disabled path was taken."""

    __slots__ = ()

    def inc(self, amount: float = 1.0) -> None:
        pass

    def dec(self, amount: float = 1.0) -> None:
        pass

    def set(self, value: float) -> None:
        pass

    def observe(self, value: float) -> None:
        pass


NOOP = _Noop()

_registry = MetricsRegistry()
_enabled = False
_timeline = None          # EagerTimelineWriter or None
_spans = None             # spans.SpanRecorder or None
_span_flush_hooks = []    # callables draining foreign span buffers
_metrics_flush_hooks = []  # callables mirroring foreign counters in
_http_server = None
_configured = False


def _env_truthy(name: str) -> bool:
    return os.environ.get(name, "").strip() not in ("", "0", "false")


def _configure_from_env() -> None:
    """Resolve enablement and export paths from the environment.  Runs
    once at first import (i.e. before any instrumented op can fire);
    :func:`reset_for_tests` re-runs it after monkeypatching."""
    global _enabled, _timeline, _http_server, _configured, _spans
    _configured = True
    # HOROVOD_METRICS is a boolean toggle ("0"/"false" disable); the
    # export-path variables enable whenever non-empty — including
    # HOROVOD_METRICS_PORT=0, which binds an ephemeral scrape port.
    _enabled = _env_truthy("HOROVOD_METRICS") or any(
        os.environ.get(v, "").strip()
        for v in _ENV_VARS if v != "HOROVOD_METRICS")

    port = os.environ.get("HOROVOD_METRICS_PORT", "").strip()
    if port and _http_server is None:
        from horovod_tpu.telemetry import exporter
        _http_server = exporter.start_http_server(
            exporter.resolve_metrics_port(int(port)),
            _registry.render_prometheus, _registry.snapshot)

    tl_path = os.environ.get("HOROVOD_EAGER_TIMELINE", "").strip()
    if tl_path and _timeline is None:
        from horovod_tpu.telemetry.eager_timeline import (
            EagerTimelineWriter, per_rank_path)
        _timeline = EagerTimelineWriter(
            per_rank_path(tl_path),
            rank=int(os.environ.get("HOROVOD_RANK", "0") or 0))

    if _spans is None:
        _spans = _spans_module.configured_recorder()


def _at_exit() -> None:
    """Flush every export path.  File/RPC targets are re-read from the
    environment HERE (not at configure time) so the launcher's per-rank
    overrides and late ``os.environ`` edits are honored."""
    global _timeline, _spans
    if _timeline is not None:
        _timeline.close()
        _timeline = None
    if _spans is not None:
        # Upstream planes (the native runtime's C++ buffer) flush into
        # the recorder first: this atexit handler can run BEFORE
        # basics.shutdown() (LIFO — basics registers its hook earlier,
        # at import), so without the explicit flush the native spans
        # would drain into an already-closed recorder and vanish.
        for hook in list(_span_flush_hooks):
            try:
                hook()
            except Exception:
                pass
        # Span export runs BEFORE the metrics push so the recorder's
        # hvd_trace_* totals land in this rank's metrics snapshot.
        try:
            _spans_module.export_at_exit(_spans)
        except Exception:
            pass  # exit path: tracing must never mask the job's rc
        _spans = None
    if not _enabled:
        return
    # Foreign metric planes (the native runtime's counter matrices)
    # mirror into the registry NOW: this handler can run before
    # basics.shutdown() (LIFO), so without the explicit flush a short
    # job's final deltas would miss the snapshot below.
    for hook in list(_metrics_flush_hooks):
        try:
            hook()
        except Exception:
            pass
    from horovod_tpu.telemetry import exporter
    endpoint = os.environ.get("HOROVOD_METRICS_RPC", "").strip()
    if endpoint:
        # Satellite of the trace plane that works even with tracing off:
        # measure this rank's monotonic-clock offset against the
        # launcher over the same collector the metrics push targets, so
        # the merged summary can attribute cross-host skew.
        skew = exporter.measure_launcher_offset(endpoint)
        if skew is not None:
            gauge("hvd_clock_skew_seconds",
                  "Monotonic-clock offset vs the launcher (launcher "
                  "minus rank, RTT-halving estimate)").set(skew[0])
        exporter.push_to_launcher(endpoint, _registry.snapshot)
    path = os.environ.get("HOROVOD_METRICS_FILE", "").strip()
    if path:
        try:
            from horovod_tpu.telemetry.eager_timeline import per_rank_path
            exporter.write_json(per_rank_path(path), _registry.snapshot)
        except OSError:
            pass  # exit path: an unwritable target must not mask the rc


atexit.register(_at_exit)
_configure_from_env()


# ---------------------------------------------------------------------------
# Hot-path API
# ---------------------------------------------------------------------------

def enabled() -> bool:
    """The one branch every instrumentation site tests first."""
    return _enabled


def timeline():
    """The eager timeline writer, or None when HOROVOD_EAGER_TIMELINE is
    unset (the timeline's own no-op guard, independent of metrics).
    Named ``timeline`` — not ``eager_timeline`` — because that attribute
    is the submodule holding the writer class."""
    return _timeline


def spans():
    """The distributed span recorder, or None when tracing is off (the
    tracing plane's own no-op guard, independent of metrics — see
    ``spans.py``)."""
    return _spans


def register_span_flush_hook(fn) -> None:
    """Register a callable that moves buffered spans from another plane
    (the native runtime's C++ buffer) into the recorder.  Hooks run
    right before the at-exit span export, which can precede
    ``basics.shutdown()`` in atexit order."""
    if fn not in _span_flush_hooks:
        _span_flush_hooks.append(fn)


def unregister_span_flush_hook(fn) -> None:
    try:
        _span_flush_hooks.remove(fn)
    except ValueError:
        pass


def register_metrics_flush_hook(fn) -> None:
    """Register a callable that mirrors another plane's counters (the
    native runtime's transport/hier matrices) into the registry.  Hooks
    run at exit right before the metrics push/dump, which can precede
    ``basics.shutdown()`` in atexit order — without them a short job's
    final deltas would never land in the snapshot."""
    if fn not in _metrics_flush_hooks:
        _metrics_flush_hooks.append(fn)


def unregister_metrics_flush_hook(fn) -> None:
    try:
        _metrics_flush_hooks.remove(fn)
    except ValueError:
        pass


def counter(name: str, help_text: str = "", **labels: str):
    if not _enabled:
        return NOOP
    return _registry.counter(name, help_text, labels or None)


def gauge(name: str, help_text: str = "", **labels: str):
    if not _enabled:
        return NOOP
    return _registry.gauge(name, help_text, labels or None)


def histogram(name: str, help_text: str = "", bounds=None, **labels: str):
    if not _enabled:
        return NOOP
    return _registry.histogram(name, help_text, labels or None,
                               bounds=bounds)


def observe_op(op: str, seconds: float, nbytes: int = 0) -> None:
    """One-call recorder for a completed eager collective: count,
    latency histogram, byte counter, effective-bandwidth histogram."""
    if not _enabled:
        return
    counter("hvd_eager_ops_total",
            "Completed eager-plane collective operations", op=op).inc()
    histogram("hvd_eager_op_seconds",
              "Eager collective latency, submit to completion (seconds)",
              bounds=DEFAULT_TIME_BUCKETS, op=op).observe(seconds)
    if nbytes:
        counter("hvd_eager_bytes_total",
                "Payload bytes submitted to eager collectives",
                op=op).inc(nbytes)
        histogram("hvd_eager_bandwidth_bytes_per_second",
                  "Effective eager collective bandwidth (payload bytes / "
                  "op latency)", bounds=DEFAULT_BANDWIDTH_BUCKETS,
                  op=op).observe(nbytes / max(seconds, 1e-9))


# ---------------------------------------------------------------------------
# Snapshot / lifecycle API
# ---------------------------------------------------------------------------

def registry() -> MetricsRegistry:
    return _registry


def metrics_snapshot() -> Dict[str, dict]:
    """The current registry contents (``hvd.metrics_snapshot()``).
    Empty when telemetry never ran — enable collection with any metrics
    env var or :func:`configure`."""
    return _registry.snapshot()


def render_prometheus() -> str:
    return _registry.render_prometheus()


def configure(enabled_flag: Optional[bool] = None) -> None:
    """Programmatic enable/disable (the launcher turns its own registry
    on with this when ``--metrics-file`` is passed; libraries embedding
    horovod_tpu can do the same without env vars)."""
    global _enabled
    if enabled_flag is not None:
        _enabled = bool(enabled_flag)


def flush() -> None:
    """Write every configured export target now (normally runs at
    interpreter exit; explicit for long-lived drivers and tests)."""
    _at_exit()


def reset_for_tests() -> None:
    """Clear the registry and re-resolve the environment.  Test-only:
    tears down the timeline writer (without terminator) and forgets a
    previously started HTTP server reference (daemon thread; freed at
    process exit)."""
    global _timeline, _http_server, _enabled, _spans
    if _timeline is not None:
        _timeline.close()
        _timeline = None
    if _spans is not None:
        _spans.close()
        _spans = None
    if _http_server is not None:
        _http_server.shutdown()
        _http_server = None
    _registry.clear()
    _configure_from_env()
