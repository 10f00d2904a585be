"""Self-healing training loop: step guard, divergence sentinel, rollback.

PR 1's fault tolerance handles *loud* failures (crashes, hangs,
blacklisting, restart-from-disk).  This module is the defense-in-depth
layer for *silent* ones — NaN bursts, replica divergence / bit flips —
so a bad step costs one step, not a relaunch (the in-memory-snapshot
recovery idea of Gemini, SOSP'23, on top of CheckFreq's, FAST'21,
iteration-boundary checkpointing):

* **Step guard** (in-graph, wired into ``parallel/data.py``,
  ``models/transformer.py`` and the benchmark's step builder): every
  jitted step checks loss + grads for NaN/Inf with a global ``is_finite``
  psum.  Collectives may not sit inside a ``lax.cond`` branch under SPMD,
  so the "conditional skip" is realized as an unconditional update
  followed by a per-leaf ``jnp.where(ok, new, old)`` select — XLA fuses
  the select, and the optimizer update it may waste ran on garbage
  anyway.  A bad step returns the *old* state and a NaN mean loss (the
  host-visible signal).  Policy via ``HOROVOD_STEP_GUARD``:
  ``off | skip | rollback | abort``.

* **Last-known-good rollback** (:class:`LastKnownGood`,
  :class:`StepGuard`): a host-side, double-buffered snapshot of the last
  *validated* ``params/opt_state/step``.  The pull to host happens off
  the critical path (``copy_to_host_async`` first, staged into a standby
  buffer, committed only after the bytes validate finite), and
  :meth:`StepGuard.after_step` restores it in-process on a NaN burst —
  every rank coordinates on a global ok flag first, so they roll back
  together or not at all.

* **Divergence sentinel**: every ``HOROVOD_SENTINEL_INTERVAL`` steps,
  allreduce a cheap per-rank digest (chained crc32, exact in float64) of
  params and optimizer state (the local shard bytes under ZeRO-1) with
  ``Min`` and ``Max`` and compare min == max.  On mismatch, an allgather
  names the diverging rank(s) (minority digest vs the modal one), and
  policy ``rollback`` heals in-process by re-broadcasting state from the
  lowest healthy rank — a diverged rank's *own* snapshots are
  finite-but-wrong, so rollback alone cannot heal divergence.

* **Preemption protocol**: :func:`install_preemption_handler` turns
  SIGTERM into a request flag; :func:`maybe_save_and_exit` performs a
  coordinated checkpoint at the next step boundary and exits with
  :data:`PREEMPTION_RC` (75, ``EX_TEMPFAIL``), which the launcher treats
  as preemption — no blacklist, no backoff, immediate reschedule
  (``runner/launch.py`` / ``runner/run.py``).

* **Warm restart** (PR 5): every Nth :class:`LastKnownGood` commit is
  also spilled to a host-local file in ``HOROVOD_SPILL_DIR`` (a per-job
  scratch dir the launcher keeps stable across elastic restarts), in a
  CRC-framed, torn-write-tolerant format.  After an elastic restart,
  :func:`warm_restore` runs the recovery ladder: surviving ranks load
  their spill, elect the freshest committed step with an eager ``Max``
  allreduce (lowest rank holding it wins), re-broadcast that state to
  the new world — falling back to the disk checkpoint, then fresh init,
  only when no survivor holds a valid spill.  The spill stores the
  *portable* (replicated optax) optimizer layout, so a ZeRO-1 run
  re-shards for the new world size on the way in.  A heartbeat sender
  (:func:`start_heartbeat`, auto-started by ``hvd.init()`` when the
  launcher injected ``HOROVOD_HEALTH_RPC``) reports
  ``(global_step, last_progress_ts)`` so the launcher can tell *dead*
  from *hung* workers.

Env knobs: ``HOROVOD_STEP_GUARD`` (policy), ``HOROVOD_SENTINEL_INTERVAL``
(0 = off), ``HOROVOD_LKG_INTERVAL`` (snapshot every N validated steps,
default 1), ``HOROVOD_GUARD_NAN_BURST`` (consecutive bad steps before a
rollback fires, default 1), ``HOROVOD_SPILL_DIR`` /
``HOROVOD_SPILL_INTERVAL`` (warm-restart spill), ``HOROVOD_HEALTH_RPC``
/ ``HOROVOD_HEARTBEAT_INTERVAL`` (heartbeats).  Everything emits
``hvd_guard_*`` / ``hvd_rollback_*`` / ``hvd_sentinel_*`` /
``hvd_warm_restart_*`` / ``hvd_heartbeat_*`` telemetry
(``docs/metrics.md``) and is chaos-testable via the ``nan`` /
``corrupt`` / ``heartbeat_drop`` / ``spill_corrupt`` fault kinds
(``faults.py``).  See ``docs/fault_tolerance.md``.
"""

from __future__ import annotations

import functools
import os
import pickle
import signal
import struct
import sys
import threading
import zlib
from typing import Any, Dict, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from horovod_tpu import basics, faults, telemetry
from horovod_tpu.native.runtime import MembershipChangedError  # noqa: F401
from horovod_tpu.ops import collective as _c
from horovod_tpu.telemetry import scopes
from horovod_tpu.utils.logging import get_logger

log = get_logger(__name__)

# Distinct exit code for "preempted, please reschedule me" — 75 is BSD
# EX_TEMPFAIL ("temporary failure, user is invited to retry"), far from
# the launcher's operator-stop codes (130/143) and from any shell/signal
# encoding (128+N).
PREEMPTION_RC = 75

GUARD_POLICIES = ("off", "skip", "rollback", "abort")

_POLICY_VAR = "HOROVOD_STEP_GUARD"
_SENTINEL_VAR = "HOROVOD_SENTINEL_INTERVAL"
_LKG_VAR = "HOROVOD_LKG_INTERVAL"
_BURST_VAR = "HOROVOD_GUARD_NAN_BURST"
_SPILL_DIR_VAR = "HOROVOD_SPILL_DIR"
_SPILL_INTERVAL_VAR = "HOROVOD_SPILL_INTERVAL"
_HEALTH_RPC_VAR = "HOROVOD_HEALTH_RPC"
_HEARTBEAT_INTERVAL_VAR = "HOROVOD_HEARTBEAT_INTERVAL"


class GuardAbort(RuntimeError):
    """Raised by :meth:`StepGuard.after_step` under policy ``abort``."""


class DivergenceError(RuntimeError):
    """Raised by the sentinel when replicas diverge and the policy does
    not heal (anything but ``rollback``).  Carries ``.ranks``."""

    def __init__(self, message: str, ranks: Sequence[int]):
        super().__init__(message)
        self.ranks = tuple(ranks)


def guard_policy() -> str:
    """The step-guard policy from ``HOROVOD_STEP_GUARD`` (default
    ``off``).  Read at *trace* time by :func:`apply_step_guard` — set it
    before building the training step."""
    value = os.environ.get(_POLICY_VAR, "off").strip().lower() or "off"
    if value not in GUARD_POLICIES:
        raise ValueError(
            f"{_POLICY_VAR}={value!r}: expected one of "
            f"{', '.join(GUARD_POLICIES)}")
    return value


def _env_interval(var: str, default: int, minimum: int = 0) -> int:
    raw = os.environ.get(var, "")
    if not raw.strip():
        return default
    try:
        value = int(raw)
    except ValueError:
        raise ValueError(f"{var}={raw!r} is not an integer")
    if value < minimum:
        raise ValueError(f"{var}={value} must be >= {minimum}")
    return value


# ---------------------------------------------------------------------------
# In-graph step guard
# ---------------------------------------------------------------------------

def all_finite(axes, loss, *trees):
    """In-graph global finiteness flag: True iff ``loss`` and every
    inexact leaf of ``trees`` is finite on **every** shard of ``axes``.
    The local flag is an int32 min over leaves; the global agreement is
    ``psum(flag) == psum(1)`` (the product of the axis sizes), so all
    shards compute the same boolean."""
    flags = []
    for leaf in jax.tree_util.tree_leaves((loss,) + tuple(trees)):
        arr = jnp.asarray(leaf)
        if jnp.issubdtype(arr.dtype, jnp.inexact):
            flags.append(jnp.all(jnp.isfinite(arr)).astype(jnp.int32))
    local = (functools.reduce(jnp.minimum, flags) if flags
             else jnp.int32(1))
    axes = tuple(a for a in (axes or ()) if a)
    if not axes:
        return local == 1
    return lax.psum(local, axes) == lax.psum(jnp.int32(1), axes)


def apply_step_guard(do_update, *, loss, grads, old_state, axes=(),
                     agree_axes=None):
    """Wrap one optimizer update with the NaN/Inf step guard.

    ``do_update()`` (a closure over ``grads``) must return a new state
    pytree congruent with ``old_state``.  Returns ``(state, mean_loss)``
    where ``mean_loss = pmean(loss, axes)``.  Under policy ``off`` this
    is exactly ``(do_update(), pmean(loss))`` — zero overhead.  Under any
    other policy the update runs unconditionally and the guard selects
    per leaf between new and old state (collectives cannot live inside a
    ``lax.cond`` branch under SPMD — the select *is* the skip), and a bad
    step's mean loss is poisoned to NaN so the host can see it
    (:meth:`StepGuard.after_step` keys off exactly that).

    ``agree_axes`` (default: ``axes``) is where the finiteness verdict is
    psummed — pass *every* mesh axis the state is sharded over (e.g. the
    tensor-parallel model axis on top of the data axes), so all shards
    select the same branch.

    The policy is read at trace time: build the step *after* setting
    ``HOROVOD_STEP_GUARD``.
    """
    axes = tuple(a for a in (axes or ()) if a)
    agree_axes = (axes if agree_axes is None
                  else tuple(a for a in agree_axes if a))
    with jax.named_scope(scopes.LOSS_MEAN):
        mean_loss = lax.pmean(loss, axes) if axes else loss
    policy = guard_policy()
    if policy == "off":
        return do_update(), mean_loss
    if telemetry.enabled():  # trace-time: counts guarded step *traces*
        telemetry.counter(
            "hvd_guard_traces_total",
            "training-step traces built with the step guard enabled",
            policy=policy).inc()
    with jax.named_scope(scopes.STEP_GUARD):
        ok = all_finite(agree_axes, loss, grads)
    new_state = do_update()
    with jax.named_scope(scopes.STEP_GUARD):
        guarded = jax.tree_util.tree_map(
            lambda new, old: jnp.where(ok, new, old), new_state, old_state)
        bad = jnp.asarray(jnp.nan, dtype=jnp.result_type(mean_loss))
        return guarded, jnp.where(ok, mean_loss, bad)


# ---------------------------------------------------------------------------
# Host-side helpers
# ---------------------------------------------------------------------------

def _host_finite(arr: np.ndarray) -> bool:
    """Finiteness of host bytes; ml_dtypes kinds (bf16 is 'V' to numpy)
    go through a float32 cast."""
    kind = getattr(arr.dtype, "kind", "")
    if kind in ("f", "c"):
        return bool(np.isfinite(arr).all())
    if kind == "V":  # bfloat16 & friends
        return bool(np.isfinite(np.asarray(arr, np.float32)).all())
    return True


def _pull_to_host(leaves):
    """Device->host for a list of leaves, overlapping the transfers:
    issue every async copy first, then materialize."""
    for leaf in leaves:
        copy_async = getattr(leaf, "copy_to_host_async", None)
        if copy_async is not None:
            copy_async()
    return [np.asarray(leaf) for leaf in leaves]


def _leaf_sharding(leaf):
    if isinstance(leaf, jax.Array):
        try:
            return leaf.sharding
        except Exception:  # pragma: no cover - deleted/donated buffers
            return None
    return None


def tree_digest(tree) -> int:
    """Cheap deterministic digest of a pytree: crc32 chained over the
    host bytes of every leaf in tree-flatten order.  crc32 < 2**32 is
    exactly representable in float64, so digests survive a float
    allreduce bit-exactly."""
    crc = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        arr = np.ascontiguousarray(np.asarray(leaf))
        crc = zlib.crc32(arr.tobytes(), crc)
    return crc


def _divergent_ranks(digests) -> list:
    """Name the diverging rank(s): rows of ``digests`` (one per rank)
    that differ from the modal row.  Ties break to the smallest row, so
    every rank computes the same answer from the same allgathered
    array."""
    rows = [tuple(np.asarray(row).ravel().tolist()) for row in digests]
    counts = {}
    for row in rows:
        counts[row] = counts.get(row, 0) + 1
    top = max(counts.values())
    modal = min(row for row, n in counts.items() if n == top)
    return [i for i, row in enumerate(rows) if row != modal]


class LastKnownGood:
    """Double-buffered host snapshot of the last validated training
    state.  :meth:`stage` pulls to the standby buffer and validates the
    bytes (nearly free — they are already on the host); :meth:`commit`
    flips it in only after the *global* verdict is in, so a poisoned or
    torn snapshot can never replace a good one.  Requires the state to
    be fully addressable from this process (true for this repo's
    per-process device meshes)."""

    def __init__(self):
        self._committed = None  # (step, treedef, host leaves, shardings)
        self._staged = None

    @property
    def available(self) -> bool:
        return self._committed is not None

    @property
    def step(self) -> Optional[int]:
        return self._committed[0] if self._committed else None

    def stage(self, params, opt_state, step: int) -> bool:
        """Pull ``(params, opt_state)`` into the standby buffer.  Returns
        False — and stages nothing — when the pulled bytes contain
        NaN/Inf (the live state is already poisoned)."""
        t0 = telemetry.clock()
        leaves, treedef = jax.tree_util.tree_flatten((params, opt_state))
        shardings = [_leaf_sharding(l) for l in leaves]
        host = _pull_to_host(leaves)
        ok = all(_host_finite(h) for h in host)
        if ok:
            self._staged = (int(step), treedef, host, shardings)
        else:
            self._staged = None
            if telemetry.enabled():
                telemetry.counter(
                    "hvd_rollback_snapshot_rejected_total",
                    "staged snapshots rejected for non-finite bytes").inc()
        if telemetry.enabled():
            telemetry.histogram(
                "hvd_rollback_snapshot_seconds",
                "host pull + validation time per staged snapshot",
            ).observe(telemetry.clock() - t0)
        return ok

    def commit(self) -> None:
        if self._staged is None:
            return
        self._committed, self._staged = self._staged, None
        if telemetry.enabled():
            telemetry.counter(
                "hvd_rollback_snapshots_total",
                "last-known-good snapshots committed").inc()

    def discard_stage(self) -> None:
        self._staged = None

    def restore(self) -> Tuple[Any, Any, int]:
        """Fresh device copies of the committed snapshot as
        ``(params, opt_state, step)``.  Explicit copies (``device_put``
        with the captured shardings) so the restored arrays never alias
        the host buffers — safe to feed straight back into a donating
        jitted step."""
        if self._committed is None:
            raise RuntimeError("no last-known-good snapshot available")
        step, treedef, host, shardings = self._committed
        leaves = [jax.device_put(h, s) if s is not None else jnp.array(h)
                  for h, s in zip(host, shardings)]
        params, opt_state = jax.tree_util.tree_unflatten(treedef, leaves)
        if telemetry.enabled():
            telemetry.counter(
                "hvd_rollback_restores_total",
                "in-process restores from last-known-good").inc()
        return params, opt_state, step


class GuardEvent(NamedTuple):
    """What :meth:`StepGuard.after_step` did.  ``action`` is one of
    ``ok | skip | rollback | heal``; ``step`` is the step the returned
    state corresponds to (the last-known-good step after a rollback)."""
    action: str
    step: int


class StepGuard:
    """Host-side coordinator for the in-graph guard: validates each
    step's outcome across ranks, maintains the last-known-good snapshot,
    runs the divergence sentinel, and decides skip/rollback/abort.

    Usage::

        guard = hvd.StepGuard()            # reads HOROVOD_STEP_GUARD etc.
        for step in range(n):
            params, opt_state, loss = train_step(params, opt_state, batch)
            params, opt_state, ev = guard.after_step(
                params, opt_state, step, loss)

    ``loss`` is the step's returned mean loss — NaN marks a guarded-bad
    step (see :func:`apply_step_guard`).  All ranks must call
    ``after_step`` for every step: the verdict is coordinated with an
    eager-plane ``Min`` allreduce of the local ok flag, so either every
    rank rolls back or none does (a NaN burst can hit one rank's shard
    only, but state must stay replicated)."""

    def __init__(self, policy: Optional[str] = None,
                 sentinel_interval: Optional[int] = None,
                 snapshot_interval: Optional[int] = None,
                 nan_burst: Optional[int] = None):
        self.policy = guard_policy() if policy is None else policy
        if self.policy not in GUARD_POLICIES:
            raise ValueError(
                f"policy {self.policy!r}: expected one of "
                f"{', '.join(GUARD_POLICIES)}")
        self.sentinel_interval = (
            _env_interval(_SENTINEL_VAR, 0)
            if sentinel_interval is None else int(sentinel_interval))
        self.snapshot_interval = (
            _env_interval(_LKG_VAR, 1, minimum=1)
            if snapshot_interval is None else max(1, int(snapshot_interval)))
        self.nan_burst = (
            _env_interval(_BURST_VAR, 1, minimum=1)
            if nan_burst is None else max(1, int(nan_burst)))
        self.lkg = LastKnownGood()
        self._bad_streak = 0
        self._warned_no_lkg = False
        # Warm-restart spill: every Nth commit is persisted host-locally
        # so a restarted world can recover the committed step from a
        # surviving peer instead of the (older) disk checkpoint.
        self._spill_dir = spill_dir()
        self.spill_interval = _env_interval(_SPILL_INTERVAL_VAR, 1,
                                            minimum=1)
        # Training loops may stash small host state here (RNG key, data
        # cursor) — it rides along in each spill and comes back from
        # warm_restore().
        self.spill_extra: Dict[str, Any] = {}
        self._commits = 0

    # -- coordination -----------------------------------------------------

    @staticmethod
    def _global_ok(local_ok: bool) -> bool:
        """Min-allreduce of the local verdict over the eager plane: the
        step is good only if it is good on *every* rank."""
        if basics.size() <= 1:
            return local_ok
        flag = np.array([1.0 if local_ok else 0.0], np.float32)
        out = _c._eager_allreduce(
            flag, _c.Min, "hvd.resilience.guard.ok", 1.0, 1.0)
        return bool(np.asarray(out)[0] >= 0.5)

    # -- sentinel ---------------------------------------------------------

    def _digests(self, params, opt_state) -> np.ndarray:
        opt_digest = None
        try:
            from horovod_tpu.parallel import zero
            if isinstance(opt_state, zero.ZeroShardedState):
                opt_digest = zero.local_state_digest(opt_state)
        except ImportError:  # pragma: no cover
            pass
        if opt_digest is None:
            opt_digest = tree_digest(opt_state)
        return np.array([float(tree_digest(params)), float(opt_digest)],
                        np.float64)

    def _sentinel(self, params, opt_state, step: int):
        """min/max digest agreement; on mismatch, name the diverging
        rank(s) and heal (policy ``rollback``) or raise."""
        if telemetry.enabled():
            telemetry.counter(
                "hvd_sentinel_checks_total",
                "divergence sentinel digest comparisons").inc()
        digest = self._digests(params, opt_state)
        lo = _c._eager_allreduce(
            digest, _c.Min, "hvd.resilience.sentinel.min", 1.0, 1.0)
        hi = _c._eager_allreduce(
            digest, _c.Max, "hvd.resilience.sentinel.max", 1.0, 1.0)
        if np.array_equal(np.asarray(lo), np.asarray(hi)):
            return params, opt_state, None
        gathered = _c._eager_allgather(
            digest.reshape(1, -1), "hvd.resilience.sentinel.digests")
        bad_ranks = _divergent_ranks(np.asarray(gathered))
        if telemetry.enabled():
            telemetry.counter(
                "hvd_sentinel_divergence_total",
                "sentinel checks that found diverged replicas").inc()
        message = (f"divergence sentinel at step {step}: replica digests "
                   f"disagree; diverging rank(s): {bad_ranks}")
        if self.policy != "rollback":
            log.error("%s", message)
            raise DivergenceError(message, bad_ranks)
        source = min(r for r in range(basics.size()) if r not in bad_ranks)
        log.error("%s — healing by re-broadcasting state from rank %d",
                  message, source)
        params, opt_state = _broadcast_state(params, opt_state, source)
        if telemetry.enabled():
            telemetry.counter(
                "hvd_sentinel_heals_total",
                "in-process divergence heals (state re-broadcast)").inc()
        return params, opt_state, GuardEvent("heal", step)

    # -- the step boundary -------------------------------------------------

    def after_step(self, params, opt_state, step: int, loss):
        """Validate one completed step.  Returns
        ``(params, opt_state, GuardEvent)`` — possibly the restored
        last-known-good state.  Must be called on every rank."""
        report_progress(step)  # feeds the heartbeat health plane
        if self.policy == "off" and self.sentinel_interval == 0:
            return params, opt_state, GuardEvent("ok", step)
        if telemetry.enabled():
            telemetry.counter(
                "hvd_guard_checks_total",
                "host-side step-boundary guard evaluations").inc()

        local_ok = bool(np.isfinite(np.asarray(loss, np.float64)).all())
        staged = False
        if (local_ok and self.policy == "rollback"
                and step % self.snapshot_interval == 0):
            staged = self.lkg.stage(params, opt_state, step)
            local_ok = staged  # a rejected pull means the state is bad
        ok = self._global_ok(local_ok)

        if ok:
            if staged:
                self.lkg.commit()
                if self._spill_dir:
                    self._commits += 1
                    if self._commits % self.spill_interval == 0:
                        self._spill(params, opt_state, step)
            self._bad_streak = 0
            if (self.sentinel_interval > 0 and step > 0
                    and step % self.sentinel_interval == 0
                    and basics.size() > 1):
                params, opt_state, event = self._sentinel(
                    params, opt_state, step)
                if event is not None:
                    return params, opt_state, event
            return params, opt_state, GuardEvent("ok", step)

        # Bad step (on at least one rank — all ranks agree it was bad).
        self.lkg.discard_stage()
        self._bad_streak += 1
        if telemetry.enabled():
            telemetry.counter(
                "hvd_guard_nonfinite_steps_total",
                "steps rejected by the guard (non-finite loss/grads)").inc()
        if self.policy == "abort":
            raise GuardAbort(
                f"step guard: non-finite loss/grads at step {step} "
                f"(policy abort)")
        if (self.policy == "rollback"
                and self._bad_streak >= self.nan_burst):
            if self.lkg.available:
                params, opt_state, good_step = self.lkg.restore()
                self._bad_streak = 0
                log.warning(
                    "step guard: non-finite step %d — rolled back to "
                    "last-known-good step %d", step, good_step)
                return params, opt_state, GuardEvent("rollback", good_step)
            if not self._warned_no_lkg:
                self._warned_no_lkg = True
                log.warning(
                    "step guard: rollback requested at step %d but no "
                    "last-known-good snapshot exists yet — skipping "
                    "instead", step)
        if telemetry.enabled():
            telemetry.counter(
                "hvd_guard_skipped_steps_total",
                "bad steps skipped (old state kept)").inc()
        log.warning("step guard: non-finite step %d skipped "
                    "(streak %d)", step, self._bad_streak)
        return params, opt_state, GuardEvent("skip", step)

    # -- warm-restart spill ------------------------------------------------

    def _spill(self, params, opt_state, step: int) -> None:
        """Persist the just-committed state host-locally.  Failures
        degrade (log + counter) — a broken scratch disk must not take
        down a healthy training loop."""
        try:
            write_spill(self._spill_dir, params, opt_state, step,
                        extra=self.spill_extra)
        except Exception as e:  # noqa: BLE001 — degrade, don't die
            log.warning("warm-restart spill at step %d FAILED (%s: %s); "
                        "continuing without it", step,
                        type(e).__name__, e)
            if telemetry.enabled():
                telemetry.counter(
                    "hvd_warm_restart_spill_failures_total",
                    "spill writes that raised (degraded, not fatal)").inc()


def _broadcast_state(params, opt_state, root_rank: int):
    """Re-broadcast ``(params, opt_state)`` from ``root_rank`` over the
    eager plane, re-placing each leaf with its original sharding —
    the divergence heal (a diverged rank's own snapshots are
    finite-but-wrong, so only a healthy rank's live state can heal
    it)."""
    leaves, treedef = jax.tree_util.tree_flatten((params, opt_state))
    out = []
    for i, leaf in enumerate(leaves):
        sharding = _leaf_sharding(leaf)
        host = np.ascontiguousarray(np.asarray(leaf))
        healed = _c._eager_broadcast(
            host, root_rank, f"hvd.resilience.heal.{i}")
        healed = np.asarray(healed, dtype=host.dtype)
        out.append(jax.device_put(healed, sharding)
                   if sharding is not None else jnp.array(healed))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# Warm restart: host-local spill files + peer-recovery election
# ---------------------------------------------------------------------------

SPILL_MAGIC = b"HVDSPILL"
SPILL_VERSION = 1
# magic, version, step, world_size, rank, payload_len, payload_crc32
_SPILL_HEADER = struct.Struct("!8sIqIIQI")


def spill_dir() -> Optional[str]:
    """The per-job host-local scratch dir (``HOROVOD_SPILL_DIR``,
    injected by the launcher and stable across elastic restarts), or
    None when warm restart is not configured."""
    return os.environ.get(_SPILL_DIR_VAR, "").strip() or None


def _spill_path(directory: str, rank: int) -> str:
    return os.path.join(directory, f"rank{int(rank)}.spill")


def write_spill(directory: str, params, opt_state, step: int, *,
                extra: Optional[Dict[str, Any]] = None,
                rank: Optional[int] = None,
                world_size: Optional[int] = None) -> str:
    """Persist a committed training state to a host-local spill file.

    The optimizer state is converted to the *portable* (replicated
    optax) layout first — under ZeRO-1 each rank's shard alone could
    never reconstruct the full state after a peer died, and the portable
    layout is what lets :func:`warm_restore` re-shard for a different
    world size through ``gather_full_state``/``scatter_full_state``.

    Torn-write tolerance: bytes go to a temp file (flushed + fsynced)
    and land via ``os.replace``; the header frames the payload with its
    length and crc32 so :func:`read_spill` rejects anything short or
    mangled instead of loading garbage."""
    rank = basics.rank() if rank is None else int(rank)
    world_size = basics.size() if world_size is None else int(world_size)
    from horovod_tpu import checkpoint as _ckpt
    portable_opt = _ckpt._gather_zero(opt_state)
    t0 = telemetry.clock()
    # np.array(..., order="C") rather than ascontiguousarray: the latter
    # promotes 0-d leaves (optax's step count) to shape (1,), which would
    # poison the layout-signature agreement check on restore.
    payload = {
        "params": [np.array(np.asarray(l), order="C")
                   for l in jax.tree_util.tree_leaves(params)],
        "opt": [np.array(np.asarray(l), order="C")
                for l in jax.tree_util.tree_leaves(portable_opt)],
        "extra": dict(extra or {}),
    }
    os.makedirs(directory, exist_ok=True)
    blob = pickle.dumps(payload, protocol=pickle.HIGHEST_PROTOCOL)
    header = _SPILL_HEADER.pack(SPILL_MAGIC, SPILL_VERSION, int(step),
                                world_size, rank, len(blob),
                                zlib.crc32(blob))
    path = _spill_path(directory, rank)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(header)
        f.write(blob)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    faults.mangle_spill(path, rank)
    if telemetry.enabled():
        telemetry.counter(
            "hvd_warm_restart_spills_total",
            "warm-restart spill files written").inc()
        telemetry.histogram(
            "hvd_warm_restart_spill_seconds",
            "host serialization + fsync time per spill").observe(
            telemetry.clock() - t0)
    log.debug("spilled step %d (%d bytes) to %s", step, len(blob), path)
    return path


def read_spill(path: str) -> Optional[Dict[str, Any]]:
    """Load + validate one spill file.  Returns the record (``step`` /
    ``world_size`` / ``rank`` / ``params`` / ``opt`` / ``extra``) or
    None — a missing, torn, or corrupt file is rejected with a warning
    and a counter, never raised on: the recovery ladder just moves to
    the next rung."""

    def _reject(why: str) -> None:
        log.warning("rejecting spill %s: %s", path, why)
        if telemetry.enabled():
            telemetry.counter(
                "hvd_warm_restart_spill_rejected_total",
                "spill files rejected by validation (torn write / CRC / "
                "version mismatch)").inc()
        return None

    try:
        with open(path, "rb") as f:
            raw = f.read()
    except OSError:
        return None
    if len(raw) < _SPILL_HEADER.size:
        return _reject(f"short header ({len(raw)} bytes)")
    magic, version, step, world, rank, plen, crc = \
        _SPILL_HEADER.unpack_from(raw)
    if magic != SPILL_MAGIC:
        return _reject("bad magic")
    if version != SPILL_VERSION:
        return _reject(f"unsupported version {version}")
    blob = raw[_SPILL_HEADER.size:]
    if len(blob) != plen:
        return _reject(f"torn payload ({len(blob)}/{plen} bytes)")
    if zlib.crc32(blob) != crc:
        return _reject("payload crc mismatch")
    try:
        payload = pickle.loads(blob)
    except Exception as e:  # noqa: BLE001 — reject-and-continue contract
        return _reject(f"unpicklable payload ({type(e).__name__}: {e})")
    return {"step": int(step), "world_size": int(world),
            "rank": int(rank), "path": path, **payload}


def best_local_spill(directory: str) -> Optional[Dict[str, Any]]:
    """The valid spill with the highest committed step on THIS host's
    scratch dir.  All ``*.spill`` files are scanned (not just this
    rank's): after a shrink the ranks renumber, and a host that ran two
    ranks may now run one — whichever surviving file is freshest
    wins."""
    try:
        entries = sorted(os.listdir(directory))
    except OSError:
        return None
    best = None
    for entry in entries:
        if not entry.endswith(".spill"):
            continue
        rec = read_spill(os.path.join(directory, entry))
        if rec is not None and (best is None or rec["step"] > best["step"]):
            best = rec
    return best


def _layout_signature(leaves) -> int:
    """crc32 over the (shape, dtype) of each leaf in order — cheap
    agreement check that a spilled state is congruent with the live
    template before any bytes go over the wire."""
    crc = 0
    for leaf in leaves:
        shape = tuple(np.shape(leaf))
        try:
            dtype = np.dtype(getattr(leaf, "dtype", None) or
                             np.result_type(leaf))
        except TypeError:
            dtype = np.dtype(object)
        crc = zlib.crc32(f"{shape}:{dtype.str};".encode(), crc)
    return crc


def _peer_recover(params, opt_state, local: Optional[Dict[str, Any]],
                  local_step: int, best: int):
    """Elect the spill source and re-broadcast its state to the world.

    Source = the LOWEST rank whose local spill holds the elected step
    ``best`` (eager ``Min`` allreduce over candidate ranks).  Before any
    state moves, the source's layout signature is broadcast and every
    rank checks it against its own live template — a globally
    coordinated ``Min`` verdict, so either everyone accepts the spill or
    everyone falls to the next ladder rung together.  Returns
    ``(params, opt_state, extra)`` or None on signature mismatch."""
    size, me = basics.size(), basics.rank()
    from horovod_tpu import checkpoint as _ckpt
    portable_opt = _ckpt._gather_zero(opt_state)
    p_leaves, p_def = jax.tree_util.tree_flatten(params)
    o_leaves, o_def = jax.tree_util.tree_flatten(portable_opt)
    template_sig = _layout_signature(p_leaves + o_leaves)

    if size > 1:
        cand = float(me) if (local is not None and local_step == best) \
            else float(size)
        src = int(np.asarray(_c._eager_allreduce(
            np.array([cand], np.float64), _c.Min,
            "hvd.resilience.warm.src", 1.0, 1.0))[0])
    else:
        src = 0
    i_am_src = me == src

    spill_sig = (_layout_signature(local["params"] + local["opt"])
                 if i_am_src else 0)
    sig = np.array([float(spill_sig)], np.float64)
    if size > 1:
        sig = _c._eager_broadcast(sig, src, "hvd.resilience.warm.sig")
    sig_ok = float(np.asarray(sig)[0]) == float(template_sig)
    if size > 1:
        sig_ok = StepGuard._global_ok(sig_ok)
    if not sig_ok:
        log.warning(
            "warm restart: spill at step %d (rank %d) does not match the "
            "live state layout — falling back down the recovery ladder",
            best, src)
        if telemetry.enabled():
            telemetry.counter(
                "hvd_warm_restart_layout_mismatch_total",
                "peer recoveries abandoned because the spilled layout "
                "disagreed with the live template").inc()
        return None

    spilled = (local["params"] + local["opt"]) if i_am_src else None
    out_leaves = []
    for i, leaf in enumerate(p_leaves + o_leaves):
        tmpl = np.asarray(leaf)
        host = (np.ascontiguousarray(np.asarray(spilled[i],
                                                dtype=tmpl.dtype))
                if i_am_src else np.ascontiguousarray(tmpl))
        if size > 1:
            host = _c._eager_broadcast(
                host, src, f"hvd.resilience.warm.state.{i}")
        got = np.asarray(host, dtype=tmpl.dtype).reshape(tmpl.shape)
        sharding = _leaf_sharding(leaf)
        out_leaves.append(jax.device_put(got, sharding)
                          if sharding is not None else jnp.asarray(got))
    n_p = len(p_leaves)
    new_params = jax.tree_util.tree_unflatten(p_def, out_leaves[:n_p])
    new_portable = jax.tree_util.tree_unflatten(o_def, out_leaves[n_p:])
    new_opt = _ckpt._scatter_zero(new_portable, opt_state)

    extra: Dict[str, Any] = dict(local["extra"]) if i_am_src else {}
    if size > 1:
        blob = pickle.dumps(extra, protocol=pickle.HIGHEST_PROTOCOL) \
            if i_am_src else b""
        ln = _c._eager_broadcast(np.array([len(blob)], np.int64), src,
                                 "hvd.resilience.warm.extra.len")
        n = int(np.asarray(ln)[0])
        if n:
            buf = (np.frombuffer(blob, np.uint8).copy() if i_am_src
                   else np.zeros(n, np.uint8))
            buf = _c._eager_broadcast(buf, src,
                                      "hvd.resilience.warm.extra")
            extra = pickle.loads(np.asarray(buf, np.uint8).tobytes())
        else:
            extra = {}
    return new_params, new_opt, extra


def warm_restore(params, opt_state, *, ckpt_dir: Optional[str] = None,
                 directory: Optional[str] = None):
    """The warm-restart recovery ladder, called on every rank of the new
    world right after (re)initializing the training state:

    1. **peer spill** — each rank loads its host's freshest valid spill;
       the highest committed step wins an eager ``Max`` allreduce
       election and the lowest rank holding it re-broadcasts that state;
    2. **disk checkpoint** — when no survivor holds a valid spill,
       restore the newest intact checkpoint under ``ckpt_dir`` (the
       repo-standard ``{"params", "opt_state", "step"}`` layout);
    3. **fresh init** — nothing to recover: train from the passed-in
       state.

    Returns ``(params, opt_state, step, source, extra)`` with ``source``
    in ``("spill", "disk", "fresh")``, ``step`` the recovered committed
    step (-1 for fresh), and ``extra`` the dict spilled via
    ``StepGuard.spill_extra`` (RNG key, data cursor; empty otherwise).
    ZeRO-1 optimizer states come back re-sharded for THIS world size —
    re-place them (``step.state_shardings`` / ``jax.device_put``) before
    training, exactly as after ``checkpoint.restore``."""
    directory = spill_dir() if directory is None else directory
    size = basics.size()
    local = best_local_spill(directory) if directory else None
    local_step = local["step"] if local is not None else -1

    if size > 1:
        best = int(np.asarray(_c._eager_allreduce(
            np.array([float(local_step)], np.float64), _c.Max,
            "hvd.resilience.warm.step", 1.0, 1.0))[0])
    else:
        best = local_step

    if best >= 0:
        recovered = _peer_recover(params, opt_state, local, local_step,
                                  best)
        if recovered is not None:
            new_params, new_opt, extra = recovered
            if telemetry.enabled():
                telemetry.counter(
                    "hvd_warm_restart_peer_recoveries_total",
                    "warm restarts recovered from a peer spill").inc()
            log.info("warm restart: recovered committed step %d from a "
                     "peer spill (no disk checkpoint read)", best)
            return new_params, new_opt, best, "spill", extra

    if ckpt_dir:
        from horovod_tpu import checkpoint
        found = np.zeros(1, np.int32)
        if basics.rank() == 0 and checkpoint.latest_step(ckpt_dir) \
                is not None:
            found[0] = 1
        if size > 1:
            found = _c._eager_broadcast(found, 0,
                                        "hvd.resilience.warm.disk")
        if int(np.asarray(found)[0]):
            template = {"params": params, "opt_state": opt_state,
                        "step": np.zeros((), np.int64)}
            state = checkpoint.restore(ckpt_dir, template)
            if telemetry.enabled():
                telemetry.counter(
                    "hvd_warm_restart_disk_fallbacks_total",
                    "warm restarts that fell back to the disk "
                    "checkpoint").inc()
            step = int(np.asarray(state["step"]))
            log.info("warm restart: no usable peer spill — restored "
                     "disk checkpoint step %d", step)
            return (state["params"], state["opt_state"], step, "disk",
                    {})

    if telemetry.enabled():
        telemetry.counter(
            "hvd_warm_restart_fresh_inits_total",
            "warm restarts with nothing to recover (fresh init)").inc()
    log.info("warm restart: nothing to recover — fresh init")
    return params, opt_state, -1, "fresh", {}


# ---------------------------------------------------------------------------
# Heartbeat sender (the worker half of the health plane)
# ---------------------------------------------------------------------------

_progress_lock = threading.Lock()
_progress_step = -1
_progress_ts = 0.0


def report_progress(step: int) -> None:
    """Record that training reached ``step`` (monotonic; older steps are
    ignored).  ``StepGuard.after_step`` calls this automatically; loops
    without a guard call it directly.  The heartbeat sender attaches the
    latest ``(step, ts)`` to every heartbeat so the launcher can tell a
    stalled step from a dead process."""
    global _progress_step, _progress_ts
    with _progress_lock:
        if step > _progress_step:
            _progress_step = int(step)
            _progress_ts = telemetry.clock()


def progress() -> Tuple[int, float]:
    with _progress_lock:
        return _progress_step, _progress_ts


class HeartbeatSender:
    """Daemon thread sending ``{"kind": "heartbeat", rank, step,
    progress_ts, epoch, seq}`` to the launcher's health plane every
    ``interval`` seconds over the authenticated RPC plane.  Single-shot
    dials with no retries and a short timeout — a slow or dead launcher
    must never stall training — and every failure is swallowed (counted,
    logged at debug).

    Two control-plane duties ride along (docs/control_plane.md):

    * Rank 0's successful sends are the coordinator lease renewals —
      counted as ``hvd_coord_lease_renewals_total`` and consumed by the
      launcher's ``_CoordinationPlane``.
    * The **partition fence**: a rank that cannot reach the launcher for
      ``HOROVOD_PARTITION_GRACE_SECONDS`` is the cut-off side of a
      partition (the launcher is a fixed point — its death kills local
      ranks anyway).  It exits with rc 75 (reschedule) rather than
      holding a stale gang hostage; 0 disables the fence.
    """

    def __init__(self, addr: str, port: int, key: bytes, rank: int,
                 interval: float):
        from horovod_tpu import config
        self.addr = addr
        self.port = int(port)
        self.key = key
        self.rank = int(rank)
        self.interval = max(0.05, float(interval))
        self.epoch = config.env_int("HOROVOD_COORD_EPOCH")
        # Membership epoch (fail-in-place): a fresh sender starts after
        # every reform_world() re-init, so reading the env once here is
        # enough for the launcher to tell old-world heartbeats (still
        # keyed by pre-reformation ranks) from reformed-world ones.
        self.world_epoch = config.env_int("HOROVOD_WORLD_EPOCH", 0) or 0
        self.partition_grace = config.env_float(
            "HOROVOD_PARTITION_GRACE_SECONDS")
        self._seq = 0
        self._last_ok: Optional[float] = None   # monotonic, None = never
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name="hvd-heartbeat", daemon=True)

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()

    def _fence_check(self, now: float) -> None:
        """Self-fence (exit rc 75) after a full grace window with zero
        launcher contact.  Only armed once a first heartbeat landed —
        start-up misconfiguration belongs to the rendezvous timeout,
        not the fence."""
        if not self.partition_grace or self._last_ok is None:
            return
        if now - self._last_ok <= self.partition_grace:
            return
        msg = (f"rank {self.rank}: no launcher contact for "
               f"{now - self._last_ok:.0f}s (> partition grace "
               f"{self.partition_grace:g}s); self-fencing with rc "
               f"{PREEMPTION_RC}")
        log.error(msg)
        print(f"horovod_tpu: {msg}", file=sys.stderr, flush=True)
        if telemetry.enabled():
            telemetry.counter(
                "hvd_partition_fences_total",
                "Ranks that self-fenced after losing launcher contact "
                "past the partition grace").inc()
            telemetry.flush()
        os._exit(PREEMPTION_RC)

    def _run(self) -> None:
        import time as _time
        from horovod_tpu.runner import rpc
        while not self._stop.wait(self.interval):
            if faults.drop_heartbeat(self.rank):
                if telemetry.enabled():
                    telemetry.counter(
                        "hvd_heartbeat_dropped_total",
                        "heartbeats suppressed by fault injection").inc()
                continue
            step, ts = progress()
            self._seq += 1
            try:
                resp = rpc.rpc_call(
                    self.addr, self.port,
                    {"kind": "heartbeat", "rank": self.rank,
                     "step": step, "progress_ts": ts,
                     "epoch": self.epoch, "seq": self._seq,
                     "world_epoch": self.world_epoch},
                    self.key, timeout=max(1.0, self.interval),
                    retries=0)
                self._last_ok = _time.monotonic()
                if telemetry.enabled():
                    telemetry.counter(
                        "hvd_heartbeat_sent_total",
                        "heartbeats delivered to the launcher").inc()
                    if self.rank == 0:
                        telemetry.counter(
                            "hvd_coord_lease_renewals_total",
                            "Coordinator lease renewals (rank 0 "
                            "heartbeats that reached the launcher)").inc()
                if isinstance(resp, dict) and resp.get("reform"):
                    # Fail-in-place: the launcher computed the survivors'
                    # new world and delivers this rank's slice of it in
                    # the heartbeat reply (the same channel remote
                    # preemption rides — the launcher can't signal a
                    # remote rank directly).  reform_world() consumes it.
                    _deliver_reform_spec(resp["reform"])
                if isinstance(resp, dict) and resp.get("preempt") and \
                        not _preempt_event.is_set():
                    # The launcher can't SIGTERM a remote rank (only its
                    # ssh client) — the preemption arrives here instead,
                    # and the next guarded step runs the same deferred
                    # coordinated-save path as the signal handler.
                    log.warning("launcher requested preemption via the "
                                "health plane")
                    if telemetry.enabled():
                        telemetry.counter(
                            "hvd_preempt_requests_total",
                            "preemption signals received").inc()
                    request_preemption()
            except Exception as e:  # noqa: BLE001 — never stall training
                if telemetry.enabled():
                    telemetry.counter(
                        "hvd_heartbeat_send_failures_total",
                        "heartbeat sends that failed (launcher slow, "
                        "restarting, or gone)").inc()
                log.debug("heartbeat send failed: %s: %s",
                          type(e).__name__, e)
                self._fence_check(_time.monotonic())


_heartbeat_sender: Optional[HeartbeatSender] = None
_heartbeat_lock = threading.Lock()


def start_heartbeat(rank: Optional[int] = None
                    ) -> Optional[HeartbeatSender]:
    """Start the heartbeat sender when the launcher configured the
    health plane (``HOROVOD_HEALTH_RPC=addr:port`` in this rank's env).
    Idempotent; called automatically from ``hvd.init()``.  Returns the
    sender, or None when the health plane is not configured."""
    global _heartbeat_sender
    target = os.environ.get(_HEALTH_RPC_VAR, "").strip()
    if not target:
        return None
    with _heartbeat_lock:
        if _heartbeat_sender is not None:
            return _heartbeat_sender
        addr, _, port = target.rpartition(":")
        if not addr or not port.isdigit():
            log.warning("%s=%r is not addr:port — heartbeats disabled",
                        _HEALTH_RPC_VAR, target)
            return None
        try:
            interval = float(
                os.environ.get(_HEARTBEAT_INTERVAL_VAR, "") or 2.0)
        except ValueError:
            log.warning("%s=%r is not a number — using 2.0s",
                        _HEARTBEAT_INTERVAL_VAR,
                        os.environ.get(_HEARTBEAT_INTERVAL_VAR))
            interval = 2.0
        if rank is None:
            rank = int(os.environ.get("HOROVOD_RANK", "0") or 0)
        from horovod_tpu.runner import rpc
        key = rpc.job_key_bytes(os.environ.get("HOROVOD_SECRET_KEY"))
        sender = HeartbeatSender(addr, int(port), key, rank, interval)
        sender.start()
        _heartbeat_sender = sender
        log.debug("heartbeat sender started -> %s (interval %.2fs)",
                  target, interval)
        return sender


def stop_heartbeat() -> None:
    global _heartbeat_sender
    with _heartbeat_lock:
        if _heartbeat_sender is not None:
            _heartbeat_sender.stop()
            _heartbeat_sender = None


# ---------------------------------------------------------------------------
# Fail-in-place: in-process world reformation on rank death
# (HOROVOD_ON_RANK_FAILURE=shrink|shrink-then-restart)
# ---------------------------------------------------------------------------

_reform_lock = threading.Lock()
_reform_event = threading.Event()
_reform_spec: Optional[dict] = None


def _deliver_reform_spec(spec) -> None:
    """Latch a launcher-delivered reformation spec (heartbeat reply).

    Stale specs — epoch not beyond the world this process is already
    running under — are dropped: after a reformation the heartbeat keys
    collide with the OLD rank numbering for a reply or two until the
    launcher's pending table clears, and re-applying the same spec would
    tear down the freshly reformed world."""
    global _reform_spec
    if not isinstance(spec, dict):
        return
    from horovod_tpu import config
    current = config.env_int("HOROVOD_WORLD_EPOCH", 0) or 0
    if int(spec.get("epoch", 0)) <= current:
        return
    with _reform_lock:
        _reform_spec = dict(spec)
        _reform_event.set()
    log.info("reformation spec received: epoch %s, new rank %s of %s",
             spec.get("epoch"), spec.get("rank"), spec.get("size"))


def _take_reform_spec(timeout: float) -> Optional[dict]:
    global _reform_spec
    if not _reform_event.wait(timeout):
        return None
    with _reform_lock:
        spec, _reform_spec = _reform_spec, None
        _reform_event.clear()
    return spec


def reform_world(params, opt_state, *, ckpt_dir: Optional[str] = None,
                 timeout: Optional[float] = None):
    """Reform the collective world in-process after a peer death.

    The recovery rung ABOVE transport self-healing and BELOW the elastic
    relaunch (docs/fault_tolerance.md): called from the training loop's
    ``except MembershipChangedError`` handler when
    ``HOROVOD_ON_RANK_FAILURE`` is ``shrink`` / ``shrink-then-restart``.
    Sequence:

    1. **wait for the spec** — the launcher detects the death, computes
       the survivors' contiguous re-ranking and delivers each rank its
       slice via the heartbeat reply (the sender is still running — the
       old world is broken, not this process);
    2. **tear down** the old world (``hvd.shutdown()``: drains the
       queue, closes transport links, stops the heartbeat);
    3. **adopt** the spec: new rank/size/local topology, the fresh
       rendezvous port, ``HOROVOD_WORLD_EPOCH`` and
       ``HOROVOD_ELASTIC_PREV_SIZE`` (so PR 5's elastic-continuity
       lr/accumulate policy sees the N->N-1 shrink);
    4. **re-init** (``hvd.init()``: new rendezvous among survivors, flat
       ring + hierarchical levels + shm/striped links rebuilt against
       the new peer set; heartbeat restarts under the new rank);
    5. **recover state** with the :func:`warm_restore` ladder (Max-step
       election, peer-spill re-broadcast, ZeRO re-shard for N-1).

    Returns ``(params, opt_state, step, source, extra)`` exactly like
    :func:`warm_restore`.  Raises ``TimeoutError`` when no spec arrives
    within ``timeout`` (default ``HOROVOD_REFORM_TIMEOUT``, 60s) — the
    caller re-raises the original failure and the job falls back to the
    relaunch path (shrink-then-restart) or dies (shrink)."""
    import time as _time
    from horovod_tpu import config
    if timeout is None:
        timeout = config.env_float("HOROVOD_REFORM_TIMEOUT", 60.0)
    t0 = _time.monotonic()
    pre_step, _ = progress()
    spec = _take_reform_spec(float(timeout))
    if spec is None:
        raise TimeoutError(
            f"no reformation spec from the launcher within {timeout:g}s "
            f"(HOROVOD_REFORM_TIMEOUT) — falling back to the restart "
            f"path")
    basics.shutdown()
    os.environ["HOROVOD_ELASTIC_PREV_SIZE"] = str(
        spec.get("prev_size", int(spec["size"]) + 1))
    os.environ["HOROVOD_WORLD_EPOCH"] = str(spec["epoch"])
    os.environ["HOROVOD_RANK"] = str(spec["rank"])
    os.environ["HOROVOD_SIZE"] = str(spec["size"])
    os.environ["HOROVOD_LOCAL_RANK"] = str(spec["local_rank"])
    os.environ["HOROVOD_LOCAL_SIZE"] = str(spec["local_size"])
    # Overwrite unconditionally: the launch-time values are stale for
    # the reformed world and basics.init() would otherwise read them.
    os.environ["HOROVOD_CROSS_RANK"] = str(spec.get(
        "cross_rank", int(spec["rank"]) // max(int(spec["local_size"]), 1)))
    os.environ["HOROVOD_CROSS_SIZE"] = str(spec.get("cross_size", 1))
    os.environ["HOROVOD_RENDEZVOUS_ADDR"] = str(spec["rendezvous_addr"])
    os.environ["HOROVOD_RENDEZVOUS_PORT"] = str(spec["rendezvous_port"])
    if spec.get("topology"):
        os.environ["HOROVOD_TOPOLOGY"] = str(spec["topology"])
    basics.init()
    new_params, new_opt, step, source, extra = warm_restore(
        params, opt_state, ckpt_dir=ckpt_dir)
    seconds = _time.monotonic() - t0
    if telemetry.enabled():
        telemetry.histogram(
            "hvd_failinplace_reformation_seconds",
            "Wall time from membership-change detection to the reformed "
            "world's state recovery completing",
            bounds=telemetry.DEFAULT_TIME_BUCKETS).observe(seconds)
        telemetry.gauge(
            "hvd_failinplace_world_epoch",
            "Membership epoch this rank is running under (0 = never "
            "reformed)").set(int(spec["epoch"]))
        if basics.rank() == 0 and pre_step >= 0 and step >= 0:
            # New rank 0 only, so the merged summary books the loss once.
            telemetry.counter(
                "hvd_failinplace_steps_lost_total",
                "Steps rolled back by in-process reformations (progress "
                "high-water minus the recovered committed step)").inc(
                    max(pre_step - step, 0))
    log.info("fail-in-place: reformed world epoch %s as rank %d/%d in "
             "%.2fs (recovered step %d from %s)", spec["epoch"],
             basics.rank(), basics.size(), seconds, step, source)
    return new_params, new_opt, step, source, extra


# ---------------------------------------------------------------------------
# Preemption protocol
# ---------------------------------------------------------------------------

_preempt_event = threading.Event()
_handler_lock = threading.Lock()
_handler_installed = False


def install_preemption_handler(signum: int = signal.SIGTERM) -> None:
    """Turn ``signum`` (default SIGTERM — what schedulers send on
    preemption) into a deferred request: the handler only sets a flag;
    the training loop acts on it at the next step boundary via
    :func:`maybe_save_and_exit`.  Idempotent; main thread only (signal
    module constraint)."""
    global _handler_installed
    with _handler_lock:
        if _handler_installed:
            return

        def _on_signal(sig, frame):  # noqa: ARG001
            _preempt_event.set()
            if telemetry.enabled():
                telemetry.counter(
                    "hvd_preempt_requests_total",
                    "preemption signals received").inc()

        signal.signal(signum, _on_signal)
        _handler_installed = True
        log.debug("preemption handler installed for signal %d", signum)


def preemption_requested() -> bool:
    return _preempt_event.is_set()


def request_preemption() -> None:
    """Programmatic equivalent of receiving the preemption signal (used
    by tests and embedding frameworks with their own signal plumbing)."""
    _preempt_event.set()


def exit_preempted() -> "None":
    """Exit with :data:`PREEMPTION_RC` via ``sys.exit`` so atexit hooks
    (telemetry dumps, async-checkpoint drain) still run."""
    log.warning("exiting with preemption rc %d (reschedule, do not "
                "blacklist)", PREEMPTION_RC)
    # Kill the heartbeat first: a sender racing the interpreter teardown
    # can otherwise push one last beat AFTER the launcher's monitor was
    # reset for the next attempt, haunting the new world's bookkeeping.
    stop_heartbeat()
    sys.exit(PREEMPTION_RC)


def maybe_save_and_exit(ckpt_dir: str, state, step: int) -> bool:
    """Call at every step boundary.  No-op (returns False) unless a
    preemption was requested; then every rank performs the coordinated
    synchronous save (the signal is delivered process-group-wide, so all
    ranks reach this together), drains any in-flight async write first,
    and exits with :data:`PREEMPTION_RC`."""
    if not _preempt_event.is_set():
        return False
    from horovod_tpu import checkpoint
    log.warning("preemption requested — coordinated save at step %d "
                "to %s", step, ckpt_dir)
    # The save below can take a while on big states; keep the health
    # plane fed so the watchdog never mistakes a rank mid-coordinated-
    # save for a hung one and SIGKILLs it out of its own rescue.
    report_progress(step)
    checkpoint.wait_for_async_save()
    checkpoint.save(ckpt_dir, state, step=step)
    if telemetry.enabled():
        telemetry.counter(
            "hvd_preempt_saves_total",
            "coordinated preemption saves completed").inc()
    exit_preempted()
    return True  # pragma: no cover — sys.exit above


def _reset_for_tests() -> None:
    """Clear module state (preemption flag + handler marker + heartbeat
    sender + progress)."""
    global _handler_installed, _progress_step, _progress_ts
    _preempt_event.clear()
    with _handler_lock:
        _handler_installed = False
    stop_heartbeat()
    with _progress_lock:
        _progress_step = -1
        _progress_ts = 0.0
