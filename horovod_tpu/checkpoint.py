"""Checkpoint save/restore with rank-0-writes + broadcast consistency.

The reference has no checkpoint subsystem; its convention (SURVEY §5.4)
is "rank 0 writes framework checkpoints; on start, restore on rank 0 and
broadcast state to all ranks" — ``BroadcastGlobalVariablesHook``
(reference ``tensorflow/__init__.py:159-192``), torch
``broadcast_parameters``/``broadcast_optimizer_state``
(``torch/__init__.py:255-403``), and every example gates ``checkpoint_dir``
on ``hvd.rank() == 0`` (``examples/tensorflow_mnist.py:144``).

This module makes that convention a first-class API for JAX/flax/optax
training state, backed by orbax (the TPU-ecosystem checkpointer):

    state = {"params": params, "opt_state": opt_state, "step": step}
    hvd.checkpoint.save(ckpt_dir, state, step=step)       # rank 0 only
    state = hvd.checkpoint.restore(ckpt_dir, state)       # restore+broadcast

``restore`` reads on rank 0 and broadcasts every leaf over the eager
plane, so all ranks resume bit-identical even if their local filesystems
diverge — the same consistency guarantee the reference gets from
``BroadcastGlobalVariablesCallback``.
"""

from __future__ import annotations

import atexit
import os
import threading
from typing import Any, Optional

import jax
import numpy as np

from horovod_tpu import basics, telemetry
from horovod_tpu.ops import collective as _c
from horovod_tpu.utils.logging import get_logger

log = get_logger(__name__)


def _tree_broadcast(tree: Any, root_rank: int, name_prefix: str) -> Any:
    """Broadcast every array leaf of a pytree from ``root_rank``, keyed by
    its tree path so wire names agree across ranks."""
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    treedef = jax.tree_util.tree_structure(tree)
    out_leaves = []
    for path, leaf in leaves_with_paths:
        key = name_prefix + jax.tree_util.keystr(path)
        arr = np.asarray(leaf)
        out = _c._eager_broadcast(arr, root_rank, key)
        # preserve jax vs numpy leaf type and dtype
        if isinstance(leaf, jax.Array):
            import jax.numpy as jnp
            out = jnp.asarray(out, dtype=leaf.dtype)
        else:
            out = np.asarray(out, dtype=arr.dtype)
        out_leaves.append(out)
    return jax.tree_util.tree_unflatten(treedef, out_leaves)


def _gather_zero(state: Any) -> Any:
    """Replace every ZeRO-1 sharded optimizer state in ``state`` with the
    equivalent REPLICATED optax state (full per-leaf pytree).

    Checkpoints are written in this layout, so they are independent of the
    mesh the run happened to use: a 8-way-sharded run's checkpoint restores
    into a 32-way (or replicated) run unchanged."""
    from horovod_tpu.parallel import zero
    return jax.tree_util.tree_map(
        lambda x: zero.gather_full_state(x) if zero.is_zero_state(x) else x,
        state, is_leaf=zero.is_zero_state)


def _scatter_zero(state: Any, template: Any) -> Any:
    """Inverse of :func:`_gather_zero` on restore: wherever ``template``
    holds a ZeRO-1 sharded state, re-shard the restored replicated-layout
    subtree into the template's flat-bucket layout (the template — the
    freshly ``init``-ed state — supplies the bucketing plan for THIS
    mesh, which may differ from the mesh that saved)."""
    from horovod_tpu.parallel import zero
    leaves = jax.tree_util.tree_leaves(template, is_leaf=zero.is_zero_state)
    if not any(zero.is_zero_state(l) for l in leaves):
        return state
    return jax.tree_util.tree_map(
        lambda t, s: zero.scatter_full_state(s, like=t)
        if zero.is_zero_state(t) else s,
        template, state, is_leaf=zero.is_zero_state)


def _valid_steps(ckpt_dir: str) -> list:
    """Step numbers with a finalized checkpoint directory, ascending.

    A rank 0 killed mid-save (exactly what elastic restarts recover
    from) leaves orbax's temporary directory behind — the atomic-rename
    commit never happened.  Those leftovers, and finalized step dirs
    that lost their payload, are skipped with a warning: a restart must
    resume from the newest INTACT checkpoint, not die on the debris of
    the crash it is recovering from."""
    try:
        entries = os.listdir(ckpt_dir)
    except OSError:
        return []
    steps = []
    for entry in sorted(entries):
        path = os.path.join(ckpt_dir, entry)
        if not os.path.isdir(path):
            continue
        if not entry.isdigit():
            if "tmp" in entry:
                log.warning(
                    "skipping half-written checkpoint %s (temporary "
                    "directory left by an interrupted save)", path)
            continue
        try:
            empty = not os.listdir(path)
        except OSError:
            empty = True
        if empty:
            log.warning("skipping corrupt checkpoint %s: directory is "
                        "empty", path)
            continue
        steps.append(int(entry))
    return sorted(steps)


def _write_step(ckpt_dir: str, state: Any, step: int,
                max_to_keep: Optional[int]) -> None:
    """The orbax write of ``state`` to ``ckpt_dir/<step>``, finished or
    raised when this returns.  Both callers block on the write (``save``
    by contract, ``save_async`` on a thread of its own), so orbax's own
    asynchronous layer is switched off: when its directory creation
    fails, the non-daemon threads it has already started wait out the
    coordination timeout for a signal that never comes, and the process
    cannot exit until they do."""
    import orbax.checkpoint as ocp
    with ocp.CheckpointManager(
            ckpt_dir,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep,
                enable_async_checkpointing=False)) as mgr:
        mgr.save(step, args=ocp.args.StandardSave(state))


def save(ckpt_dir: str, state: Any, step: int = 0,
         max_to_keep: Optional[int] = None) -> Optional[str]:
    """Write ``state`` (a pytree) to ``ckpt_dir/<step>``; rank 0 writes,
    every other rank waits on a success-flag broadcast so no rank races
    ahead and reads a half-written checkpoint.  Returns the checkpoint
    path on rank 0 when the write succeeded, None elsewhere / on failure.

    The flag broadcast *replaces* the old barrier and fixes its deadlock:
    if rank 0's orbax write raises, peers used to wait forever in
    ``rt.barrier`` — now the exception is caught, counted
    (``hvd_checkpoint_save_failures_total``), broadcast as ``ok=0``, and
    everyone continues (degrade, don't deadlock — the next save retries).

    ZeRO-1 sharded optimizer states (``shard_optimizer=True`` /
    ``hvd.sharded_optimizer``) are gathered to the replicated per-leaf
    layout before writing, so checkpoints stay layout-independent — see
    :func:`_gather_zero`.  Any in-flight :func:`save_async` write is
    drained first."""
    wait_for_async_save()
    path = None
    ok = np.zeros(1, np.int32)
    if basics.rank() == 0:
        try:
            state = _gather_zero(state)
            ckpt_dir = os.path.abspath(ckpt_dir)
            t0 = telemetry.clock()
            _write_step(ckpt_dir, state, step, max_to_keep)
            if telemetry.enabled():
                telemetry.counter("hvd_checkpoint_saves_total",
                                  "Checkpoints written by rank 0").inc()
                telemetry.histogram(
                    "hvd_checkpoint_save_seconds",
                    "Wall time of a rank-0 checkpoint save").observe(
                    telemetry.clock() - t0)
            path = os.path.join(ckpt_dir, str(step))
            ok[0] = 1
            log.info("checkpoint step %d written to %s", step, path)
        except Exception as e:  # noqa: BLE001 — degrade, don't deadlock
            log.error("checkpoint save step %d to %s FAILED (%s: %s); "
                      "continuing without a checkpoint", step, ckpt_dir,
                      type(e).__name__, e)
            if telemetry.enabled():
                telemetry.counter(
                    "hvd_checkpoint_save_failures_total",
                    "rank-0 checkpoint writes that raised").inc()
    if basics.size() > 1:
        ok = _c._eager_broadcast(ok, 0, f"hvd.checkpoint.save.ok.{step}")
    return path if int(np.asarray(ok)[0]) else None


class _AsyncSave:
    """One in-flight background checkpoint write (rank 0 only)."""

    __slots__ = ("thread", "step", "path", "error")

    def __init__(self, step: int):
        self.thread = None
        self.step = step
        self.path = None
        self.error = None


_async_lock = threading.Lock()
_async_current: Optional[_AsyncSave] = None
_async_atexit_registered = False


def save_async(ckpt_dir: str, state: Any, step: int = 0,
               max_to_keep: Optional[int] = None) -> Optional[str]:
    """CheckFreq-style asynchronous save: snapshot ``state`` to host
    memory *now* (the only part that blocks the step — a device pull),
    then write it with orbax on a background thread.  Returns the
    eventual checkpoint path on rank 0, None elsewhere.

    At most one write is in flight: a previous one is drained first
    (:func:`wait_for_async_save` — also registered atexit, so a job that
    exits right after ``save_async`` never loses the checkpoint).  No
    cross-rank barrier or flag is needed, unlike :func:`save`: only
    rank 0 touches the directory, readers are protected by orbax's
    atomic rename plus :func:`_valid_steps`' intact-directory filter,
    and a background failure is logged + counted
    (``hvd_ckpt_async_failures_total``) when drained, never raised."""
    global _async_current, _async_atexit_registered
    wait_for_async_save()
    if basics.rank() != 0:
        return None
    t0 = telemetry.clock()
    state = _gather_zero(state)
    leaves, treedef = jax.tree_util.tree_flatten(state)
    for leaf in leaves:
        copy_async = getattr(leaf, "copy_to_host_async", None)
        if copy_async is not None:
            copy_async()
    snapshot = jax.tree_util.tree_unflatten(
        treedef, [np.asarray(leaf) for leaf in leaves])
    if telemetry.enabled():
        telemetry.histogram(
            "hvd_ckpt_async_snapshot_seconds",
            "device->host snapshot time per async save (the only part "
            "that blocks the step)").observe(telemetry.clock() - t0)
    ckpt_dir = os.path.abspath(ckpt_dir)
    record = _AsyncSave(step)

    def _write():
        t1 = telemetry.clock()
        try:
            _write_step(ckpt_dir, snapshot, step, max_to_keep)
            record.path = os.path.join(ckpt_dir, str(step))
            if telemetry.enabled():
                telemetry.counter(
                    "hvd_ckpt_async_saves_total",
                    "background checkpoint writes completed").inc()
                telemetry.histogram(
                    "hvd_ckpt_async_write_seconds",
                    "background orbax write time per async save").observe(
                    telemetry.clock() - t1)
            log.info("async checkpoint step %d written to %s", step,
                     record.path)
        except Exception as e:  # noqa: BLE001 — reported at drain time
            record.error = e
            if telemetry.enabled():
                telemetry.counter(
                    "hvd_ckpt_async_failures_total",
                    "background checkpoint writes that raised").inc()

    record.thread = threading.Thread(
        target=_write, name=f"hvd-ckpt-async-{step}", daemon=True)
    with _async_lock:
        _async_current = record
        if not _async_atexit_registered:
            atexit.register(wait_for_async_save)
            _async_atexit_registered = True
    record.thread.start()
    return os.path.join(ckpt_dir, str(step))


def wait_for_async_save(timeout: Optional[float] = None) -> Optional[str]:
    """Drain the in-flight :func:`save_async` write, if any.  Returns
    the written path, or None (no write in flight / it failed / timed
    out).  A background failure is logged here — log-and-continue, the
    deadlock-free degradation contract of :func:`save`."""
    global _async_current
    with _async_lock:
        record, _async_current = _async_current, None
    if record is None or record.thread is None:
        return None
    record.thread.join(timeout)
    if record.thread.is_alive():
        # Put it back: still running, someone may drain it later.
        with _async_lock:
            if _async_current is None:
                _async_current = record
        log.warning("async checkpoint step %d still writing after "
                    "%.1fs wait", record.step, timeout or 0.0)
        return None
    if record.error is not None:
        log.error("async checkpoint save step %d FAILED (%s: %s); "
                  "continuing without it", record.step,
                  type(record.error).__name__, record.error)
        return None
    return record.path


def restore(ckpt_dir: str, state_template: Any,
            step: Optional[int] = None, root_rank: int = 0) -> Any:
    """Restore the latest (or ``step``-th) checkpoint on ``root_rank`` and
    broadcast it to every rank.  ``state_template`` supplies the pytree
    structure/shapes/dtypes (pass the freshly-initialized state).

    ZeRO-1 sharded optimizer states in the template are restored from the
    checkpoint's replicated per-leaf layout and re-sharded into the
    template's flat-bucket layout for THIS mesh (see :func:`_scatter_zero`)
    — a checkpoint saved N-way-sharded (or replicated) restores into any
    mesh size.  Re-place the result (``step.state_shardings`` /
    ``jax.device_put``) before training."""
    # Restore + broadcast run in the layout-independent replicated format;
    # conversion back to the sharded layout happens once at the end.
    portable_template = _gather_zero(state_template)
    state = portable_template
    found = np.zeros(1, np.int32)
    t0 = telemetry.clock()
    if basics.rank() == root_rank:
        import orbax.checkpoint as ocp
        ckpt_dir = os.path.abspath(ckpt_dir)
        # Newest first; an explicitly pinned step is tried alone (falling
        # back to a DIFFERENT step than the one asked for would be
        # silently wrong).
        candidates = ([step] if step is not None
                      else list(reversed(_valid_steps(ckpt_dir))))
        for use_step in candidates:
            try:
                with ocp.CheckpointManager(ckpt_dir) as mgr:
                    state = mgr.restore(
                        use_step,
                        args=ocp.args.StandardRestore(portable_template))
                found[0] = 1
                log.info("restored checkpoint step %s from %s",
                         use_step, ckpt_dir)
                break
            except Exception as e:  # noqa: BLE001 — skip-and-warn contract
                state = portable_template
                log.warning(
                    "skipping unrestorable checkpoint step %s in %s "
                    "(%s: %s); %s", use_step, ckpt_dir,
                    type(e).__name__, e,
                    "trying the next older step" if step is None
                    else "starting fresh")
    if basics.size() > 1:
        found = _c._eager_broadcast(found, root_rank,
                                    "hvd.checkpoint.restore.found")
        if int(found[0]):
            state = _tree_broadcast(state, root_rank,
                                    "hvd.checkpoint.restore")
    state = _scatter_zero(state, state_template)
    if telemetry.enabled():
        telemetry.counter(
            "hvd_checkpoint_restores_total",
            "Checkpoint restore attempts (including broadcast)",
            found=str(bool(int(found[0])))).inc()
        telemetry.histogram(
            "hvd_checkpoint_restore_seconds",
            "Wall time of restore + cross-rank broadcast").observe(
            telemetry.clock() - t0)
    return state


def load_local(ckpt_dir: str, state_template: Any,
               step: Optional[int] = None):
    """Restore the latest (or ``step``-th) intact checkpoint from local
    disk WITHOUT any collective — the serving-replica half of the
    checkpoint plane (:func:`horovod_tpu.serving.replica
    .load_replica_model`), where every process reads its own copy
    instead of rank 0 broadcasting one.

    Returns ``(state, used_step)``; ``used_step`` is None (and ``state``
    is the template, unchanged) when nothing restorable exists.  Only
    replicated states round-trip here: ZeRO-sharded training states are
    ``restore``'s job — it owns the gather/scatter relayout, which needs
    the training mesh this path deliberately runs without.  Shares
    :func:`restore`'s skip-and-warn contract for half-written or corrupt
    step directories."""
    if not os.path.isdir(ckpt_dir):
        return state_template, None
    import orbax.checkpoint as ocp
    ckpt_dir = os.path.abspath(ckpt_dir)
    candidates = ([step] if step is not None
                  else list(reversed(_valid_steps(ckpt_dir))))
    for use_step in candidates:
        try:
            with ocp.CheckpointManager(ckpt_dir) as mgr:
                state = mgr.restore(
                    use_step,
                    args=ocp.args.StandardRestore(state_template))
            log.info("loaded checkpoint step %s locally from %s",
                     use_step, ckpt_dir)
            return state, int(use_step)
        except Exception as e:  # noqa: BLE001 — skip-and-warn contract
            log.warning(
                "skipping unrestorable checkpoint step %s in %s "
                "(%s: %s); %s", use_step, ckpt_dir,
                type(e).__name__, e,
                "trying the next older step" if step is None
                else "starting fresh")
    return state_template, None


def latest_step(ckpt_dir: str) -> Optional[int]:
    """Highest INTACT checkpoint step present in ``ckpt_dir`` (local
    read; no collective).  Half-written or corrupt step directories are
    skipped with a warning, never raised on — see :func:`_valid_steps`."""
    if not os.path.isdir(ckpt_dir):
        return None
    steps = _valid_steps(ckpt_dir)
    return steps[-1] if steps else None
