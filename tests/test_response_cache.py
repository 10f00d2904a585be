"""Response-cache steady-state fast path: hits accumulate on repeated
same-shape collectives, and the cached path must NOT survive a membership
change — process-set registration clears the replicas at a deterministic
response-stream position, and an elastic re-init starts from an empty
cache (native/cc/include/response_cache.h invariant).

The slot-level semantics (hit/miss, Clear, post-clear re-slotting, FIFO
eviction across the boundary) are pinned by the C++ oracle
(native/cc/tests/test_response_cache.cc, run through ``make unittest``);
the launcher test drives the same invariants end-to-end over the wire
through the hvd_cache_lookups/hvd_cache_hits introspection counters.
"""

import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

INVALIDATION_SCRIPT = textwrap.dedent("""\
    import numpy as np
    import horovod_tpu as hvd
    from horovod_tpu import basics

    hvd.init()
    rank, size = hvd.rank(), hvd.size()
    rt = basics.runtime()

    def counters():
        cfg = rt.tuned_config()
        return cfg["cache_lookups"], cfg["cache_hits"]

    # Steady state: the same names announce repeatedly, so after the
    # first (miss) round every announcement is a one-bit cache hit.
    for step in range(12):
        out = np.asarray(hvd.allreduce(np.full(8, float(step), np.float32),
                                       op=hvd.Sum, name=f"cache.{step % 4}"))
        np.testing.assert_allclose(out, np.full(8, float(step) * size))
    lookups1, hits1 = counters()
    assert hits1 >= 4, (lookups1, hits1)   # steady names hit
    misses1 = lookups1 - hits1

    # Membership change: registering a process set must clear the cache
    # on every rank (same response-stream position), so the SAME names
    # must renegotiate as full requests — at least 4 fresh misses.
    ps = hvd.add_process_set(list(range(size)))
    for step in range(8):
        out = np.asarray(hvd.allreduce(np.full(8, 1.0, np.float32),
                                       op=hvd.Sum, name=f"cache.{step % 4}"))
        np.testing.assert_allclose(out, np.full(8, float(size)))
    lookups2, hits2 = counters()
    misses2 = lookups2 - hits2
    assert misses2 >= misses1 + 4, (
        "cached fast path survived add_process_set",
        misses1, misses2, lookups2, hits2)
    # ... and the re-announced names hit AGAIN once re-cached.
    assert hits2 > hits1, (hits1, hits2)

    # The new set works (sanity: the clear did not corrupt negotiation).
    out = np.asarray(hvd.allreduce(np.full(4, 2.0, np.float32),
                                   op=hvd.Sum, name="ps.t",
                                   process_set=ps))
    np.testing.assert_allclose(out, np.full(4, 2.0 * size))

    # Elastic world-size change: a re-init builds a fresh native state —
    # the counters restart at zero, i.e. no stale fast path crosses an
    # elastic boundary.  A zero-copy result array rides across it: its
    # weakref finalizer fires hvd_release(old_handle) against the NEW
    # runtime whenever Python collects it, so handle ids must be unique
    # across inits (epoch in the high bits) or the release would free a
    # live epoch-2 entry mid-flight.
    import gc
    tok = rt.allreduce_submit("epoch1.survivor",
                              np.full(8, 5.0, np.float32), 1)  # 1 = Sum
    h_epoch1 = tok[0]
    survivor = rt.allreduce_finish(tok)
    np.testing.assert_allclose(np.asarray(survivor).ravel(),
                               np.full(8, 5.0 * size))
    hvd.shutdown()
    hvd.init()
    rt = basics.runtime()
    lookups3, hits3 = counters()
    assert lookups3 == 0 and hits3 == 0, (lookups3, hits3)
    out = np.asarray(hvd.allreduce(np.full(8, 3.0, np.float32),
                                   op=hvd.Sum, name="cache.0"))
    np.testing.assert_allclose(out, np.full(8, 3.0 * size))
    # Epoch-2 ids live above every epoch-1 id (pre-fix the fresh queue
    # restarted at 0 and re-walked the old range); the stale finalizer
    # must no-op while an epoch-2 op is in flight.
    tok2 = rt.allreduce_submit("epoch2.t", np.full(8, 7.0, np.float32), 1)
    assert tok2[0] > h_epoch1, (tok2[0], h_epoch1)
    del survivor
    gc.collect()   # fires the epoch-1 finalizer against the new state
    out2 = np.asarray(rt.allreduce_finish(tok2))
    np.testing.assert_allclose(out2.ravel(), np.full(8, 7.0 * size))
    print(f"CACHE_INVALIDATION_OK rank={rank}")
""")


def test_cache_slot_semantics_unit():
    """C++ oracle: hit/miss, Clear, post-clear re-slotting, FIFO eviction
    (native/cc/tests/test_response_cache.cc)."""
    cc_dir = os.path.join(REPO, "horovod_tpu", "native", "cc")
    res = subprocess.run(["make", "-s", "unittest"], cwd=cc_dir,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "RESPONSE CACHE GATE OK" in res.stdout


def test_cache_invalidation_np2(tmp_path):
    """2-rank end-to-end: hits climb in steady state, add_process_set
    forces renegotiation, an elastic re-init starts cold."""
    script = tmp_path / "workload.py"
    script.write_text(INVALIDATION_SCRIPT)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO  # exactly the package under test
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         sys.executable, str(script)],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert res.stdout.count("CACHE_INVALIDATION_OK") == 2, res.stdout
