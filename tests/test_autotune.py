"""Autotune end-to-end: the parameter manager must explore, log trials,
converge, pin — and never corrupt results while fusion thresholds, cycle
times and cache gating change mid-stream.

Reference strategy: the autotuner has no dedicated test in the reference
tree; its contract is documented behavior (parameter_manager.cc:142-176 —
warmup -> score -> tune -> broadcast -> converge).  Here the contract is
asserted through the launcher the same way test/test_timeline.py asserts
the timeline artifact.
"""

import csv
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""\
    import numpy as np
    import horovod_tpu as hvd

    hvd.init()
    r, s = hvd.rank(), hvd.size()
    # Many small allreduces: feeds the tuner with busy cycles and checks
    # correctness under every parameter combination it tries.
    for step in range(600):
        x = np.full((64,), float(step % 7), np.float32)
        out = np.asarray(hvd.allreduce(x, op=hvd.Sum, name=f"g.{step % 8}"))
        np.testing.assert_allclose(out, np.full((64,), (step % 7) * s))
    print(f"rank {r}: autotune workload done")
""")


def test_autotune_tunes_and_pins(tmp_path):
    log = tmp_path / "autotune.csv"
    script = tmp_path / "workload.py"
    script.write_text(SCRIPT)

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO  # exactly the package under test
    env.pop("XLA_FLAGS", None)
    # Fast schedule so the search completes within the workload.
    env.update({
        "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "1",
        "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "3",
        "HOROVOD_AUTOTUNE_SAMPLES": "3",
        "HOROVOD_AUTOTUNE_BAYES_TRIALS": "10",
    })

    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         "--autotune", "--autotune-log-file", str(log),
         sys.executable, str(script)],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "autotune workload done" in res.stdout

    # The trial log is rank 0's record of the search.
    assert log.exists(), "autotune log not written"
    with open(log) as f:
        rows = list(csv.DictReader(f))
    assert len(rows) >= 5, rows
    # The optimizer actually explored: parameters vary across trials.
    cycles = {row["cycle_time_ms"] for row in rows}
    fusions = {row["fusion_threshold_mb"] for row in rows}
    assert len(cycles) > 1 or len(fusions) > 1, rows
    # The search converged and pinned a best configuration.
    assert rows[-1]["pinned"] == "1", rows[-1]
    # Scores are sane positive bytes/usec.
    assert all(float(row["score_bytes_per_usec"]) > 0 for row in rows)


def test_autotune_off_by_default(tmp_path):
    """Without --autotune nothing is tuned and no log appears."""
    log = tmp_path / "autotune.csv"
    script = tmp_path / "workload.py"
    script.write_text(textwrap.dedent("""\
        import numpy as np
        import horovod_tpu as hvd
        hvd.init()
        out = np.asarray(hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum,
                                       name="t"))
        assert out[0] == hvd.size()
        print("plain run ok")
    """))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO  # exactly the package under test
    env.pop("XLA_FLAGS", None)
    env["HOROVOD_AUTOTUNE_LOG"] = str(log)   # env set, flag absent
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         sys.executable, str(script)],
        capture_output=True, text=True, timeout=120, env=env, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert not log.exists()


def test_autotune_explores_hierarchical_and_ranks_agree(tmp_path):
    """The tuner explores the hierarchical allreduce/allgather booleans as
    categorical dimensions (reference parameter_manager.h:133-246) on a
    topology the bootstrap agreed is CAPABLE — without the user setting
    the HOROVOD_HIERARCHICAL_* env flags — flipping the routing
    mid-stream at an agreed response position; results stay correct
    through every flip and all ranks end on the same routing state."""
    log = tmp_path / "autotune.csv"
    script = tmp_path / "workload.py"
    script.write_text(textwrap.dedent("""\
        import os
        import numpy as np
        rank = int(os.environ["HOROVOD_RANK"])
        size = int(os.environ["HOROVOD_SIZE"])
        # Simulated 2-host block topology (hier_check_np4.py trick): makes
        # the hierarchical path AVAILABLE; the env flags stay unset.
        os.environ["HOROVOD_LOCAL_SIZE"] = str(size // 2)
        os.environ["HOROVOD_LOCAL_RANK"] = str(rank % (size // 2))
        import horovod_tpu as hvd
        from horovod_tpu import basics
        hvd.init()
        # Payloads above the (agreed, env-zeroed) threshold so a flipped
        # hierarchical flag actually changes the routing; correctness
        # must hold through every mid-stream flip the tuner makes.
        x = np.arange(100_003, dtype=np.float32)
        for step in range(420):
            out = np.asarray(hvd.allreduce(x * (rank + 1), average=False,
                                           name=f"g.{step % 8}"))
            np.testing.assert_allclose(
                out, x * (size * (size + 1) / 2), rtol=1e-5)
        # All ranks must agree on the final routing state (a diverged
        # flag would already have deadlocked above, but assert it
        # explicitly end-to-end).
        state = float(basics.runtime().hierarchical_enabled())
        states = np.asarray(hvd.allgather(np.array([state]), name="hs"))
        assert len(set(states.tolist())) == 1, states
        print(f"rank {rank}: hier state {state} agreed")
    """))

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO  # exactly the package under test
    env.pop("XLA_FLAGS", None)
    env.update({
        "HOROVOD_HIERARCHICAL_ALLREDUCE_THRESHOLD": "0",
        "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "1",
        "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "3",
        "HOROVOD_AUTOTUNE_SAMPLES": "3",
        "HOROVOD_AUTOTUNE_BAYES_TRIALS": "10",
    })
    res = subprocess.run(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "4",
         "--autotune", "--autotune-log-file", str(log),
         sys.executable, str(script)],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert res.stdout.count("agreed") == 4, res.stdout

    with open(log) as f:
        rows = list(csv.DictReader(f))
    assert len(rows) >= 5, rows
    # The tuner actually explored the hierarchical dimension: both
    # routing states appear across trials.
    hier_vals = {row["hier_allreduce"] for row in rows}
    assert hier_vals == {"0", "1"}, rows
    # The search converged and pinned.  Not "the last row is the pinned
    # one": after pinning the coordinator keeps monitoring and re-opens
    # exploration when throughput drifts (docs/autotune.md, step 5),
    # which the other workers of a parallel test run cause at will.
    assert any(row["pinned"] == "1" for row in rows), rows


def test_bayes_vs_grid_oracle():
    """Convergence-quality gate for the GP/EI optimizer:
    at the production 20-trial budget the deterministic search must
    land within 95% (3-D) / 90% (5-D) of a dense grid-search maximum on
    smooth 2-peak objectives (native/cc/tests/test_bayes_oracle.cc)."""
    cc_dir = os.path.join(REPO, "horovod_tpu", "native", "cc")
    res = subprocess.run(["make", "-s", "unittest"], cwd=cc_dir,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "BAYES ORACLE GATE OK" in res.stdout


def test_monitor_anchor_oracle():
    """Drift-monitor anchoring gate: benign +/-8% fluctuation around the
    post-pin anchor must never re-open tuning, while a gradual -5%/window
    regression (in-band against a walking baseline forever) must trip the
    anchor-clamped floor (native/cc/tests/test_param_monitor.cc)."""
    cc_dir = os.path.join(REPO, "horovod_tpu", "native", "cc")
    res = subprocess.run(["make", "-s", "unittest"], cwd=cc_dir,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "PARAM MONITOR GATE OK" in res.stdout
