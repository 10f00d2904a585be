"""Multi-process integration tests: spawn real jobs under the launcher.

Reference strategy (SURVEY §4): "multi-node" is N processes on localhost
over the real transport — `horovodrun -np 2 pytest ...`
(.buildkite/gen-pipeline.sh:189-190).  These tests are the single-process
driver side: they invoke hvdrun and assert on job results, timeline
artifacts (test/test_timeline.py), stall handling (test/test_stall.py) and
failure fan-out (gloo_run.py:256-262).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _hvdrun(args, script=None, np_=2, timeout=180, env=None, tmp_path=None):
    full_env = dict(os.environ)
    full_env["JAX_PLATFORMS"] = "cpu"
    full_env["PYTHONPATH"] = REPO  # exactly the package under test
    full_env.pop("XLA_FLAGS", None)  # subprocesses don't need 8 fake devices
    if env:
        full_env.update(env)
    cmd = [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np_)] + args
    if script is not None:
        path = tmp_path / "script.py"
        path.write_text(script)
        cmd += [sys.executable, str(path)]
    return subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, env=full_env, cwd=REPO)


@pytest.mark.slow
def test_native_ops_under_launcher(tmp_path):
    """The full eager op matrix under a real 2-process job."""
    res = _hvdrun([sys.executable, "-m", "pytest", "-x", "-q",
                   "-p", "no:cacheprovider",
                   os.path.join(REPO, "tests", "distributed")],
                  np_=2, timeout=600)
    assert res.returncode == 0, res.stdout + res.stderr


@pytest.mark.slow
def test_elastic_restart_resumes_from_checkpoint(tmp_path):
    """Elastic-lite end-to-end (docs/fault_tolerance.md): rank 1 dies mid-train
    on attempt 0; hvdrun --elastic-restarts relaunches with a fresh
    rendezvous; the job resumes from the latest checkpoint and finishes
    with the exact state an uninterrupted run produces."""
    ckpt = tmp_path / "ckpt"
    script = textwrap.dedent(f"""\
        import os
        import numpy as np
        import horovod_tpu as hvd
        from horovod_tpu import checkpoint

        hvd.init()
        rank, size = hvd.rank(), hvd.size()
        attempt = os.environ.get("HOROVOD_RESTART_ATTEMPT", "0")
        CKPT = {str(ckpt)!r}
        TOTAL = 6

        state = {{"w": np.zeros(4, np.float32),
                  "step": np.zeros((), np.int64)}}
        state = checkpoint.restore(CKPT, state)
        start = int(state["step"])
        if attempt == "1":
            # The relaunch must actually RESUME (a full rerun would
            # also produce the right numbers — assert it didn't).
            assert start == 3, f"expected resume from step 3, got {{start}}"
        for step in range(start, TOTAL):
            # "Training": every rank contributes rank+step; the mean is
            # deterministic, so the final w is checkable exactly.
            g = np.full(4, float(rank + step), np.float32)
            state["w"] = state["w"] + np.asarray(
                hvd.allreduce(g, name=f"el.{{step}}"))
            state["step"] = np.asarray(step + 1, np.int64)
            checkpoint.save(CKPT, state, step + 1)
            if step == 2 and rank == 1 and attempt == "0":
                os._exit(9)   # simulated hard failure mid-training

        mean_rank = (size - 1) / 2.0
        want = sum(mean_rank + s for s in range(TOTAL))
        np.testing.assert_allclose(state["w"], np.full(4, want), rtol=1e-6)
        if rank == 0:
            print(f"ELASTIC_OK attempt={{attempt}} final={{state['w'][0]}}",
                  flush=True)
    """)
    path = tmp_path / "train.py"
    path.write_text(script)
    res = _hvdrun(["--elastic-restarts", "2", sys.executable, str(path)],
                  np_=2, timeout=300, tmp_path=tmp_path)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "ELASTIC_OK attempt=1" in res.stdout, res.stdout
    assert "elastic restart 1/2" in res.stderr + res.stdout


def test_operator_stop_does_not_elastic_restart(tmp_path):
    """SIGTERM to the launcher = operator stop: launch_job returns 130
    (even though the SIGTERMed ranks exit -15) and the elastic loop must
    NOT relaunch — otherwise the operator races every fresh attempt."""
    script = tmp_path / "spin.py"
    script.write_text(textwrap.dedent("""\
        import time
        import horovod_tpu as hvd
        hvd.init()
        print("spinning", flush=True)
        time.sleep(120)
    """))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    env.pop("XLA_FLAGS", None)
    proc = subprocess.Popen(
        [sys.executable, "-m", "horovod_tpu.runner", "-np", "2",
         "--elastic-restarts", "3", sys.executable, str(script)],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
    # Wait until both ranks are up, then stop the job like an operator.
    import signal as _signal
    import time as _time
    deadline = _time.time() + 60
    up = 0
    while up < 2 and _time.time() < deadline:
        line = proc.stdout.readline()
        if "spinning" in line:
            up += 1
    assert up == 2, "ranks never came up"
    proc.send_signal(_signal.SIGTERM)
    out = proc.stdout.read()
    rc = proc.wait(timeout=60)
    assert rc == 130, (rc, out)
    assert "elastic restart" not in out, out


def test_adasum_three_ranks(tmp_path):
    """Non-power-of-2 Adasum: rank 2 folds into rank 0 before the 2-rank
    butterfly and receives the result back; every rank must hold the
    oracle value bitwise-identically (native AdasumButterfly,
    data_plane.cc)."""
    script = textwrap.dedent("""\
        import numpy as np
        import horovod_tpu as hvd
        hvd.init()
        r, s = hvd.rank(), hvd.size()
        assert s == 3
        vecs = [np.random.default_rng(7 + i).standard_normal(129)
                .astype(np.float32) for i in range(3)]

        def pair(a, b):
            dot = float(np.dot(a, b))
            na = float(np.dot(a, a)); nb = float(np.dot(b, b))
            ac = 1.0 - dot / (2.0 * na) if na > 0 else 1.0
            bc = 1.0 - dot / (2.0 * nb) if nb > 0 else 1.0
            return ac * a + bc * b

        out = np.asarray(hvd.allreduce(vecs[r], op=hvd.Adasum,
                                       name="ad3"))
        # Fold order: extra rank 2 -> position 0, then the 0/1 butterfly.
        want = pair(pair(vecs[0], vecs[2]), vecs[1])
        np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)
        # Bitwise agreement across ranks.
        allout = np.asarray(hvd.allgather(out[None], name="ad3.g"))
        for rr in range(s):
            np.testing.assert_array_equal(allout[rr], out)
        print(f"rank {r}: adasum3 ok")
    """)
    res = _hvdrun([], script=script, np_=3, timeout=120, tmp_path=tmp_path)
    assert res.returncode == 0, res.stdout + res.stderr
    assert res.stdout.count("adasum3 ok") == 3


def test_network_interface_pins_loopback(tmp_path):
    """--network-interface lo: both ranks bind AND advertise loopback's
    address; the job runs collectives normally (reference horovodrun
    --network-interface, run/run.py:195-265)."""
    script = textwrap.dedent("""\
        import numpy as np
        import horovod_tpu as hvd
        hvd.init()
        out = np.asarray(hvd.allreduce(np.ones(4, np.float32),
                                       op=hvd.Sum, name="t"))
        assert out[0] == hvd.size()
        print("nic pinned ok")
    """)
    res = _hvdrun(["--network-interface", "lo"], script=script, np_=2,
                  timeout=120, tmp_path=tmp_path)
    assert res.returncode == 0, res.stdout + res.stderr
    assert res.stdout.count("nic pinned ok") == 2


def test_network_interface_unknown_fails_fast(tmp_path):
    """A bogus NIC name must fail init immediately with an attributed
    error, not hang out the rendezvous deadline."""
    script = textwrap.dedent("""\
        import horovod_tpu as hvd
        hvd.init()
    """)
    res = _hvdrun([], script=script, np_=2, timeout=60, tmp_path=tmp_path,
                  env={"HOROVOD_NETWORK_INTERFACE": "bogus0"})
    assert res.returncode != 0
    assert "bogus0: no such interface" in res.stdout + res.stderr


@pytest.mark.slow
def test_misadvertised_address_attributed_error(tmp_path):
    """An advertised address peers cannot reach must surface WHO cannot
    reach WHOM at WHAT address and name the knobs — the bootstrap dial
    doubles as the cross-rank reachability probe."""
    script = textwrap.dedent("""\
        import horovod_tpu as hvd
        hvd.init()
    """)
    # Bind loopback's 127.0.0.1 but advertise 127.0.0.2: the listener
    # never accepts there, so the peer's dial is refused until its
    # deadline and the attributed diagnosis fires.
    res = _hvdrun(["--network-interface", "lo"], script=script, np_=2,
                  timeout=120, tmp_path=tmp_path,
                  env={"HOROVOD_HOSTNAME": "127.0.0.2"})
    assert res.returncode != 0
    out = res.stdout + res.stderr
    assert "cannot reach rank" in out and "127.0.0.2" in out, out
    assert "HOROVOD_NETWORK_INTERFACE" in out, out


@pytest.mark.slow
def test_jax_distributed_spmd_under_launcher(tmp_path):
    """hvdrun --jax-distributed: 2 processes x 4 virtual CPU devices run
    one jax.distributed-initialized SPMD train step over a GLOBAL
    8-device mesh, with the native TCP plane live in the same job
    (tests/distributed/spmd_np2_check.py; the joint-certification seam,
    reference .buildkite/gen-pipeline.sh:120-190)."""
    res = _hvdrun(["--jax-distributed", sys.executable,
                   os.path.join(REPO, "tests", "distributed",
                                "spmd_np2_check.py")],
                  np_=2, timeout=300,
                  env={"XLA_FLAGS":
                       "--xla_force_host_platform_device_count=4"})
    assert res.returncode == 0, res.stdout + res.stderr
    assert "SPMD_NP2_OK" in res.stdout


@pytest.mark.slow
def test_failure_fan_out(tmp_path):
    """A crashing rank must take the job down, non-zero (reference
    gloo_run.py:256-262)."""
    script = textwrap.dedent("""\
        import os, sys, time
        import horovod_tpu as hvd
        hvd.init()
        if hvd.rank() == 1:
            sys.exit(3)
        time.sleep(60)
    """)
    res = _hvdrun([], script=script, np_=2, timeout=90, tmp_path=tmp_path)
    assert res.returncode != 0


def test_timeline_artifact(tmp_path):
    """HOROVOD_TIMELINE produces chrome-tracing JSON containing negotiation
    and execution phases (reference test/test_timeline.py:39-56)."""
    tl = tmp_path / "timeline.json"
    script = textwrap.dedent("""\
        import numpy as np
        import horovod_tpu as hvd
        hvd.init()
        for i in range(3):
            hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum, name=f"t{i}")
        hvd.allgather(np.ones((2, 2), np.float32), name="ag")
        hvd.shutdown()
    """)
    res = _hvdrun(["--timeline-filename", str(tl), "--timeline-mark-cycles"],
                  script=script, np_=2, timeout=120, tmp_path=tmp_path)
    assert res.returncode == 0, res.stdout + res.stderr
    content = tl.read_text()
    assert "NEGOTIATE_ALLREDUCE" in content
    assert "ALLREDUCE" in content
    assert "NEGOTIATE_ALLGATHER" in content
    assert "CYCLE_START" in content
    json.loads(content)  # must be valid JSON


@pytest.mark.slow
def test_stall_detection(tmp_path):
    """A rank that never submits triggers the stall watchdog: warning with
    missing ranks, then coordinated shutdown error (reference
    test/test_stall.py:12-29 with 2s check / 5s shutdown)."""
    script = textwrap.dedent("""\
        import sys
        import numpy as np
        import horovod_tpu as hvd
        hvd.init()
        if hvd.rank() == 0:
            try:
                hvd.allreduce(np.ones(2, np.float32), op=hvd.Sum, name="stall")
            except RuntimeError as e:
                assert "Stalled" in str(e), e
                print("GOT_STALL_ERROR", flush=True)
                sys.exit(0)
            sys.exit(1)
        else:
            import time
            time.sleep(8)  # never submits 'stall'
    """)
    res = _hvdrun(["--stall-check-time-seconds", "2",
                   "--stall-shutdown-time-seconds", "4"],
                  script=script, np_=2, timeout=120, tmp_path=tmp_path)
    assert "GOT_STALL_ERROR" in res.stdout, res.stdout + res.stderr
    assert "missing ranks" in res.stdout + res.stderr


def test_output_filename(tmp_path):
    """--output-filename writes per-rank files (reference
    gloo_run.py:165-197)."""
    script = textwrap.dedent("""\
        import horovod_tpu as hvd
        hvd.init()
        print(f"hello from rank {hvd.rank()}")
    """)
    out_dir = tmp_path / "logs"
    res = _hvdrun(["--output-filename", str(out_dir)], script=script,
                  np_=2, timeout=120, tmp_path=tmp_path)
    assert res.returncode == 0, res.stderr
    for r in range(2):
        content = (out_dir / f"rank.{r}" / "stdout").read_text()
        assert f"hello from rank {r}" in content


def test_three_process_job(tmp_path):
    """Odd-size ring exercises the uneven chunking paths."""
    script = textwrap.dedent("""\
        import numpy as np
        import horovod_tpu as hvd
        hvd.init()
        out = np.asarray(hvd.allreduce(
            np.arange(7, dtype=np.float32) * (hvd.rank() + 1),
            op=hvd.Sum, name="odd"))
        np.testing.assert_allclose(out, np.arange(7) * 6)
        out = np.asarray(hvd.allgather(
            np.ones((hvd.rank() + 1,), np.float32), name="ag"))
        assert out.shape == (6,)
        hvd.shutdown()
    """)
    res = _hvdrun([], script=script, np_=3, timeout=120, tmp_path=tmp_path)
    assert res.returncode == 0, res.stdout + res.stderr


# ---------------------------------------------------------------------------
# Connection authentication (reference run/common/network.py:50-84: HMAC-
# signed launcher RPC; here a mutual HMAC-SHA256 handshake on controller and
# data-plane connects, keyed by the launcher-generated HOROVOD_SECRET_KEY).
# ---------------------------------------------------------------------------

def _rank_env(rank, size, port, key):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO,  # exactly the package under test
        "HOROVOD_RANK": str(rank),
        "HOROVOD_SIZE": str(size),
        "HOROVOD_LOCAL_RANK": str(rank),
        "HOROVOD_LOCAL_SIZE": str(size),
        "HOROVOD_RENDEZVOUS_ADDR": "127.0.0.1",
        "HOROVOD_RENDEZVOUS_PORT": str(port),
        "HOROVOD_SECRET_KEY": key,
    })
    env.pop("XLA_FLAGS", None)
    return env


_AUTH_SCRIPT = textwrap.dedent("""\
    import numpy as np
    import horovod_tpu as hvd
    hvd.init()
    out = np.asarray(hvd.allreduce(np.ones(4, np.float32), op=hvd.Sum,
                                   name="auth.ok"))
    np.testing.assert_allclose(out, np.full(4, float(hvd.size())))
    print("AUTH_JOB_OK", flush=True)
    hvd.shutdown()
""")


def test_wrong_key_connect_rejected(tmp_path):
    """A rank holding a different HOROVOD_SECRET_KEY must be refused at the
    rendezvous with an auth error, not admitted or hung."""
    import base64
    import socket as pysocket

    script = tmp_path / "auth_job.py"
    script.write_text(_AUTH_SCRIPT)
    with pysocket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    key = base64.urlsafe_b64encode(b"k" * 32).decode()
    wrong = base64.urlsafe_b64encode(b"x" * 32).decode()

    rank0 = subprocess.Popen(
        [sys.executable, str(script)], env=_rank_env(0, 2, port, key),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=REPO)
    try:
        rank1 = subprocess.run(
            [sys.executable, str(script)], env=_rank_env(1, 2, port, wrong),
            capture_output=True, text=True, timeout=60, cwd=REPO)
        assert rank1.returncode != 0
        assert "auth" in (rank1.stdout + rank1.stderr).lower(), (
            rank1.stdout + rank1.stderr)
    finally:
        rank0.kill()
        rank0.wait()


def test_rogue_connection_ignored(tmp_path):
    """Garbage/unauthenticated connects to the rendezvous port must be
    dropped while the real job completes (scanner resilience)."""
    import base64
    import socket as pysocket
    import threading
    import time

    script = tmp_path / "auth_job.py"
    script.write_text(_AUTH_SCRIPT)
    with pysocket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    key = base64.urlsafe_b64encode(b"k" * 32).decode()

    rank0 = subprocess.Popen(
        [sys.executable, str(script)], env=_rank_env(0, 2, port, key),
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=REPO)

    def rogue():
        # Let rank 0 start listening, then poke it with garbage and with a
        # connect-and-say-nothing probe (must not stall the accept loop).
        deadline = time.time() + 30
        while time.time() < deadline:
            try:
                c = pysocket.create_connection(("127.0.0.1", port),
                                               timeout=2)
                break
            except OSError:
                time.sleep(0.2)
        else:
            return
        with c:
            c.sendall(b"\xff" * 64)  # malformed handshake reply
            time.sleep(0.5)
        with pysocket.create_connection(("127.0.0.1", port), timeout=2):
            time.sleep(0.5)  # silent probe; server times it out

    th = threading.Thread(target=rogue)
    th.start()
    time.sleep(2)  # give the rogue the first connects
    try:
        rank1 = subprocess.run(
            [sys.executable, str(script)], env=_rank_env(1, 2, port, key),
            capture_output=True, text=True, timeout=120, cwd=REPO)
        th.join()
        out0, _ = rank0.communicate(timeout=60)
        assert rank1.returncode == 0, rank1.stdout + rank1.stderr
        assert "AUTH_JOB_OK" in rank1.stdout
        assert "AUTH_JOB_OK" in out0, out0
    finally:
        th.join(timeout=5)
        rank0.kill()
        rank0.wait()


def test_launcher_sets_secret_key(tmp_path):
    """hvdrun injects a per-job HOROVOD_SECRET_KEY so jobs authenticate by
    default."""
    script = textwrap.dedent("""\
        import os
        import horovod_tpu as hvd
        hvd.init()
        assert os.environ.get("HOROVOD_SECRET_KEY"), "no job secret set"
        print("KEY_PRESENT", flush=True)
        hvd.shutdown()
    """)
    res = _hvdrun([], script=script, np_=2, timeout=120, tmp_path=tmp_path)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "KEY_PRESENT" in res.stdout


def test_remote_spawn_secret_not_on_command_line(tmp_path):
    """The ssh spawn path must deliver HOROVOD_SECRET_KEY over stdin, not
    argv (argv is world-readable via ps).  A fake ssh executes the remote
    command locally and logs its argv; 127.0.1.1 routes to loopback but is
    not classified local, so both ranks take the ssh path for real."""
    argv_log = tmp_path / "ssh_argv.log"
    fake_ssh = tmp_path / "fake_ssh"
    fake_ssh.write_text(textwrap.dedent(f"""\
        #!/bin/bash
        printf '%s\\n' "$@" >> {argv_log}
        # args: -o StrictHostKeyChecking=no <host> <remote-command>
        exec bash -c "$4"
    """))
    fake_ssh.chmod(0o755)

    script = tmp_path / "job.py"
    script.write_text(textwrap.dedent("""\
        import os
        import numpy as np
        import horovod_tpu as hvd
        hvd.init()
        assert os.environ.get("HOROVOD_SECRET_KEY"), "secret missing"
        out = np.asarray(hvd.allreduce(np.ones(4, np.float32),
                                       op=hvd.Sum, name="ssh.ok"))
        np.testing.assert_allclose(out, np.full(4, float(hvd.size())))
        print("SSH_JOB_OK", flush=True)
        hvd.shutdown()
    """))
    res = _hvdrun(["-H", "127.0.1.1:2", sys.executable, str(script)],
                  np_=2, timeout=120,
                  env={"HOROVOD_SSH_CMD": str(fake_ssh)})
    assert res.returncode == 0, res.stdout + res.stderr
    assert res.stdout.count("SSH_JOB_OK") == 2, res.stdout + res.stderr
    argv = argv_log.read_text()
    assert "HOROVOD_SECRET_KEY" not in argv.replace(
        "read -r HOROVOD_SECRET_KEY; export HOROVOD_SECRET_KEY", "")
    assert "HOROVOD_RANK" in argv  # env inlining still present for the rest
