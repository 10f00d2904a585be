"""The harness end to end, in rehearsal: tiny sizes on the CPU, one device
and four virtual ones.  Each run is a process of its own, as on the chip."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import run


def _run(*args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), *args],
        env=env, capture_output=True, text=True, timeout=600, cwd=run.ROOT)


@pytest.mark.parametrize("workload,devices,trace", [
    ("gpt67_t2048", 1, "0"), ("gpt67_t8192", 1, "1"),
    ("gpt67_t2048_dp4", 4, "0"), ("resnet50_b256", 1, "0")])
def test_rehearsal_runs_and_prints_no_metric(workload, devices, trace):
    done = _run("--workload", workload, "--seed", "3", "--seconds", "1",
                "--trace", trace, "--rehearse-cpu", devices=devices)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"
    assert "REHEARSAL" in done.stdout


def test_no_chip_no_result():
    done = _run("--workload", "gpt67_t2048", "--seed", "1", "--seconds",
                "1", "--trace", "0")
    assert done.returncode == 2
    assert "{" not in done.stdout.strip().splitlines()[-1][:1]
    assert "not 'tpu'" in done.stderr


def test_too_few_chips_no_result():
    done = _run("--workload", "gpt67_t2048_dp4", "--seed", "1",
                "--seconds", "1", "--trace", "0", "--rehearse-cpu")
    assert done.returncode == 2 and "needs 4 chip(s)" in done.stderr


def test_same_seed_same_losses():
    def losses(seed):
        out = _run("--workload", "gpt67_t2048", "--seed", seed,
                   "--seconds", "1", "--trace", "0", "--rehearse-cpu").stdout
        return [line.split("loss at the fence")[1]
                for line in out.splitlines() if line.startswith("chunk")][:3]

    assert losses("5") == losses("5") != losses("6")
