"""The rules of the entry scripts that run on the chip, checked without one:
``chip_smoke.py`` and ``bench.py`` refuse the CPU by name, a failing bench
lane is a failing run, the compile cache can be placed from outside,
``hvd.init()`` under the launcher claims no device, and the launcher
refuses ranks that would contend for a chip."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, **env):
    full = {k: v for k, v in os.environ.items()
            if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    full.update(PYTHONPATH=REPO, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable] + args, env=full, cwd=REPO,
                          capture_output=True, text=True, timeout=120)


def test_chip_smoke_refuses_cpu_by_name():
    res = _run(["chip_smoke.py"])
    assert res.returncode not in (0, 1), res
    assert "platform 'cpu'" in res.stderr and "not 'tpu'" in res.stderr
    # The device line is printed; no result is.
    assert "platform=cpu" in res.stdout
    assert '"ok"' not in res.stdout


_PRINT_CACHE_DIR = (
    "import jax\n"
    "from horovod_tpu.utils.compile_cache import enable_compile_cache\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "enable_compile_cache()\n"
    "print(before, jax.config.jax_compilation_cache_dir)\n")


def test_compile_cache_defaults_to_fixed_path_under_checkout():
    res = _run(["-c", _PRINT_CACHE_DIR])
    assert res.returncode == 0, res.stderr
    assert res.stdout.split() == ["None", os.path.join(REPO, ".jax_cache")]


def test_compile_cache_leaves_outside_setting_alone(tmp_path):
    res = _run(["-c", _PRINT_CACHE_DIR],
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert res.returncode == 0, res.stderr
    assert res.stdout.split() == [str(tmp_path), str(tmp_path)]


def test_init_under_launcher_env_initialises_no_backend():
    """With HOROVOD_RANK/SIZE set, hvd.init() must not call
    jax.process_index()/local_devices(): on a host with chips that claims
    them for a rank that may never need one."""
    res = _run(["-c",
                "import horovod_tpu as hvd\n"
                "from jax._src import xla_bridge\n"
                "hvd.init()\n"
                "print(hvd.rank(), hvd.size(),\n"
                "      xla_bridge.backends_are_initialized())\n"],
               HOROVOD_RANK="0", HOROVOD_SIZE="1")
    assert res.returncode == 0, res.stderr
    assert res.stdout.split() == ["0", "1", "False"]


@pytest.fixture()
def bench(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import bench
    import horovod_tpu as hvd
    yield bench
    hvd.shutdown()           # main() initialises and never shuts down


def test_bench_refuses_cpu_by_name(bench, capsys):
    assert bench.main() == 2
    out, err = capsys.readouterr()
    assert out == ""
    assert "platform 'cpu'" in err and "not 'tpu'" in err


def test_bench_lane_failure_is_a_failing_run(bench, monkeypatch, capsys):
    from horovod_tpu import benchmark
    from horovod_tpu.utils import compile_cache

    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(benchmark, "device_info", lambda: tpu)
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: None)

    def boom():
        raise RuntimeError("lane exploded")

    monkeypatch.setattr(bench, "LANES", (
        ("resnet50", lambda: {"value": 1.0, "vs_baseline": 2.0}),
        ("lm", boom),
        ("resnet101", lambda: None),             # switched off
        ("eager_allreduce", lambda: {"busbw_gbs": 3.0})))
    assert bench.main() == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["errors"] == {"lm": "RuntimeError: lane exploded"}
    assert "lm" not in line and "resnet101" not in line
    assert line["value"] == 1.0 and line["device"] == tpu
    assert line["eager_allreduce"] == {"busbw_gbs": 3.0}

    # A failed headline lane quotes no figure.
    monkeypatch.setattr(bench, "LANES", (("resnet50", boom),))
    assert bench.main() == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["value"] is None and line["vs_baseline"] is None

    monkeypatch.setattr(bench, "LANES", (("resnet50", lambda: {"value": 1}),))
    assert bench.main() == 0


def test_launcher_refuses_ranks_that_would_share_a_chip(monkeypatch):
    from jax._src import hardware_utils

    from horovod_tpu.runner import hosts, run

    chips = [4]
    monkeypatch.setattr(hardware_utils,
                        "num_available_tpu_chips_and_device_id",
                        lambda: (chips[0], None))
    two = hosts.allocate([hosts.HostSlots("localhost", 2)], 2)
    one = hosts.allocate([hosts.HostSlots("localhost", 1)], 1)
    msg = run.chip_contention(two, {"JAX_PLATFORMS": "tpu,cpu"})
    assert "2 ranks" in msg and "4 TPU chip(s)" in msg
    assert run.chip_contention(two, {}) is not None
    assert run.chip_contention(two, {"JAX_PLATFORMS": "cpu"}) is None
    assert run.chip_contention(one, {}) is None
    chips[0] = 0
    assert run.chip_contention(two, {}) is None


def test_unlisted_accelerator_kind_is_an_error():
    """MFU is never quietly left out or computed against a guess."""
    from horovod_tpu.benchmark import device_peak_tflops

    class Dev:
        platform, device_kind = "tpu", "TPU v5 lite"

    assert device_peak_tflops(Dev) == 197.0
    Dev.device_kind = "TPU v99"
    with pytest.raises(ValueError, match="TPU v99"):
        device_peak_tflops(Dev)
    Dev.platform, Dev.device_kind = "cpu", "cpu"
    assert device_peak_tflops(Dev) is None
