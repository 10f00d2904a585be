"""The rules of the entry scripts that run on the chip, checked without one:
``chip_smoke.py`` refuses the CPU by name, the compile cache can be placed
from outside, ``hvd.init()`` under the launcher claims no device, and the
launcher refuses ranks that would contend for a chip."""

import os
import subprocess
import sys

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
