"""Examples double as smoke tests, the reference's CI strategy
(.buildkite/gen-pipeline.sh runs example scripts under the launcher on
every image).  Tiny shapes: these verify the wiring end-to-end, not
performance."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EXAMPLES = os.path.join(REPO, "examples")


def _example_env(xla_devices=None):
    """Hermetic child env: CPU platform, PYTHONPATH exactly REPO (the
    package under test, not whatever the parent's path would resolve),
    optional virtual device count."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO
    if xla_devices is None:
        env.pop("XLA_FLAGS", None)
    else:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={xla_devices}")
    return env


def _run_example(script, args, np_=2, timeout=420, extra_env=None):
    env = _example_env()
    if extra_env:
        env.update(extra_env)
    cmd = [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np_),
           sys.executable, os.path.join(EXAMPLES, script)] + args
    return subprocess.run(cmd, capture_output=True, text=True,
                          timeout=timeout, env=env, cwd=REPO)


@pytest.mark.slow
def test_jax_mnist_single_process(tmp_path):
    """BASELINE config #1: the 1-process allreduce baseline."""
    res = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, "jax_mnist.py"),
         "--steps", "80", "--batch-size", "32",
         "--checkpoint-dir", str(tmp_path / "ck")],
        capture_output=True, text=True, timeout=420, env=_example_env(),
        cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "train accuracy" in res.stdout


@pytest.mark.slow
def test_jax_mnist_two_ranks(tmp_path):
    res = _run_example("jax_mnist.py", ["--steps", "60", "--batch-size",
                                        "32"])
    assert res.returncode == 0, res.stdout + res.stderr


@pytest.mark.slow
def test_pytorch_synthetic_benchmark():
    res = _run_example("pytorch_synthetic_benchmark.py",
                       ["--model", "resnet18", "--batch-size", "2",
                        "--image-size", "32", "--num-warmup-batches", "1",
                        "--num-batches-per-iter", "1", "--num-iters", "2"])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "Total img/sec" in res.stdout


@pytest.mark.slow
def test_tensorflow2_mnist(tmp_path):
    pytest.importorskip("tensorflow")
    res = _run_example("tensorflow2_mnist.py",
                       ["--steps", "80", "--batch-size", "32",
                        "--checkpoint-dir", str(tmp_path / "ck")])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "train accuracy" in res.stdout


@pytest.mark.slow
def test_keras_mnist(tmp_path):
    pytest.importorskip("keras")
    res = _run_example("keras_mnist.py",
                       ["--epochs", "2", "--batch-size", "64",
                        "--checkpoint-dir", str(tmp_path)])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "final train accuracy" in res.stdout


@pytest.mark.slow
def test_jax_synthetic_benchmark_reports_img_sec():
    """The port of the reference's synthetic benchmark runs its rounds over
    the mesh and prints img/sec as mean +- 1.96 sigma, per chip and in
    total."""
    import re
    res = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, "jax_synthetic_benchmark.py"),
         "--model", "resnet18", "--batch-size", "2", "--image-size", "32",
         "--num-warmup-batches", "1", "--num-batches-per-iter", "2",
         "--num-iters", "2"],
        capture_output=True, text=True, timeout=420,
        env=_example_env(xla_devices=4), cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert res.stdout.count("Iter #") == 2, res.stdout
    total = re.search(r"Total img/sec on 4 chip\(s\): ([0-9.]+) \+-[0-9.]+",
                      res.stdout)
    assert total and float(total.group(1)) > 0, res.stdout


@pytest.mark.slow
def test_pytorch_mnist_two_ranks():
    """Full torch MNIST recipe under the launcher (reference
    examples/pytorch_mnist.py run by CI under horovodrun)."""
    pytest.importorskip("torch")
    res = _run_example("pytorch_mnist.py",
                       ["--epochs", "3", "--batch-size", "64", "--lr",
                        "0.1", "--train-size", "2048", "--test-size",
                        "512"])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout
    assert "accuracy" in res.stdout


@pytest.mark.slow
def test_mxnet_mnist_two_ranks():
    mx = pytest.importorskip("mxnet")
    if getattr(mx, "__is_horovod_tpu_shim__", False):
        # test_mxnet_binding installs the API shim process-wide; the
        # example's subprocesses have no shim and need REAL mxnet.
        pytest.skip("only the mxnet API shim is present (no real mxnet)")
    res = _run_example("mxnet_mnist.py",
                       ["--epochs", "2", "--train-size", "1024",
                        "--test-size", "512"])
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout


@pytest.mark.slow
def test_jax_imagenet_resnet50_resume(tmp_path):
    """The ImageNet recipe trains, checkpoints, and resumes (reference
    keras_imagenet_resnet50.py's resume-from-checkpoint contract)."""
    ck = str(tmp_path / "ck")
    env = _example_env(xla_devices=4)
    args = [sys.executable,
            os.path.join(EXAMPLES, "jax_imagenet_resnet50.py"),
            "--epochs", "2", "--steps-per-epoch", "2", "--batch-size", "2",
            "--image-size", "32", "--num-classes", "8", "--warmup-epochs",
            "1", "--checkpoint-dir", ck]
    res = subprocess.run(args, capture_output=True, text=True, timeout=420,
                         env=env, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "epoch 1" in res.stdout
    # Second run resumes past the checkpointed epochs and trains 2 more.
    args[args.index("--epochs") + 1] = "4"
    res = subprocess.run(args, capture_output=True, text=True, timeout=420,
                         env=env, cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "resumed from epoch 1" in res.stdout
    assert "epoch 3" in res.stdout


@pytest.mark.slow
def test_jax_lm_pretrain_dp_tp_sp():
    """The LM pretraining flagship: 2x2x2 DPxTPxSP mesh, loss decreases."""
    res = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, "jax_lm_pretrain.py"),
         "--dp", "2", "--tp", "2", "--sp", "2", "--steps", "20",
         "--batch-size", "4", "--seq-len", "128", "--n-layers", "1"],
        capture_output=True, text=True, timeout=420,
        env=_example_env(xla_devices=8), cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout


def test_jax_word2vec():
    """Embedding-family example (reference tensorflow_word2vec.py): topic
    similarity margin must grow."""
    res = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, "jax_word2vec.py")],
        capture_output=True, text=True, timeout=420,
        env=_example_env(xla_devices=8), cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout


def test_jax_moe():
    """Expert-parallel Switch-MoE example: 2 data x 4 experts, learns."""
    res = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, "jax_moe.py"),
         "--steps", "100"],
        capture_output=True, text=True, timeout=420,
        env=_example_env(xla_devices=8), cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout


def test_jax_moe_ragged_dispatch():
    """The same example over the ragged transport (--dispatch ragged):
    the training loop must learn identically well."""
    res = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, "jax_moe.py"),
         "--steps", "100", "--dispatch", "ragged"],
        capture_output=True, text=True, timeout=420,
        env=_example_env(xla_devices=8), cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout


@pytest.mark.slow
def test_jax_lm_pretrain_dp_pp():
    """The LM example's --pp path: 2 data x 4 pipe stages, loss decreases."""
    res = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, "jax_lm_pretrain.py"),
         "--dp", "2", "--pp", "4", "--steps", "30", "--warmup-steps",
         "3", "--batch-size", "4", "--seq-len", "64", "--n-layers", "4"],
        capture_output=True, text=True, timeout=420,
        env=_example_env(xla_devices=8), cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout


@pytest.mark.slow
def test_jax_lm_pretrain_dp_pp_1f1b():
    """The LM example's --pp-schedule 1f1b path: same topology as the
    GPipe test, hand-scheduled 1F1B (O(stages) activation memory), loss
    decreases."""
    res = subprocess.run(
        [sys.executable, os.path.join(EXAMPLES, "jax_lm_pretrain.py"),
         "--dp", "2", "--pp", "4", "--pp-schedule", "1f1b", "--steps",
         "30", "--warmup-steps", "3", "--batch-size", "4", "--seq-len",
         "64", "--n-layers", "4"],
        capture_output=True, text=True, timeout=420,
        env=_example_env(xla_devices=8), cwd=REPO)
    assert res.returncode == 0, res.stdout + res.stderr
    assert "OK" in res.stdout
