"""Keras binding tests (reference test/test_keras.py:48-173), rank-aware —
run standalone (size 1) or under ``hvdrun -np N``.

Backend-parametrized by environment: the suite runs as-is under BOTH
``KERAS_BACKEND=tensorflow`` and ``KERAS_BACKEND=jax`` (ci/run_tests.sh
runs the jax pass explicitly; the backend is fixed per process, so the
two passes are separate pytest invocations)."""

import os

import numpy as np
import pytest

keras = pytest.importorskip("keras")

BACKEND = keras.backend.backend()
if BACKEND not in ("tensorflow", "jax"):
    pytest.skip(f"unsupported keras backend {BACKEND}",
                allow_module_level=True)


@pytest.fixture()
def khvd(hvd):
    import horovod_tpu.keras as khvd
    return khvd


def _tiny_model():
    keras.utils.set_random_seed(42)   # same init on all ranks
    return keras.Sequential([
        keras.layers.Input(shape=(4,)),
        keras.layers.Dense(3, activation="relu"),
        keras.layers.Dense(1),
    ])


def test_keras_distributed_optimizer_fit(khvd, rank, size):
    """model.fit with the wrapped optimizer: gradients are averaged so
    weights stay identical across ranks despite rank-dependent data
    (reference test_keras.py:48-86)."""
    model = _tiny_model()
    opt = khvd.DistributedOptimizer(keras.optimizers.SGD(learning_rate=0.05))
    model.compile(optimizer=opt, loss="mse")
    rng = np.random.RandomState(100 + rank)   # different data per rank
    x = rng.randn(16, 4).astype(np.float32)
    y = rng.randn(16, 1).astype(np.float32)
    model.fit(x, y, batch_size=8, epochs=1, verbose=0)

    flat = np.concatenate([w.ravel() for w in model.get_weights()])
    gathered = khvd.allgather(flat[None, :], name="keras.weights.check")
    for r in range(size):
        assert np.allclose(gathered[r], gathered[0], atol=1e-5), \
            f"rank {r} weights diverged"


def test_keras_broadcast_callback(khvd, rank, size):
    """BroadcastGlobalVariablesCallback overwrites divergent init with the
    root's (reference _keras/callbacks.py:20-43)."""
    keras.utils.set_random_seed(7 + rank)   # deliberately different init
    model = keras.Sequential([
        keras.layers.Input(shape=(4,)),
        keras.layers.Dense(2),
    ])
    model.compile(optimizer=keras.optimizers.SGD(learning_rate=0.0),
                  loss="mse")
    cb = khvd.callbacks.BroadcastGlobalVariablesCallback(root_rank=0)
    x = np.zeros((4, 4), np.float32)
    y = np.zeros((4, 2), np.float32)
    model.fit(x, y, batch_size=4, epochs=1, verbose=0, callbacks=[cb])

    flat = np.concatenate([w.ravel() for w in model.get_weights()])
    gathered = khvd.allgather(flat[None, :], name="keras.bcast.check")
    for r in range(size):
        assert np.allclose(gathered[r], gathered[0]), \
            f"rank {r} weights not broadcast"


def test_keras_metric_average_callback(khvd, rank, size):
    from horovod_tpu._keras.callbacks import MetricAverageCallbackImpl
    cb = MetricAverageCallbackImpl()
    logs = {"loss": float(rank + 1)}
    cb._average_metrics_in_place(logs)
    assert np.isclose(logs["loss"], (size + 1) / 2)


def test_keras_lr_warmup_callback(khvd, rank, size):
    """Warmup multiplies LR from lr/size up to lr (reference
    _keras/callbacks.py:163-185)."""
    model = _tiny_model()
    opt = keras.optimizers.SGD(learning_rate=0.1)
    model.compile(optimizer=opt, loss="mse")
    cb = khvd.callbacks.LearningRateWarmupCallback(warmup_epochs=2,
                                                   steps_per_epoch=2)
    x = np.zeros((8, 4), np.float32)
    y = np.zeros((8, 1), np.float32)
    model.fit(x, y, batch_size=4, epochs=3, verbose=0, callbacks=[cb])
    # after warmup the LR is back to the base value
    assert np.isclose(float(np.asarray(model.optimizer.learning_rate)), 0.1,
                      atol=1e-6)


def test_keras_save_load_model(khvd, rank, size, tmp_path):
    """Save with a wrapped optimizer, reload via hvd load_model: the
    restored optimizer is re-wrapped (reference test_keras.py:148-173)."""
    model = _tiny_model()
    opt = khvd.DistributedOptimizer(keras.optimizers.Adam(learning_rate=1e-3))
    model.compile(optimizer=opt, loss="mse")
    x = np.zeros((8, 4), np.float32)
    y = np.zeros((8, 1), np.float32)
    model.fit(x, y, batch_size=4, epochs=1, verbose=0)

    path = os.path.join(str(tmp_path), f"model_r{rank}.keras")
    model.save(path)
    loaded = khvd.load_model(path)
    assert type(loaded.optimizer).__name__ == "Adam"
    assert hasattr(type(loaded.optimizer), "_hvd_wrapped"), \
        "restored optimizer is not distributed-wrapped"
    for a, b in zip(model.get_weights(), loaded.get_weights()):
        assert np.allclose(a, b)
    # the reloaded model must still train under the distributed optimizer
    loaded.fit(x, y, batch_size=4, epochs=1, verbose=0)
