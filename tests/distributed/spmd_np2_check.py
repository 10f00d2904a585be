"""Joint launcher + multi-process SPMD certification.

Run as::

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \
    python -m horovod_tpu.runner -np 2 --jax-distributed \
    python tests/distributed/spmd_np2_check.py

Each launched rank holds 4 virtual CPU devices; ``hvd.init()`` sees
``HOROVOD_JAX_DISTRIBUTED=1`` + ``HOROVOD_COORDINATOR_ADDR`` (set by the
launcher's ``--jax-distributed``) and bootstraps ``jax.distributed``
before any backend init, so ``jax.devices()`` is the GLOBAL 8-device set
spanning both processes.  The script then:

1. runs a real DP×model SPMD training step (``make_train_step``) over a
   global (4, 2) mesh built from all 8 devices — XLA collectives cross
   the process boundary; and
2. allreduces the resulting loss over the NATIVE TCP eager plane in the
   same job, asserting both ranks computed the same value —
   the one seam no other test covers (multi-process SPMD plane + native
   plane live together; reference equivalent: every suite running under
   ``horovodrun``, ``.buildkite/gen-pipeline.sh:120-190``).

Prints ``SPMD_NP2_OK`` on rank 0.
"""

import os
import sys

import numpy as np

# The launcher's env is authoritative; the asserts catch direct
# mis-invocation (without --jax-distributed this script would run two
# independent single-process meshes and certify nothing).
assert os.environ.get("HOROVOD_JAX_DISTRIBUTED") == "1", \
    "run under hvdrun --jax-distributed"

import jax  # noqa: E402  (import only; backend init happens in hvd.init)
import jax.numpy as jnp  # noqa: E402

import horovod_tpu as hvd  # noqa: E402

hvd.init()

assert jax.process_count() == 2, jax.process_count()
assert jax.device_count() == 8, jax.device_count()
assert len(jax.local_devices()) == 4

import optax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from horovod_tpu.benchmark import make_train_step  # noqa: E402
from horovod_tpu.models import ResNet18  # noqa: E402
from horovod_tpu.topology import build_mesh  # noqa: E402

rank, size = hvd.rank(), hvd.size()

mesh = build_mesh(axes=("data", "model"), shape=(4, 2),
                  devices=jax.devices())

model = ResNet18(num_classes=8)
rng = jax.random.PRNGKey(0)
variables = model.init(rng, jnp.zeros((1, 32, 32, 3), jnp.float32),
                       train=False)
params, batch_stats = variables["params"], variables["batch_stats"]
optimizer = optax.sgd(0.01, momentum=0.9)
opt_state = optimizer.init(params)

# Global batch sharded over the data axis: each PROCESS contributes its
# local half via make_array_from_process_local_data — the multi-host
# input path a pod job uses.
global_bs = 8
# default_rng(0): the same global batch on both ranks; each process
# contributes only its local slice below.
images_g = np.random.default_rng(0).standard_normal(
    (global_bs, 32, 32, 3)).astype(np.float32)
labels_g = (np.arange(global_bs) % 8).astype(np.int32)
data_sh = NamedSharding(mesh, P("data"))
images = jax.make_array_from_process_local_data(
    data_sh, images_g[rank * 4:(rank + 1) * 4])
labels = jax.make_array_from_process_local_data(
    data_sh, labels_g[rank * 4:(rank + 1) * 4])

repl = NamedSharding(mesh, P())
params, batch_stats, opt_state = jax.device_put(
    (params, batch_stats, opt_state), repl)

step = make_train_step(model, optimizer, mesh, axis_name="data")
params, batch_stats, opt_state, loss = step(
    params, batch_stats, opt_state, images, labels)
loss_val = float(np.asarray(loss))
assert np.isfinite(loss_val), loss_val

# Seam check: the native TCP plane is alive in the SAME job; both ranks
# must have computed the SAME loss (the SPMD step is deterministic and
# its collectives spanned both processes).
mean = np.asarray(hvd.allreduce(np.array([loss_val], np.float64),
                                name="spmd.loss"))
assert abs(mean[0] - loss_val) < 1e-9, (mean[0], loss_val)

# Second step with the updated params must also agree (optimizer state
# advanced consistently on both processes).
params, batch_stats, opt_state, loss2 = step(
    params, batch_stats, opt_state, images, labels)
loss2_val = float(np.asarray(loss2))
mean2 = np.asarray(hvd.allreduce(np.array([loss2_val], np.float64),
                                 name="spmd.loss2"))
assert abs(mean2[0] - loss2_val) < 1e-9
assert loss2_val != loss_val  # training moved

hvd.shutdown()
if rank == 0:
    print("SPMD_NP2_OK", flush=True)
sys.exit(0)
