# Test/deploy image (reference Dockerfile + Dockerfile.test.cpu: one image
# that builds the native runtime and can run the full suite).  The compute
# path is JAX; swap the pip line for the matching jax[tpu] wheel on real
# TPU hosts.
#
# Stages (the MAIN image is the last stage, so a plain `docker build .`
# produces it; BuildKit skips the opt-in stage unless targeted):
#   mxnet-test — py3.11 stage that EXECUTES the MXNet binding suite
#                (opt-in: `docker build --target mxnet-test ...`)
#   main       — py3.12 test/deploy image (default)

# --- MXNet binding execution stage (opt-in) --------------------------------
# MXNet was archived upstream (Apache attic, 2023) and its last release
# ships wheels only through Python 3.11, so the binding cannot execute in
# the py3.12 main image or on the authoring host (no package egress there
# either; the binding is API-validated and its numpy-plane internals are
# the same code the EXECUTED torch/TF suites cover — see
# docs/frameworks.md for the descope statement).  Anyone with egress runs
# the real suite with:
#   docker build --target mxnet-test -t hvd-tpu-mxnet .
#   docker run hvd-tpu-mxnet
FROM python:3.11-slim AS mxnet-test
RUN apt-get update && apt-get install -y --no-install-recommends \
        g++ make && \
    rm -rf /var/lib/apt/lists/*
WORKDIR /horovod_tpu
COPY . .
# Separate resolutions: the framework is pinned to jax 0.9.0 (numpy>=2)
# while mxnet's final release pins numpy<2.0, so no joint resolve exists —
# install the pinned jax first, then mxnet alone without its pins.
# Whether that mxnet imports under numpy 2 is unverified: this stage has
# never been built (ROADMAP D12).
RUN pip install --no-cache-dir "jax==0.9.0" "jaxlib==0.9.0" flax optax \
        chex pytest pyyaml && \
    pip install --no-cache-dir --no-deps mxnet && \
    pip install --no-cache-dir --no-deps -e . && \
    python -m horovod_tpu.native.build
CMD ["sh", "-c", "JAX_PLATFORMS=cpu PYTHONPATH=/horovod_tpu \
     python -m horovod_tpu.runner -np 2 \
     python -m pytest tests/distributed/test_mxnet_binding.py -x -q"]

# --- Main test/deploy image (default target) -------------------------------
FROM python:3.12-slim AS main

RUN apt-get update && apt-get install -y --no-install-recommends \
        g++ make openssh-client && \
    rm -rf /var/lib/apt/lists/*

WORKDIR /horovod_tpu
COPY . .

# CPU jax by default (CI); on TPU hosts use: pip install 'jax[tpu]==0.9.0' \
#   -f https://storage.googleapis.com/jax-releases/libtpu_releases.html
RUN pip install --no-cache-dir "jax==0.9.0" "jaxlib==0.9.0" flax optax \
        orbax-checkpoint chex \
        einops numpy pytest pyyaml && \
    pip install --no-cache-dir -e .

# Binding-framework deps so their suites run NON-skipped in this image
# (the build host this repo was authored on has no package egress, so
# the pyspark veneer smoke in tests/distributed/test_spark_veneer.py
# could never execute real Spark there — this is where that self-heals).
# tensorflow+keras+torch back the TF/Keras/torch binding suites and the
# CI KERAS_BACKEND=jax gate; default-jre-headless gives pyspark its JVM.
# MXNet is NOT installed here: it publishes no wheel for Python >= 3.12,
# so an install in this stage could never succeed (see the mxnet-test
# stage above for the py3.11 path).
RUN apt-get update && \
    apt-get install -y --no-install-recommends default-jre-headless && \
    rm -rf /var/lib/apt/lists/*
RUN pip install --no-cache-dir tensorflow-cpu keras pyspark && \
    pip install --no-cache-dir torch --index-url \
        https://download.pytorch.org/whl/cpu

# Native runtime is built by the install hook; fail the image build if the
# library is missing rather than at first use.
RUN python -m horovod_tpu.native.build && \
    python -m horovod_tpu.runner --check-build

CMD ["bash", "ci/run_tests.sh"]
