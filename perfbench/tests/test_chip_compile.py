"""Every cell's step program compiled at its real size for a v5e that is
described and not attached (rehearsal 3 of the on-chip-measurement guide).

Nothing runs, so nothing here is a measurement: the compiler either takes
the program or refuses it, and says what it would hold on each chip.  The
topology is described inside a fixture, never at import, and everything
compiles in this process (a child could not load libtpu beside it).
About 75 seconds on eight cores.
"""

import json
import os

import pytest

from perfbench import run

HBM_GIB = 15.75          # what the v5e's allocator hands out of 16 GB


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # A program compiled for a described chip cannot be read back from
    # the persistent cache without the chip: keep these out of it.
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _cells():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.mark.parametrize("workload", _cells())
def test_cell_compiles_and_fits_one_chip(topo, workload):
    import importlib

    import numpy as np
    from jax.sharding import Mesh

    _, entry, config, mix = run._cell_files(workload, rehearse=False)
    devices = np.asarray(topo.devices[:entry["chips"]])
    mesh = Mesh(devices, tuple(mix["mesh_axes"]))
    harness = {k: mix.pop(k) for k in run.HARNESS_KEYS}
    adapter = importlib.import_module("perfbench.adapters." + config["kind"])
    cell = adapter.build(config, mix, mesh)

    lowered = cell.step.lower(*cell.state_shapes, *cell.batch_shapes)
    if cell.kernels:
        assert "tpu_custom_call" in lowered.as_text()
    analysis = lowered.compile().memory_analysis()
    step = (analysis.argument_size_in_bytes + analysis.output_size_in_bytes
            - analysis.alias_size_in_bytes + analysis.temp_size_in_bytes
            + analysis.generated_code_size_in_bytes)
    batch = sum(int(np.prod(s.sharding.shard_shape(s.shape)))
                * s.dtype.itemsize for s in cell.batch_shapes)
    pool = (harness["pool"] - 1) * batch
    gib = (step + pool) / 2 ** 30
    print(f"{workload}: step {step / 2 ** 30:.2f} GiB + rest of the pool "
          f"{pool / 2 ** 30:.2f} GiB on each chip")
    assert gib < HBM_GIB
