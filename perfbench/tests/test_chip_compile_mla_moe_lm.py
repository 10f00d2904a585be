"""The offline compile that sized ``glm47flash_t8192``'s depth and
``remat``: the cell's step at its real size for a v5e that is described
and not attached (``test_chip_compile.py``'s recipe), once per
recomputation policy.

Nothing runs, so nothing here is a measurement.  The rule (ISSUE 37): the
least of ``none`` / ``dots`` / ``full`` whose step, with the rest of the
batch pool, fits 15.75 GiB with 0.5 GiB to spare, and reads at least 8
GiB (under that the cell is too small: add layers); the traffic file
carries the policy this finds.  And every kernel instruction of the
compiled step is one the adapter's ``Cell.kernels`` matches, whatever
number XLA gave it.  Run by hand, in a process of its own (it loads the
TPU compiler): about six minutes.
"""

import importlib
import os
import re

import pytest

from perfbench import run

HBM_GIB = 15.75
SPARE_GIB = 0.5
FLOOR_GIB = 8.0
POLICIES = ("none", "dots", "full")
WORKLOAD = "glm47flash_t8192"
KERNEL = re.compile(r"^\s*(%[\w.\-]+ = .*custom_call_target="
                    r"\"tpu_custom_call\".*)$", re.MULTILINE)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def compiled_step(topo, remat: str):
    """``(cell, pool, compiled step or None)`` with ``remat``; None where
    the compiler refuses the program for want of memory."""
    import numpy as np
    from jax.sharding import Mesh

    _, entry, config, mix = run._cell_files(WORKLOAD, rehearse=False)
    mesh = Mesh(np.asarray(topo.devices[:entry["chips"]]),
                tuple(mix["mesh_axes"]))
    harness = {k: mix.pop(k) for k in run.HARNESS_KEYS}
    adapter = importlib.import_module("perfbench.adapters." + config["kind"])
    cell = adapter.build(config, dict(mix, remat=remat), mesh)
    lowered = cell.step.lower(*cell.state_shapes, *cell.batch_shapes)
    assert "tpu_custom_call" in lowered.as_text()
    try:
        return cell, harness["pool"], lowered.compile()
    except Exception as e:
        if "RESOURCE_EXHAUSTED" not in str(e) and "memory" not in str(e):
            raise
        print(f"{WORKLOAD} remat={remat}: refused: {str(e)[:300]}")
        return cell, harness["pool"], None


def step_gib(cell, pool, compiled, remat) -> float:
    """GiB the cell needs on its chip: the compiled step plus the rest of
    the batch pool; ``inf`` for a refused program."""
    import numpy as np

    if compiled is None:
        return float("inf")
    a = compiled.memory_analysis()
    step = (a.argument_size_in_bytes + a.output_size_in_bytes
            - a.alias_size_in_bytes + a.temp_size_in_bytes
            + a.generated_code_size_in_bytes)
    batch = sum(int(np.prod(s.sharding.shard_shape(s.shape)))
                * s.dtype.itemsize for s in cell.batch_shapes)
    gib = (step + (pool - 1) * batch) / 2 ** 30
    print(f"{WORKLOAD} remat={remat}: {gib:.4f} GiB (arguments "
          f"{a.argument_size_in_bytes}, outputs {a.output_size_in_bytes}, "
          f"aliased {a.alias_size_in_bytes}, temporaries "
          f"{a.temp_size_in_bytes}, code {a.generated_code_size_in_bytes})")
    return gib


def test_the_traffic_file_carries_the_least_policy_that_fits(topo):
    _, _, _, mix = run._cell_files(WORKLOAD, rehearse=False)
    steps = {remat: compiled_step(topo, remat) for remat in POLICIES}
    sizes = {remat: step_gib(*steps[remat], remat) for remat in POLICIES}
    fits = [r for r in POLICIES if sizes[r] <= HBM_GIB - SPARE_GIB]
    assert fits, sizes
    assert mix["remat"] == fits[0], (mix["remat"], sizes)
    assert sizes[fits[0]] >= FLOOR_GIB, sizes
    # Every kernel of the step the cell runs is in the cell's table.
    cell, _, compiled = steps[mix["remat"]]
    matches = [m for kernel in cell.kernels.values()
               for m in kernel["match"]]
    kernels = KERNEL.findall(compiled.as_text())
    assert len(kernels) > 100
    missed = [text[:40] for text in kernels
              if not any(m in text for m in matches)]
    assert not missed, missed
