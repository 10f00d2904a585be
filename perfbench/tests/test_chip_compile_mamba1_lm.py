"""The offline compile of ``jamba2_t16k``: the cell's step at its real
size for a v5e that is described and not attached
(``test_chip_compile.py``'s recipe).

Nothing runs, so nothing here is a measurement.  What it holds: the step
with the rest of the batch pool fits 15.75 GiB with 0.5 GiB to spare and
fills at least 11 GiB; both selective-scan kernels, both short-convolution
kernels and the three flash kernels are Mosaic custom calls of the
compiled step; and every kernel instruction of the step is one that the
adapter's ``Cell.kernels`` names, so that ``xla_ms_per_step`` means what
its name says.  Run by hand, in a process of its own (it loads the TPU
compiler): about two minutes.
"""

import importlib
import os
import re

import pytest

from perfbench import run

HBM_GIB = 15.75
SPARE_GIB = 0.5
FLOOR_GIB = 11.0
WORKLOAD = "jamba2_t16k"
KERNELS = ("mamba_scan_fwd", "mamba_scan_bwd", "short_conv_fwd",
           "short_conv_bwd", "flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


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


@pytest.fixture(scope="module")
def compiled(topo):
    import numpy as np
    from jax.sharding import Mesh

    _, entry, config, mix = run._cell_files(WORKLOAD, rehearse=False)
    mesh = Mesh(np.asarray(topo.devices[:entry["chips"]]),
                tuple(mix["mesh_axes"]))
    harness = {k: mix.pop(k) for k in run.HARNESS_KEYS}
    adapter = importlib.import_module("perfbench.adapters." + config["kind"])
    cell = adapter.build(config, mix, mesh)
    lowered = cell.step.lower(*cell.state_shapes, *cell.batch_shapes)
    assert "tpu_custom_call" in lowered.as_text()
    return cell, harness, lowered.compile()


def test_the_step_fits_one_chip_and_fills_it(compiled):
    import numpy as np

    cell, harness, program = compiled
    a = program.memory_analysis()
    step = (a.argument_size_in_bytes + a.output_size_in_bytes
            - a.alias_size_in_bytes + a.temp_size_in_bytes
            + a.generated_code_size_in_bytes)
    batch = sum(int(np.prod(s.sharding.shard_shape(s.shape)))
                * s.dtype.itemsize for s in cell.batch_shapes)
    gib = (step + (harness["pool"] - 1) * batch) / 2 ** 30
    print(f"{WORKLOAD}: {gib:.4f} GiB (arguments "
          f"{a.argument_size_in_bytes}, outputs {a.output_size_in_bytes}, "
          f"aliased {a.alias_size_in_bytes}, temporaries "
          f"{a.temp_size_in_bytes}, code {a.generated_code_size_in_bytes})")
    assert FLOOR_GIB <= gib <= HBM_GIB - SPARE_GIB


def test_every_kernel_of_the_step_is_one_the_adapter_names(compiled):
    cell, _, program = compiled
    text = program.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    matches = [m for kernel in cell.kernels.values()
               for m in kernel["match"]]
    missed = [line.strip()[:80] for line in calls
              if not any(m in line for m in matches)]
    assert not missed, missed
    for name in KERNELS:
        found = [line for line in calls
                 if re.search(rf"%{name}(\.\d+)? = ", line)]
        assert found, name
    # 13 Mamba layers, each forward, recomputed and backward.
    count = lambda name: sum(bool(re.search(rf"%{name}(\.\d+)? = ", line))
                             for line in calls)
    assert count("mamba_scan_fwd") == 26 and count("mamba_scan_bwd") == 13
