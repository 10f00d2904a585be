"""The offline compile that sized ``zaya1_8b_t16k``'s depth: the cell's
step at its real size for a v5e that is described and not attached
(``test_chip_compile.py``'s recipe), with the traffic file's ``remat``
(``full``).

Nothing runs, so nothing here is a measurement.  The rule (ISSUE 39, as
ISSUE 53 takes it over): the largest depth whose step, with the rest of
the batch pool, fits 15.75 GiB with 0.5 GiB to spare, whose check's
reference fits beside the state, and whose step lets a 15 s run hold five
fenced chunks of 2 steps (a step under 1.875 s).  By my compiles (PR 53):
DEPTHS_GIB below.  This test holds the configuration's depth to the first
part, to the floor of 4 GiB (a quarter of the chip), and the compiled
step's kernels to the adapter's table: every kernel instruction is one
``Cell.kernels`` matches; under ``remat`` ``full`` a layer's forward flash
kernel and its three forward grouped matmuls run twice.  Run by hand, in a
process of its own (it loads the TPU compiler): about two minutes.
"""

import importlib
import os
import re

import pytest

from perfbench import run

HBM_GIB = 15.75
SPARE_GIB = 0.5
FLOOR_GIB = 4.0
WORKLOAD = "zaya1_8b_t16k"
KERNEL = re.compile(r"^\s*(%[\w.\-]+ = .*custom_call_target="
                    r"\"tpu_custom_call\".*)$", re.MULTILINE)
# GiB of the step with the rest of the pool, by depth (my offline
# compiles, PR 53; the compiler's schedule, not the depth alone, sets the
# temporaries: 9 layers take more than 11).
DEPTHS_GIB = {9: 14.5058, 11: 14.0345, 13: 14.7108}


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


def test_the_configurations_depth_fits_and_every_kernel_is_named(topo):
    import numpy as np
    from jax.sharding import Mesh

    _, entry, config, mix = run._cell_files(WORKLOAD, rehearse=False)
    assert mix["remat"] == "full" and entry["chips"] == 1
    layers = config["num_hidden_layers"]
    mesh = Mesh(np.asarray(topo.devices[:entry["chips"]]),
                tuple(mix["mesh_axes"]))
    harness = {k: mix.pop(k) for k in run.HARNESS_KEYS}
    adapter = importlib.import_module("perfbench.adapters." + config["kind"])
    cell = adapter.build(config, mix, mesh)
    lowered = cell.step.lower(*cell.state_shapes, *cell.batch_shapes)
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    a = compiled.memory_analysis()
    step = (a.argument_size_in_bytes + a.output_size_in_bytes
            - a.alias_size_in_bytes + a.temp_size_in_bytes
            + a.generated_code_size_in_bytes)
    batch = sum(int(np.prod(s.sharding.shard_shape(s.shape)))
                * s.dtype.itemsize for s in cell.batch_shapes)
    gib = (step + (harness["pool"] - 1) * batch) / 2 ** 30
    print(f"{WORKLOAD} L={layers} remat=full: {gib:.4f} GiB (arguments "
          f"{a.argument_size_in_bytes}, outputs {a.output_size_in_bytes}, "
          f"aliased {a.alias_size_in_bytes}, temporaries "
          f"{a.temp_size_in_bytes}, code {a.generated_code_size_in_bytes})")
    assert FLOOR_GIB <= gib <= HBM_GIB - SPARE_GIB, gib
    assert gib == pytest.approx(DEPTHS_GIB[layers], abs=0.25)
    kernels = KERNEL.findall(compiled.as_text())
    matches = [m for kernel in cell.kernels.values()
               for m in kernel["match"]]
    missed = [text[:40] for text in kernels
              if not any(m in text for m in matches)]
    assert not missed, missed

    def count(name):
        return sum(bool(re.match(rf"%{name}(\.\d+)? = ", text))
                   for text in kernels)

    # Forward and recomputed (remat full: every half layer whole), then
    # the backward's: dQ and dK+dV once; of the grouped matmuls gate, up
    # and down twice forward, and for each of the three once the rows'
    # gradient and once the weights'.
    for name, times in (("flash_fwd", 2), ("flash_bwd_dq", 1),
                        ("flash_bwd_dkv", 1), ("moe_gmm", 6),
                        ("moe_gmm_nt", 3), ("moe_tgmm", 3)):
        assert count(name) == times * layers, (name, count(name))
