"""The offline compile of ``ouro26b_t4096``: the cell's step at its real
size for a v5e that is described and not attached
(``test_chip_compile.py``'s recipe), at the cell's depth and at two layers
less and more.

Nothing runs, so nothing here is a measurement.  What it holds: the step
as the cell runs it, with the rest of the batch pool, fits 15.75 GiB with
0.5 GiB to spare and fills at least 11 GiB; the three flash kernels are
Mosaic custom calls of the compiled step, once a layer and pass (the
forward once more, recomputed), and the program holds no ``while``; every
kernel instruction of the step is one that the adapter's ``Cell.kernels``
names, so that ``xla_ms_per_step`` means what its name says; and the bytes
at the cell's depth less and plus two, printed for ``PERF.md``'s table.
Run by hand, in a process of its own (it loads the TPU compiler): about
ten minutes.
"""

import importlib
import os
import re
import time

import pytest

from perfbench import run

HBM_GIB = 15.75
SPARE_GIB = 0.5
FLOOR_GIB = 11.0
WORKLOAD = "ouro26b_t4096"
KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


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
def compile_at(topo):
    """``compile_at(depth=None) -> (cell, harness, program, seconds,
    GiB)``, each compiled once."""
    import numpy as np
    from jax.sharding import Mesh

    done = {}

    def build(depth=None):
        _, entry, config, mix = run._cell_files(WORKLOAD, rehearse=False)
        depth = depth or config["num_hidden_layers"]
        if depth in done:
            return done[depth]
        config["num_hidden_layers"] = depth
        mesh = Mesh(np.asarray(topo.devices[:entry["chips"]]),
                    tuple(mix["mesh_axes"]))
        harness = {k: mix.pop(k) for k in run.HARNESS_KEYS}
        adapter = importlib.import_module(
            "perfbench.adapters." + config["kind"])
        cell = adapter.build(config, mix, mesh)
        start = time.perf_counter()
        lowered = cell.step.lower(*cell.state_shapes, *cell.batch_shapes)
        assert "tpu_custom_call" in lowered.as_text()
        program = lowered.compile()
        seconds = time.perf_counter() - start
        a = program.memory_analysis()
        step = (a.argument_size_in_bytes + a.output_size_in_bytes
                - a.alias_size_in_bytes + a.temp_size_in_bytes
                + a.generated_code_size_in_bytes)
        batch = sum(int(np.prod(s.sharding.shard_shape(s.shape)))
                    * s.dtype.itemsize for s in cell.batch_shapes)
        gib = (step + (harness["pool"] - 1) * batch) / 2 ** 30
        print(f"{WORKLOAD} N={depth}: {gib:.4f} GiB (arguments "
              f"{a.argument_size_in_bytes}, temporaries "
              f"{a.temp_size_in_bytes}, code "
              f"{a.generated_code_size_in_bytes}), lowered and compiled in "
              f"{seconds:.1f} s")
        done[depth] = cell, harness, program, seconds, gib
        return done[depth]

    return build


def _calls(program):
    return [line for line in program.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _count(calls, name):
    return sum(bool(re.search(rf"%{name}(\.\d+)? = ", line))
               for line in calls)


def test_the_step_fits_one_chip_and_fills_it(compile_at):
    *_, gib = compile_at()
    assert FLOOR_GIB <= gib <= HBM_GIB - SPARE_GIB


def test_every_kernel_of_the_step_is_one_the_adapter_names(compile_at):
    cell, _, program, _, _ = compile_at()
    calls = _calls(program)
    assert calls
    matches = [m for kernel in cell.kernels.values()
               for m in kernel["match"]]
    missed = [line.strip()[:80] for line in calls
              if not any(m in line for m in matches)]
    assert not missed, missed
    for name in KERNELS:
        assert _count(calls, name), name


def test_the_passes_are_written_out(compile_at):
    """The program holds a layer's kernels once a pass (the forward
    twice: once recomputed) and no ``while``."""
    _, _, program, _, _ = compile_at()
    config = run._cell_files(WORKLOAD, rehearse=False)[2]
    uses = config["num_hidden_layers"] * config["total_ut_steps"]
    calls = _calls(program)
    assert _count(calls, "flash_fwd") == 2 * uses
    assert _count(calls, "flash_bwd_dq") == uses
    assert _count(calls, "flash_bwd_dkv") == uses
    assert " while(" not in program.as_text()


@pytest.mark.parametrize("step", (-2, 2))
def test_the_bytes_two_layers_less_and_more(compile_at, step):
    depth = run._cell_files(WORKLOAD, rehearse=False)[2]["num_hidden_layers"]
    *_, gib = compile_at(depth=depth)
    *_, other = compile_at(depth=depth + step)
    # A layer is 0.287 GiB of state, 0.19 of float32 gradient and 0.125
    # of saved block inputs, and whatever the heap's packing adds.
    assert 0.4 < (other - gib) / step < 1.0
