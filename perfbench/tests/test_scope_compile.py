"""The LM step compiled for the described v5e holds the kernel names and
the vocabulary where the TPU compiler leaves them: the place to read what
that compiler does to ``op_name`` in fusions before spending chip time
(PERF.md, "Reading a trace").  Same fixture as ``test_chip_compile.py``;
nothing runs.  About 25 seconds.
"""

import importlib

import numpy as np

from perfbench import run, scope_reduce
from test_chip_compile import topo  # noqa: F401  (the fixture)


def test_lm_step_for_the_v5e_holds_kernel_names_and_vocabulary(topo):  # noqa: F811
    from jax.sharding import Mesh

    _, entry, config, mix = run._cell_files("gpt67_t2048", rehearse=False)
    mesh = Mesh(np.asarray(topo.devices[:entry["chips"]]),
                tuple(mix["mesh_axes"]))
    for key in run.HARNESS_KEYS:
        mix.pop(key)
    adapter = importlib.import_module("perfbench.adapters." + config["kind"])
    cell = adapter.build(config, mix, mesh)
    text = cell.step.lower(*cell.state_shapes,
                           *cell.batch_shapes).compile().as_text()
    assert text.startswith("HloModule jit_hvd_lm_train_step,")
    hlo = scope_reduce.parse_hlo(text)
    instructions, computations, _ = hlo
    entry_names = next(names for c, names in computations.items()
                       if c.startswith("main"))
    placed = {name: scope_reduce.classify(name, hlo) for name in entry_names}

    # The kernels: six layers of each, found by name, under their scope.
    kernels = [k for _, _, k, _ in placed.values() if k]
    assert sorted(set(kernels)) == sorted(scope_reduce.KERNEL_NAMES)
    assert all(kernels.count(k) == config["n_layer"] for k in set(kernels))
    assert all(instructions[n].name.startswith(k + ".")
               for n, (_, _, k, _) in placed.items() if k)
    # The old handle still finds exactly them.
    assert text.count('custom_call_target="tpu_custom_call"') == len(kernels)

    # Every scope of the step shows on some executed instruction, in the
    # phase it belongs to.
    found = {(scope, phase) for phase, scope, _, _ in placed.values()}
    for scope in ("embed", "attn/qkv", "attn/out", "attn/flash_attention",
                  "mlp", "head", "loss"):
        assert (scope, "fwd") in found, scope
    for scope in ("embed", "attn/qkv", "attn/out", "attn/flash_attention",
                  "mlp", "head"):
        assert (scope, "bwd") in found, scope
    assert ("optimizer", "optimizer") in found
    assert not any(phase == "remat" for phase, _, _, _ in placed.values())

    # What the TPU compiler does with the names (PERF.md): on one chip the
    # SGD update is fused into the weight-gradient matmul, and the fusion
    # carries the matmul's name, not the update's.
    fused_updates = [
        n for n, (phase, _, _, inside) in placed.items()
        if phase == "bwd" and "optimizer" in inside]
    assert len(fused_updates) >= 6 * config["n_layer"]
    assert all("transpose(" in instructions[n].op_name
               for n in fused_updates)
    # The q/k/v projections are written straight into the kernel's
    # layout: the relayout is part of attn/qkv, not of the flash scope.
    projections = [n for n in entry_names
                   if n.startswith("convolution_bitcast_fusion")]
    assert len(projections) >= 3 * config["n_layer"]
    assert {placed[n][1] for n in projections} <= {"attn/qkv", "attn/out"}

    # Executed matmuls, fusions, kernels and collectives all have a place.
    held = [n for n in entry_names
            if instructions[n].opcode in ("fusion", "custom-call",
                                          "convolution", "dot")]
    assert [n for n in held if placed[n][0] == "unattributed"] == []
