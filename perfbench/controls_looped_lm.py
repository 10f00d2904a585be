#!/usr/bin/env python3
"""The controls of a ``looped_lm`` cell's check, each through the
harness's own comparison at the cell's sizes.

    python3 perfbench/controls_looped_lm.py --workload ouro26b_t4096 --seed <n> [--seed <m> ...] [--program-seed <k> ...]

A control is the plain reference computing another function (``CONTROLS``:
the nine that ``perfbench/reference/looped_lm.py`` names).  For every seed
the cell's weights and first batch are made as ``run.py`` makes them; then,
for every control, a stand-in for the compiled step hands
``run._check_first_step`` the state that one SGD step from zero momentum
WITH THE CONTROL'S GRADIENTS would have left (each checked leaf's
parameter moved by ``-lr x gradient``) beside the control's loss, and the comparison
runs as it does in a timed run: the same reference, the same leaves
through ``cell.checked``, the configuration's ``check`` tolerances.  Every
control has to come out not correct; last, the program's own compiled step
goes through the same call and has to come out correct.  A
``--program-seed`` goes through that last call alone: more readings of the
program for the price of a state and one reference.  The step and every
reference are compiled once a process, whatever the seeds.

``--control`` names the controls to run (all nine without it).  One JSON
line a control and seed (``correct``, the checks that refused
it), the harness's own ``check (a)`` / ``check (b)`` lines above it with
every reading beside its limit.  Exit code 0 where every control was
refused and the program accepted, 1 otherwise, 2 off the chip (unless
``--rehearse-cpu``: tiny sizes and the rehearsal's wide tolerances, where
the outcomes mean nothing and only the code path is exercised).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONTROLS = {
    "three_passes": dict(loops=3),
    "cut_passes": dict(cut_passes=True),
    "norm_at_readouts": dict(norm_carried=False),
    "no_post_norms": dict(post_norms=False),
    "uniform_exit": dict(uniform_exit=True),
    "no_entropy": dict(entropy=False),
    "last_unnormalised": dict(last_takes_rest=False),
    "last_pass_only": dict(last_pass_only=True),
    "float8": dict(low_precision="float8_e4m3fn"),
}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, action="append", default=[])
    parser.add_argument("--program-seed", type=int, action="append",
                        default=[])
    parser.add_argument("--control", action="append", choices=sorted(CONTROLS),
                        help="these controls alone (default: all nine)")
    parser.add_argument("--rehearse-cpu", action="store_true")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu.topology import build_mesh
    from horovod_tpu.utils.compile_cache import enable_compile_cache
    from perfbench import run
    from perfbench.cell import on_first_device
    from perfbench.reference import looped_lm as reference

    _, entry, config, mix = run._cell_files(args.workload, args.rehearse_cpu)
    devices = jax.devices()
    if not args.rehearse_cpu and devices[0].platform != "tpu":
        print("controls: a control is read at the cell's sizes, on the "
              "chip only", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    hvd.init()
    mesh = build_mesh(axes=tuple(mix["mesh_axes"]),
                      devices=devices[:entry["chips"]])
    for key in run.HARNESS_KEYS:
        mix.pop(key)
    adapter = importlib.import_module("perfbench.adapters." + config["kind"])
    cell = adapter.build(config, mix, mesh)
    cfg = adapter.model_config(config, mix["seq_len"])
    paths = reference.leaf_paths(cfg.n_layers)
    learning_rate = -1.0 / cell.grad_per_delta

    def other_reference(control):
        kw = dict(CONTROLS[control])
        if "low_precision" in kw:
            kw["low_precision"] = getattr(jnp, kw["low_precision"])
        return jax.jit(functools.partial(
            reference.loss_and_grads, dims=adapter.reference_dims(cfg),
            **kw))

    def stand_in(loss, grads):
        """What ``compiled`` would return had the step's gradients been
        ``grads`` and its loss ``loss``."""
        def step(params, opt_state, tokens, labels):
            for name, grad in grads.items():
                path = paths[name]
                params = reference.with_leaf(
                    params, path, reference.leaf(params, path)
                    - learning_rate * np.asarray(grad, np.float32))
            return params, opt_state, loss
        return step

    others = {name: other_reference(name)
              for name in args.control or CONTROLS}
    compiled = cell.step.lower(*cell.state_shapes,
                               *cell.batch_shapes).compile()

    def one_seed(seed, controls):
        """``[(control or "program", checks)]``; nothing of the seed's
        gigabytes of state outlives the call."""
        state, (batch,) = cell.make(seed, 1)
        # The one reference a seed's comparisons share, computed once.
        held = cell.reference(state, batch)
        shared = dataclasses.replace(cell, reference=lambda *_: held)
        params, (tokens, labels) = on_first_device((state[0], batch), mesh)
        rows = []
        for control, other in controls.items():
            start = time.perf_counter()
            loss, grads, _ = jax.block_until_ready(
                other(params, tokens, labels))
            print(f"control {control}, seed {seed}: its reference "
                  f"{time.perf_counter() - start:.1f} s", flush=True)
            rows.append((control, run._check_first_step(
                shared, stand_in(loss, grads), state, batch,
                config["check"])[1]))
        del params
        rows.append(("program", run._check_first_step(
            shared, compiled, state, batch, config["check"])[1]))
        return rows

    refused_all = True
    for seed in args.seed + args.program_seed:
        for name, checks in one_seed(seed,
                                     others if seed in args.seed else {}):
            correct = all(checks.values())
            refused_all &= correct == (name == "program")
            print(json.dumps({
                "control": name, "seed": seed, "correct": correct,
                "refused_by": [k for k, ok in checks.items() if not ok]}),
                flush=True)
    return 0 if refused_all or args.rehearse_cpu else 1


if __name__ == "__main__":
    sys.exit(main())
