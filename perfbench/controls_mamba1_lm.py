#!/usr/bin/env python3
"""The controls of a ``mamba1_lm`` cell's check, each through the
harness's own comparison at the cell's sizes.

    python3 perfbench/controls_mamba1_lm.py --workload jamba2_t16k --seed <n> [--seed <m> ...]

A control is the plain reference computing another function (``CONTROLS``:
the six that ``perfbench/reference/mamba1_lm.py`` names).  For every seed
the cell's weights and first batch are made as ``run.py`` makes them; then,
for every control, a stand-in for the compiled step hands
``run._check_first_step`` the state that one SGD step from zero momentum
WITH THE CONTROL'S GRADIENTS would have left (each checked leaf's
parameter moved by ``-lr x gradient`` and its momentum slot holding the
gradient rounded to bf16) beside the control's loss, and the comparison
runs as it does in a timed run: the same reference, the same leaves
through ``cell.checked``, the configuration's ``check`` tolerances.  Every
control has to come out not correct; last, the program's own compiled step
goes through the same call and has to come out correct.

One JSON line a control and seed (``correct``, the checks that refused
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
    "float8": dict(low_precision="float8_e4m3fn"),
    "state_reset": dict(reset_every=256),
    "one_decay": dict(one_decay=True),
    "no_inner_norms": dict(inner_norms=False),
    "no_skip": dict(skip=False),
    "independent_kv": dict(independent_kv=True),
}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, action="append", required=True)
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
    from perfbench.reference import mamba1_lm as reference

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
    paths = reference.leaf_paths(cfg.layer_types)
    learning_rate = -1.0 / cell.grad_per_delta

    def other_reference(control):
        kw = dict(CONTROLS[control])
        if "low_precision" in kw:
            kw["low_precision"] = getattr(jnp, kw["low_precision"])
        return jax.jit(functools.partial(
            reference.loss_and_tail_grads, dims=adapter.reference_dims(cfg),
            layer_types=cfg.layer_types, **kw))

    def stand_in(loss, grads):
        """What ``compiled`` would return had the step's gradients been
        ``grads`` and its loss ``loss``."""
        def step(params, opt_state, tokens, labels):
            momentum = next(i for i, s in enumerate(opt_state)
                            if hasattr(s, "trace"))
            trace = opt_state[momentum].trace
            for name, grad in grads.items():
                grad, path = np.asarray(grad, np.float32), paths[name]
                params = reference.with_leaf(
                    params, path, reference.leaf(params, path)
                    - learning_rate * grad)
                trace = reference.with_leaf(
                    trace, path, jnp.asarray(grad).astype(
                        reference.leaf(trace, path).dtype))
            opt_state = tuple(
                s._replace(trace=trace) if i == momentum else s
                for i, s in enumerate(opt_state))
            return params, opt_state, loss
        return step

    others = {name: other_reference(name) for name in CONTROLS}

    def one_seed(seed):
        """``[(control or "program", checks)]``; nothing of the seed's
        8 GiB of state outlives the call."""
        state, (batch,) = cell.make(seed, 1)
        compiled = cell.step.lower(*state, *batch).compile()
        # The one reference a seed's comparisons share, computed once.
        held = cell.reference(state, batch)
        shared = dataclasses.replace(cell, reference=lambda *_: held)
        params, (tokens, labels) = on_first_device((state[0], batch), mesh)
        rows = []
        for control, other in others.items():
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
    for seed in args.seed:
        for name, checks in one_seed(seed):
            correct = all(checks.values())
            refused_all &= correct == (name == "program")
            print(json.dumps({
                "control": name, "seed": seed, "correct": correct,
                "refused_by": [k for k, ok in checks.items() if not ok]}),
                flush=True)
    return 0 if refused_all or args.rehearse_cpu else 1


if __name__ == "__main__":
    sys.exit(main())
