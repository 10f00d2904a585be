#!/usr/bin/env python3
"""The controls of a ``cca_moe_lm`` cell's check, each through the
harness's own comparison at the cell's sizes.

    python3 perfbench/controls_cca_moe_lm.py --workload zaya1_8b_t16k --seed <n> [--seed <m> ...] [--program-seed <k> ...]

A control is the plain reference computing another function (``CONTROLS``:
the ones that ``perfbench/reference/cca_moe_lm.py`` names).  For every seed
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

``--control`` names the controls to run (all of them without it).
``--window <n>`` does something else with every seed: ``n`` steps of the
cell's own compiled step over its pool of batches, as a timed run takes
them, and before the first and after every fourth one the rows that the
held experts of every layer receive from the batch that comes next (the
float32 reference's routing of the weights as they stand then,
``reference.layer_loads``): what the grouped matmuls' time follows.  One JSON
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
    "no_mix": dict(mix=False),
    "no_mean": dict(mean=False),
    "no_value_shift": dict(value_shift=False),
    "no_l2_norm": dict(l2_norm=False),
    "rotary_whole": dict(rotary_whole=True),
    "cut_state": dict(cut_state=True),
    "gamma_zero": dict(carry=False),
    "skip_nothing": dict(skip_term=False),
    "no_skip_choice": dict(skip_choice=False),
    "bias_weighs": dict(bias_weighs=True),
    "unweighted": dict(weighted=False),
    "plain_add": dict(scaled_merge=False),
    "float8": dict(low_precision="float8_e4m3fn"),
    "router_bf16": dict(router_low_precision="bfloat16"),
}


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, action="append", default=[])
    parser.add_argument("--program-seed", type=int, action="append",
                        default=[])
    parser.add_argument("--control", action="append", choices=sorted(CONTROLS),
                        help="these controls alone (default: all of them)")
    parser.add_argument("--window", type=int, default=0,
                        help="steps over the pool, the held rows printed")
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
    from perfbench.reference import cca_moe_lm as reference

    _, entry, config, mix = run._cell_files(args.workload, args.rehearse_cpu)
    pool = mix["pool"]
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
        kw = {key: getattr(jnp, value) if key.endswith("low_precision")
              else value for key, value in CONTROLS[control].items()}
        return jax.jit(functools.partial(
            reference.loss_and_grads, dims=adapter.reference_dims(cfg),
            **kw))

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
                # (A leaf the adapter reads from the momentum slot.)
                trace = reference.with_leaf(
                    trace, path, jnp.asarray(grad).astype(
                        reference.leaf(trace, path).dtype))
            opt_state = tuple(
                s._replace(trace=trace) if i == momentum else s
                for i, s in enumerate(opt_state))
            return params, opt_state, loss
        return step

    compiled = cell.step.lower(*cell.state_shapes,
                               *cell.batch_shapes).compile()
    if args.window:
        return held_rows_of_a_window(args, cell, compiled, mesh, pool,
                                     adapter.reference_dims(cfg))
    others = {name: other_reference(name)
              for name in args.control or CONTROLS}

    def one_seed(seed, controls):
        """``[(control or "program", checks)]``; nothing of the seed's
        gigabytes of state outlives the call."""
        state, (batch,) = cell.make(seed, 1)
        # The one reference a seed's comparisons share, computed once.
        held = cell.reference(state, batch)
        shared = dataclasses.replace(cell, reference=lambda *_: held)
        params, (tokens, labels) = on_first_device((state[0], batch), mesh)
        params = adapter.for_reference(params, cfg)
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


def held_rows_of_a_window(args, cell, compiled, mesh, pool, dims) -> int:
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from perfbench.cell import on_first_device
    from perfbench.reference import cca_moe_lm as reference

    one_layer = jax.jit(functools.partial(reference.layer_loads, dims=dims))

    def held_rows(params, tokens):
        """``(rows [L, held], skips [L])`` of one sequence."""
        params, tokens = on_first_device((params, tokens), mesh)
        x = params["embed"][tokens].astype(jnp.float32)
        state = jnp.zeros(
            (x.shape[0], params["layers"][0]["router_down"].shape[1]),
            jnp.float32)
        rows, skips = [], []
        for layer in params["layers"]:
            x, state, held, skipped = one_layer(layer, x, state)
            rows.append(np.asarray(held))
            skips.append(int(skipped))
        return np.stack(rows), np.asarray(skips)

    for seed in args.seed + args.program_seed:
        state, batches = cell.make(seed, pool)
        for done in range(args.window + 1):
            batch = batches[done % pool]
            if done % 4 == 0:
                # The first sequence of the batch the next step takes.
                rows, skips = held_rows(state[0], batch[0][0])
                per_layer = rows.sum(axis=1)
                print(json.dumps({
                    "seed": seed, "steps_done": done,
                    "batch": done % pool, "held_rows": int(rows.sum()),
                    "a_layer_min": int(per_layer.min()),
                    "a_layer_max": int(per_layer.max()),
                    "an_expert_min": int(rows.min()),
                    "an_expert_max": int(rows.max()),
                    "skips": int(skips.sum())}), flush=True)
            if done < args.window:
                *state, loss = compiled(*state, *batch)
                print(f"seed {seed}: step {done + 1}, loss "
                      f"{float(loss):.6f}", flush=True)
        del state, batches
    return 0


if __name__ == "__main__":
    sys.exit(main())
