#!/usr/bin/env python3
"""The controls of a ``bd_moe_lm`` cell's check, each through the
harness's own comparison at the cell's sizes
(``controls_mamba1_lm.py``'s way).

    python3 perfbench/controls_bd_moe_lm.py --workload sdar30b_bd8k --seed <n> [--seed <m> ...]

A control is the plain reference computing another function (``CONTROLS``:
the seven that ``perfbench/reference/bd_moe_lm.py`` names: another mask,
other positions, another loss, a lower precision).  For every seed
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

One of the seven, ``noised_causal``, is not separated at ``sdar30b_bd8k``'s
size (it hides at most 3 of a query's thousands of keys and reads
0.0105-0.0191 against a limit of 0.012: the configuration's ``check.why``):
an exit code of 1 with that control alone accepted on a seed is the known
state, anything else accepted is a finding.
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
    # One causal sequence of 2 L positions, the clean one first.
    "causal_mask": dict(rule="causal"),
    # A noised query reads the clean copy of its own block (<= for <).
    "own_clean_block": dict(rule="own_clean_block"),
    # A noised block read causally instead of in both directions.
    "noised_causal": dict(rule="noised_causal"),
    # Positions 0 .. 2 L - 1 instead of 0 .. L - 1 twice.
    "running_positions": dict(running_positions=True),
    # The weights 1 / t left out.
    "unweighted": dict(weighted=False),
    # The autoregressive shift kept: position i predicts x0[i + 1].
    "shifted_labels": dict(shift=1),
    "float8": dict(low_precision="float8_e4m3fn"),
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
    from perfbench.reference import bd_moe_lm as reference

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
            reference.loss_and_tail_grads, dims=adapter.reference_dims(cfg),
            **kw))

    def stand_in(loss, grads):
        """What ``compiled`` would return had the step's gradients been
        ``grads`` and its loss ``loss``."""
        def step(params, opt_state, *batch):
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
        state outlives the call."""
        state, (batch,) = cell.make(seed, 1)
        compiled = cell.step.lower(*state, *batch).compile()
        # The one reference a seed's comparisons share, computed once.
        held = cell.reference(state, batch)
        shared = dataclasses.replace(cell, reference=lambda *_: held)
        params, data = on_first_device((state[0], batch), mesh)
        rows = []
        for control, other in others.items():
            start = time.perf_counter()
            loss, grads, _ = jax.block_until_ready(
                other(params, *data))
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
