"""The ``looped_lm`` kind through the harness in rehearsal, on one and on
four virtual devices, its controls through the harness's comparison, and
``loop_reduce`` on a hand-built HLO and event list."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import loop_reduce, run, scope_reduce


def _run(*args, devices=1, script="run.py"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, os.path.join(run.HERE, script), *args],
        env=env, capture_output=True, text=True, timeout=1500, cwd=run.ROOT)


@pytest.mark.parametrize("trace,devices", [("0", 1), ("1", 1), ("0", 4)])
def test_ouro_cell_rehearses_end_to_end(trace, devices):
    done = _run("--workload", "ouro26b_t4096", "--seed", "5100000001",
                "--seconds", "1", "--trace", trace, "--rehearse-cpu",
                devices=devices)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["count"] == devices
    assert "reference: float32 at precision highest, 4 passes" in done.stdout
    assert "exit probability p_t" in done.stdout
    for check in ("exit_gate_w", "ln_f_scale", "w_down_last",
                  "ln2_post_last", "wk_first"):
        assert f"check (b): {check}:" in done.stdout
    assert done.stdout.count("check (b):") == 5


def test_each_control_goes_through_the_harness_comparison():
    """At the rehearsal's sizes and tolerances the outcomes mean little;
    what holds anywhere: the program passes, and a model whose exit
    weights are uniform has no gradient for the gate."""
    done = _run("--workload", "ouro26b_t4096", "--seed", "5100000001",
                "--rehearse-cpu", script="controls_looped_lm.py")
    assert done.returncode == 0, done.stderr[-2000:]
    rows = [json.loads(l) for l in done.stdout.splitlines()
            if l.startswith('{"control"')]
    assert [r["control"] for r in rows] == [
        "three_passes", "cut_passes", "norm_at_readouts", "no_post_norms",
        "uniform_exit", "no_entropy", "last_unnormalised", "last_pass_only",
        "float8", "program"]
    for row in rows:
        if row["control"] == "program":
            assert row["correct"] is True and row["refused_by"] == []
        if row["control"] == "uniform_exit":
            assert "gradient_matches_reference:exit_gate_w" in row[
                "refused_by"]


HLO = """HloModule jit_hvd_lm_train_step

%fused_computation (p: bf16[8,8]) -> bf16[8,8] {
  %p = bf16[8,8] parameter(0)
  ROOT %dot.1 = bf16[8,8] dot(%p, %p), metadata={op_name="jit(s)/jvp(loops)/while/body/layer_0/attn/qkv/dot_general"}
}

%fused_computation.1 (p: bf16[8,8]) -> bf16[8,8] {
  %p.1 = bf16[8,8] parameter(0)
  ROOT %mul.1 = bf16[8,8] multiply(%p.1, %p.1), metadata={op_name="jit(s)/jvp(loops)/while/body/layer_0/attn/qkv/mul"}
}

ENTRY %main (a: bf16[8,8]) -> bf16[8,8] {
  %a = bf16[8,8] parameter(0)
  %fusion.1 = bf16[8,8] fusion(%a), kind=kOutput, calls=%fused_computation
  %fusion.2 = bf16[8,8] fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1
  %flash_fwd.3 = bf16[8,8] custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/jvp(loops)/while/body/layer_0/attn/flash_attention/flash_fwd/pallas_call"}
  %fusion.4 = bf16[8,8] multiply(%flash_fwd.3, %flash_fwd.3), metadata={op_name="jit(s)/jvp(loops)/while/body/layer_0/attn/out/post_norm/mul"}
  %fusion.5 = bf16[8,8] multiply(%fusion.4, %fusion.4), metadata={op_name="jit(s)/jvp(loops)/while/body/layer_0/mlp/mul"}
  %fusion.6 = bf16[8,8] multiply(%fusion.5, %fusion.5), metadata={op_name="jit(s)/jvp(loops)/while/body/loop_norm/mul"}
  %fusion.7 = bf16[8,8] add(%fusion.6, %fusion.6), metadata={op_name="jit(s)/transpose(jvp(loops))/while/body/add_any"}
  %fusion.8 = bf16[8,8] multiply(%fusion.7, %fusion.7), metadata={op_name="jit(s)/jvp(head)/dot_general"}
  %fusion.9 = bf16[8,8] multiply(%fusion.8, %fusion.8), metadata={op_name="jit(s)/jvp(head)/exit_gate/reduce_sum"}
  %fusion.10 = bf16[8,8] multiply(%fusion.9, %fusion.9), metadata={op_name="jit(s)/jvp(loss)/exit_mix/exp"}
  ROOT %fusion.11 = bf16[8,8] multiply(%fusion.10, %fusion.10), metadata={op_name="jit(s)/optimizer/mul"}
}
"""


def test_loop_reduce_books_every_op_to_one_part():
    hlo = scope_reduce.parse_hlo(HLO)
    want = {"fusion.1": "attn", "fusion.2": "qk_glue",
            "flash_fwd.3": "flash", "fusion.4": "norm", "fusion.5": "mlp",
            "fusion.6": "norm", "fusion.7": "carry", "fusion.8": "head",
            "fusion.9": "exit", "fusion.10": "exit", "fusion.11": "other"}
    assert {name: loop_reduce.part_of(name, hlo) for name in want} == want
    op_s = {f"%{name} fusion bf16[8,8]": 1.0 for name in want}
    op_s["%gone.1 fusion bf16[8,8]"] = 0.5
    parts = loop_reduce.attribute(op_s, hlo)
    assert parts == {"attn": 1.0, "qk_glue": 1.0, "flash": 1.0, "norm": 2.0,
                     "mlp": 1.0, "carry": 1.0, "head": 1.0, "exit": 2.0,
                     "other": 1.5}
    assert sum(parts.values()) == sum(op_s.values())
    assert set(parts) <= set(loop_reduce.PARTS)


def test_loop_reduce_finds_nothing_on_another_program():
    """The parent's program has no loop: every reader returns None."""
    assert loop_reduce.for_ctx({"reduced": {}}) is None
    assert loop_reduce.part_ms({"reduced": None}, ("flash",)) is None
