"""The ``mamba1_lm`` kind through the harness in rehearsal, on one and on
four virtual devices, its controls through the harness's comparison, and
``mamba1_reduce`` on a hand-built HLO and event list."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import mamba1_reduce, run, scope_reduce


def _run(*args, devices=1, script="run.py"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, os.path.join(run.HERE, script), *args],
        env=env, capture_output=True, text=True, timeout=1500, cwd=run.ROOT)


@pytest.mark.parametrize("trace,devices", [("0", 1), ("1", 1), ("0", 4)])
def test_jamba_cell_rehearses_end_to_end(trace, devices):
    done = _run("--workload", "jamba2_t16k", "--seed", "4300000001",
                "--seconds", "1", "--trace", trace, "--rehearse-cpu",
                devices=devices)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["count"] == devices
    assert "reference: float32 at precision highest" in done.stdout
    for check in ("ln_f_scale", "w_down_last", "mamba_w_out_last",
                  "mamba_a_log_last", "mamba_d_last", "mamba_w_dt_last",
                  "wk_attn"):
        assert f"check (b): {check}:" in done.stdout
    assert done.stdout.count("check (b):") == 7


def test_each_control_goes_through_the_harness_comparison():
    """At the rehearsal's sizes and tolerances the outcomes mean little
    (the state is zeroed every 256 tokens of 256); what holds anywhere:
    the program passes, and a model without ``D`` has no gradient for
    it."""
    done = _run("--workload", "jamba2_t16k", "--seed", "4300000001",
                "--seed", "4300000002", "--rehearse-cpu",
                script="controls_mamba1_lm.py")
    assert done.returncode == 0, done.stderr[-2000:]
    rows = [json.loads(l) for l in done.stdout.splitlines()
            if l.startswith('{"control"')]
    assert [r["control"] for r in rows] == 2 * [
        "float8", "state_reset", "one_decay", "no_inner_norms", "no_skip",
        "independent_kv", "program"]
    assert [r["seed"] for r in rows] == 7 * [4300000001] + 7 * [4300000002]
    for row in rows:
        if row["control"] == "program":
            assert row["correct"] is True and row["refused_by"] == []
        if row["control"] == "no_skip":
            assert "gradient_matches_reference:mamba_d_last" in row[
                "refused_by"]
    # Each comparison is the harness's own: 7 leaves, 7 times a seed.
    assert done.stdout.count("check (b):") == 2 * 7 * 7


STEP = "jit(hvd_lm_train_step)"
HLO = f"""HloModule jit_hvd_lm_train_step, is_scheduled=true

%fused_computation.1 (p0: bf16[64,32], p1: bf16[32,8]) -> f32[64,8] {{
  %p0 = bf16[64,32]{{1,0}} parameter(0)
  %p1 = bf16[32,8]{{1,0}} parameter(1)
  ROOT %dot.1 = f32[64,8]{{1,0}} dot(%p0, %p1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{STEP}/transpose(jvp(layer_0))/attn/qkv/mamba_proj/dot_general"}}
}}

ENTRY %main (a: bf16[64,32], b: bf16[32,8], c: f32[8,8]) -> f32[8,8] {{
  %a = bf16[64,32]{{1,0}} parameter(0)
  %b = bf16[32,8]{{1,0}} parameter(1)
  %c = f32[8,8]{{1,0}} parameter(2)
  %fusion.1 = f32[64,8]{{1,0}} fusion(%a, %b), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{STEP}/optimizer/add"}}
  %short_conv_fwd.3 = f32[64,8]{{1,0}} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(layer_0)/attn/qkv/mamba_conv/short_conv_fwd/pallas_call"}}
  %dot.4 = f32[8,8]{{1,0}} dot(%c, %c), metadata={{op_name="{STEP}/jvp(layer_0)/attn/qkv/mamba_dt_bc/dot_general"}}
  %copy.5 = f32[8,8]{{1,0}} copy(%dot.4)
  %mamba_scan_fwd.6 = f32[8,8]{{1,0}} custom-call(%copy.5), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(layer_0)/checkpoint/rematted_computation/attn/mamba_scan/mamba_scan_fwd/pallas_call"}}
  %mamba_scan_bwd.7 = f32[8,8]{{1,0}} custom-call(%mamba_scan_fwd.6), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/transpose(jvp(layer_0))/attn/mamba_scan/mamba_scan_bwd/pallas_call"}}
  %mul.8 = f32[8,8]{{1,0}} multiply(%mamba_scan_bwd.7, %mamba_scan_bwd.7), metadata={{op_name="{STEP}/jvp(layer_0)/attn/out/mamba_gate/mul"}}
  %dot.9 = f32[8,8]{{1,0}} dot(%mul.8, %mul.8), metadata={{op_name="{STEP}/jvp(layer_0)/attn/out/mamba_out/dot_general"}}
  %dot.10 = f32[8,8]{{1,0}} dot(%dot.9, %dot.9), metadata={{op_name="{STEP}/jvp(layer_0)/mlp/dot_general"}}
  %flash_fwd.11 = f32[8,8]{{1,0}} custom-call(%dot.10), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(layer_7)/attn/flash_attention/flash_fwd/pallas_call"}}
  %dot.12 = f32[8,8]{{1,0}} dot(%flash_fwd.11, %flash_fwd.11), metadata={{op_name="{STEP}/jvp(layer_7)/attn/out/dot_general"}}
  ROOT %dot.13 = f32[8,8]{{1,0}} dot(%dot.12, %dot.12), metadata={{op_name="{STEP}/jvp()/head/dot_general"}}
}}
"""

OP_S = {"%fusion.1 fusion f32[64,8]": 1.0,
        "%short_conv_fwd.3 custom-call f32[64,8]": 2.0,
        "%dot.4 dot f32[8,8]": 4.0,
        "%copy.5 copy f32[8,8]": 0.5,
        "%mamba_scan_fwd.6 custom-call f32[8,8]": 8.0,
        "%mamba_scan_bwd.7 custom-call f32[8,8]": 16.0,
        "%mul.8 multiply f32[8,8]": 32.0,
        "%dot.9 dot f32[8,8]": 64.0,
        "%dot.10 dot f32[8,8]": 128.0,
        "%flash_fwd.11 custom-call f32[8,8]": 256.0,
        "%dot.12 dot f32[8,8]": 512.0,
        "%dot.13 dot f32[8,8]": 1024.0,
        "%not-in-the-hlo fusion f32[1]": 0.125}


def test_mamba1_reduce_books_each_op_by_its_part():
    hlo = scope_reduce.parse_hlo(HLO)
    parts = mamba1_reduce.attribute(OP_S, hlo)
    # A fusion by the matmul inside it; a copy where its result is needed
    # (the scan's relayout); the kernels by the part they run under; the
    # MLP, the attention layer and the head in no part.
    assert parts == {"mamba_proj": 1.0, "mamba_conv": 2.0,
                     "mamba_dt_bc": 4.0, "mamba_scan": 0.5 + 8.0 + 16.0,
                     "mamba_gate": 32.0, "mamba_out": 64.0}
    assert mamba1_reduce.part_of(
        f"{STEP}/jvp(layer_7)/attn/out/dot_general") is None
    table = scope_reduce.attribute(OP_S, hlo)["table"]
    by_scope = {}
    for (scope, _), seconds in table.items():
        by_scope[scope] = by_scope.get(scope, 0.0) + seconds
    # The benchmark's own table answers the model scopes.
    assert by_scope["attn/qkv"] == 1.0 + 2.0 + 4.0
    assert by_scope["attn/out"] == 32.0 + 64.0 + 512.0
    assert by_scope["layer"] == 0.5 + 8.0 + 16.0
    assert by_scope["mlp"] == 128.0
    assert by_scope["attn/flash_attention"] == 256.0
    assert by_scope["head"] == 1024.0
