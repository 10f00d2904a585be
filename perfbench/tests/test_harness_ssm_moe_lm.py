"""The ``ssm_moe_lm`` kind through the harness in rehearsal, on one and on
four virtual devices, and ``ssm_reduce`` on a hand-built HLO and event
list."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import run, scope_reduce, ssm_reduce


def _run(*args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), *args],
        env=env, capture_output=True, text=True, timeout=1500, cwd=run.ROOT)


@pytest.mark.parametrize("trace,devices", [("0", 1), ("1", 1), ("0", 4)])
def test_nemotron_cell_rehearses_end_to_end(trace, devices):
    done = _run("--workload", "nemotron3s_t8192", "--seed", "3300000001",
                "--seconds", "1", "--trace", trace, "--rehearse-cpu",
                devices=devices)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["count"] == devices
    lines = done.stdout.splitlines()
    decay = [l for l in lines if l.startswith("decay, first batch, layer")]
    held = [l for l in lines if l.startswith("held experts, first batch")]
    assert len(decay) == 5
    assert len(held) == 6 and all(
        l.endswith("dropped 0 by the bound") for l in held)
    assert "layer mtp_1" in held[-1]
    assert "rows for a buffer of 2048 = tokens x min(6, 4)" in held[0]
    assert "reference: float32 at precision highest" in done.stdout
    for check in ("ln_f_scale", "mtp_w_eh", "ssm_w_out_last",
                  "ssm_a_log_last", "w_shared_down_last"):
        assert f"check (b): {check}:" in done.stdout
    assert done.stdout.count("check (b):") == 5


STEP = "jit(hvd_lm_train_step)"
HLO = f"""HloModule jit_hvd_lm_train_step, is_scheduled=true

%fused_computation.1 (p0: bf16[64,32], p1: bf16[32,8]) -> f32[64,8] {{
  %p0 = bf16[64,32]{{1,0}} parameter(0)
  %p1 = bf16[32,8]{{1,0}} parameter(1)
  ROOT %dot.1 = f32[64,8]{{1,0}} dot(%p0, %p1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{STEP}/transpose(jvp(layer_0))/attn/qkv/ssm_proj/dot_general"}}
}}

%body.2 (s: f32[8,8]) -> f32[8,8] {{
  %s = f32[8,8]{{1,0}} parameter(0)
  ROOT %add.2 = f32[8,8]{{1,0}} add(%s, %s), metadata={{op_name="{STEP}/jvp(layer_0)/checkpoint/rematted_computation/attn/ssm_scan/while/body/add"}}
}}

ENTRY %main (a: bf16[64,32], b: bf16[32,8], c: f32[8,8]) -> f32[8,8] {{
  %a = bf16[64,32]{{1,0}} parameter(0)
  %b = bf16[32,8]{{1,0}} parameter(1)
  %c = f32[8,8]{{1,0}} parameter(2)
  %fusion.1 = f32[64,8]{{1,0}} fusion(%a, %b), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{STEP}/optimizer/add"}}
  %mul.3 = f32[64,8]{{1,0}} multiply(%fusion.1, %fusion.1), metadata={{op_name="{STEP}/jvp(layer_0)/attn/qkv/ssm_conv/mul"}}
  %copy.4 = f32[8,8]{{1,0}} copy(%c)
  %while.5 = f32[8,8]{{1,0}} while(%copy.4), condition=%body.2, body=%body.2, metadata={{op_name="{STEP}/jvp(layer_0)/attn/ssm_scan/while"}}
  %mul.6 = f32[8,8]{{1,0}} multiply(%while.5, %while.5), metadata={{op_name="{STEP}/jvp(layer_0)/attn/out/ssm_gate_norm/mul"}}
  %dot.7 = f32[8,8]{{1,0}} dot(%mul.6, %mul.6), metadata={{op_name="{STEP}/jvp(layer_0)/attn/out/ssm_out/dot_general"}}
  %dot.8 = f32[8,8]{{1,0}} dot(%dot.7, %dot.7), metadata={{op_name="{STEP}/jvp(layer_1)/mlp/moe_latent/dot_general"}}
  %moe_gmm.9 = f32[8,8]{{1,0}} custom-call(%dot.8), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(layer_1)/mlp/moe_experts/moe_gmm/pallas_call"}}
  %dot.10 = f32[8,8]{{1,0}} dot(%moe_gmm.9, %moe_gmm.9), metadata={{op_name="{STEP}/jvp(layer_1)/mlp/moe_shared/dot_general"}}
  %add.11 = f32[8,8]{{1,0}} add(%dot.10, %dot.10), metadata={{op_name="{STEP}/jvp(layer_1)/mlp/add"}}
  %flash_fwd.12 = f32[8,8]{{1,0}} custom-call(%add.11), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(layer_7)/attn/flash_attention/flash_fwd/pallas_call"}}
  %dot.13 = f32[8,8]{{1,0}} dot(%flash_fwd.12, %flash_fwd.12), metadata={{op_name="{STEP}/jvp()/head/dot_general"}}
  %dot.14 = f32[8,8]{{1,0}} dot(%dot.13, %dot.13), metadata={{op_name="{STEP}/jvp(mtp)/embed/dot_general"}}
  %flash_fwd.15 = f32[8,8]{{1,0}} custom-call(%dot.14), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(mtp)/layer_0/attn/flash_attention/flash_fwd/pallas_call"}}
  %sort.16 = f32[8,8]{{1,0}} sort(%flash_fwd.15), dimensions={{0}}, metadata={{op_name="{STEP}/transpose(jvp(mtp))/layer_1/mlp/moe_dispatch/sort"}}
  ROOT %dot.17 = f32[8,8]{{1,0}} dot(%sort.16, %sort.16), metadata={{op_name="{STEP}/jvp(mtp)/head/dot_general"}}
}}
"""

OP_S = {"%fusion.1 fusion f32[64,8]": 1.0,
        "%mul.3 multiply f32[64,8]": 2.0,
        "%copy.4 copy f32[8,8]": 0.5,
        "%while.5 while f32[8,8]": 0.25,
        "%add.2 add f32[8,8]": 8.0,
        "%mul.6 multiply f32[8,8]": 3.0,
        "%dot.7 dot f32[8,8]": 4.0,
        "%dot.8 dot f32[8,8]": 16.0,
        "%moe_gmm.9 custom-call f32[8,8]": 32.0,
        "%dot.10 dot f32[8,8]": 64.0,
        "%add.11 add f32[8,8]": 128.0,
        "%flash_fwd.12 custom-call f32[8,8]": 256.0,
        "%dot.13 dot f32[8,8]": 512.0,
        "%dot.14 dot f32[8,8]": 1024.0,
        "%flash_fwd.15 custom-call f32[8,8]": 2048.0,
        "%sort.16 sort f32[8,8]": 4096.0,
        "%dot.17 dot f32[8,8]": 8192.0,
        "%not-in-the-hlo fusion f32[1]": 0.125}


def test_ssm_reduce_books_each_op_by_its_part():
    hlo = scope_reduce.parse_hlo(HLO)
    parts = ssm_reduce.attribute(OP_S, hlo)
    # A fusion by the matmul inside it; a copy where its result is needed
    # (the loop); the loop's body and its container alike; the residual
    # add of an expert layer, a kernel of the main stack's attention and
    # the main head in no part; the prediction module's ops in "mtp" and,
    # where they have one, in their part too.
    assert parts == {"ssm_proj": 1.0, "ssm_conv": 2.0,
                     "ssm_scan": 0.5 + 0.25 + 8.0, "ssm_gate_norm": 3.0,
                     "ssm_out": 4.0, "moe_latent": 16.0,
                     "moe_experts": 32.0, "moe_shared": 64.0,
                     "moe_dispatch": 4096.0,
                     "mtp": 1024.0 + 2048.0 + 4096.0 + 8192.0}
    table = scope_reduce.attribute(OP_S, hlo)["table"]
    by_scope = {}
    for (scope, _), seconds in table.items():
        by_scope[scope] = by_scope.get(scope, 0.0) + seconds
    # The benchmark's own table answers the model scopes: the module's
    # parts are booked with the main stack's, so the identity holds.
    assert by_scope["attn/qkv"] == 1.0 + 2.0
    assert by_scope["attn/out"] == 3.0 + 4.0
    assert by_scope["layer"] == 0.5 + 0.25 + 8.0
    assert by_scope["mlp"] == 16.0 + 32.0 + 64.0 + 128.0 + 4096.0
    assert by_scope["attn/flash_attention"] == 256.0 + 2048.0
    assert by_scope["head"] == 512.0 + 8192.0
    assert by_scope["embed"] == 1024.0
    assert table[("layer", "remat")] == 8.0
    assert table[("mlp", "bwd")] == 4096.0


def test_ssm_reduce_finds_nothing_in_another_program():
    other = HLO.replace("(mtp)", "()")
    for part in ssm_reduce.SSM_PARTS + ssm_reduce.SHARED_PARTS:
        other = other.replace("/" + part, "")
    found = ssm_reduce.attribute(OP_S, scope_reduce.parse_hlo(other))
    # PR 26's four parts alone are another model's (olmoe_t4096's).
    assert set(found) <= set(ssm_reduce.ROUTED_PARTS)
    assert ssm_reduce.part_ms({"reduced": {}}, ssm_reduce.SSM_PARTS) is None
    assert ssm_reduce.parts_of(f"{STEP}/jvp(layer_0)/mlp/dot_general") == []
    assert ssm_reduce.parts_of(
        f"{STEP}/transpose(jvp(layer_2))/attn/ssm_scan/while/body/mul"
    ) == ["ssm_scan"]
    assert ssm_reduce.parts_of(
        f"{STEP}/transpose(jvp(mtp))/layer_1/mlp/moe_shared/dot_general"
    ) == ["moe_shared", "mtp"]
    # A part is a whole component: a parameter named after one is not it.
    assert ssm_reduce.parts_of(f"{STEP}/optimizer/my_ssm_scan_x/add") == []
    assert ssm_reduce.parts_of(f"{STEP}/optimizer/mtp_w_eh/add") == []
