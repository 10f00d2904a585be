"""The ``hybrid_lm`` kind through the harness in rehearsal, and
``gdn_reduce`` on a hand-built HLO and event list."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import gdn_reduce, run, scope_reduce


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    return subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), *args],
        env=env, capture_output=True, text=True, timeout=1500, cwd=run.ROOT)


@pytest.mark.parametrize("trace", ("0", "1"))
def test_olmohybrid_cell_rehearses_end_to_end(trace):
    done = _run("--workload", "olmohybrid_t16k", "--seed", "3100000001",
                "--seconds", "1", "--trace", trace, "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["correct"] is True and last["failed"] == 0
    gates = [l for l in done.stdout.splitlines()
             if l.startswith("gates, first batch, layer")]
    assert len(gates) == 3
    assert "reference: float32 at precision highest" in done.stdout
    for check in ("ln_f_scale", "w_down_last", "lin_wo_last",
                  "lin_wa_last"):
        assert f"check (b): {check}:" in done.stdout


STEP = "jit(hvd_lm_train_step)"
HLO = f"""HloModule jit_hvd_lm_train_step, is_scheduled=true

%fused_computation.1 (p0: bf16[64,32], p1: bf16[32,8]) -> f32[64,8] {{
  %p0 = bf16[64,32]{{1,0}} parameter(0)
  %p1 = bf16[32,8]{{1,0}} parameter(1)
  ROOT %dot.1 = f32[64,8]{{1,0}} dot(%p0, %p1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{STEP}/transpose(jvp(layer_0))/attn/qkv/gdn_proj/dot_general"}}
}}

%body.2 (s: f32[8,8]) -> f32[8,8] {{
  %s = f32[8,8]{{1,0}} parameter(0)
  ROOT %dot.2 = f32[8,8]{{1,0}} dot(%s, %s), metadata={{op_name="{STEP}/jvp(layer_0)/checkpoint/rematted_computation/attn/gdn_scan/while/body/dot_general"}}
}}

ENTRY %main (a: bf16[64,32], b: bf16[32,8], c: f32[8,8]) -> f32[8,8] {{
  %a = bf16[64,32]{{1,0}} parameter(0)
  %b = bf16[32,8]{{1,0}} parameter(1)
  %c = f32[8,8]{{1,0}} parameter(2)
  %fusion.1 = f32[64,8]{{1,0}} fusion(%a, %b), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{STEP}/optimizer/add"}}
  %mul.3 = f32[64,8]{{1,0}} multiply(%fusion.1, %fusion.1), metadata={{op_name="{STEP}/jvp(layer_0)/attn/qkv/gdn_conv/mul"}}
  %copy.4 = f32[8,8]{{1,0}} copy(%c)
  %while.5 = f32[8,8]{{1,0}} while(%copy.4), condition=%body.2, body=%body.2, metadata={{op_name="{STEP}/jvp(layer_0)/attn/gdn_scan/while"}}
  %mul.6 = f32[8,8]{{1,0}} multiply(%while.5, %while.5), metadata={{op_name="{STEP}/jvp(layer_1)/attn/out/gdn_gate_norm/mul"}}
  %dot.7 = f32[8,8]{{1,0}} dot(%mul.6, %mul.6), metadata={{op_name="{STEP}/jvp(layer_1)/attn/out/gdn_out/dot_general"}}
  %dot.8 = f32[8,8]{{1,0}} dot(%dot.7, %dot.7), metadata={{op_name="{STEP}/jvp(layer_3)/attn/qkv/dot_general"}}
  %flash_fwd.9 = f32[8,8]{{1,0}} custom-call(%dot.8), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(layer_3)/attn/flash_attention/flash_fwd/pallas_call"}}
  ROOT %dot.10 = f32[8,8]{{1,0}} dot(%flash_fwd.9, %flash_fwd.9), metadata={{op_name="{STEP}/jvp()/head/dot_general"}}
}}
"""

OP_S = {"%fusion.1 fusion f32[64,8]": 1.0,
        "%mul.3 multiply f32[64,8]": 2.0,
        "%copy.4 copy f32[8,8]": 0.5,
        "%while.5 while f32[8,8]": 0.25,
        "%dot.2 dot f32[8,8]": 8.0,
        "%mul.6 multiply f32[8,8]": 3.0,
        "%dot.7 dot f32[8,8]": 4.0,
        "%dot.8 dot f32[8,8]": 16.0,
        "%flash_fwd.9 custom-call f32[8,8]": 32.0,
        "%dot.10 dot f32[8,8]": 64.0,
        "%not-in-the-hlo fusion f32[1]": 128.0}


def test_gdn_reduce_books_each_op_by_the_mixers_part():
    hlo = scope_reduce.parse_hlo(HLO)
    parts = gdn_reduce.attribute(OP_S, hlo)
    # A fusion by the matmul inside it; a copy where its result is needed
    # (the loop); the loop's body and its container alike; a full layer's
    # projections, its kernel and the head nowhere.
    assert parts == {"gdn_proj": 1.0, "gdn_conv": 2.0,
                     "gdn_scan": 0.5 + 0.25 + 8.0, "gdn_gate_norm": 3.0,
                     "gdn_out": 4.0}
    table = scope_reduce.attribute(OP_S, hlo)["table"]
    by_scope = {}
    for (scope, _), seconds in table.items():
        by_scope[scope] = by_scope.get(scope, 0.0) + seconds
    # The benchmark's own table: the parts under attn/qkv and attn/out
    # answer those, the recurrence "layer"; every phase counts.
    assert by_scope["attn/qkv"] == 1.0 + 2.0 + 16.0
    assert by_scope["attn/out"] == 3.0 + 4.0
    assert by_scope["layer"] == 0.5 + 0.25 + 8.0
    assert table[("layer", "remat")] == 8.0
    assert by_scope["attn/flash_attention"] == 32.0


def test_gdn_reduce_finds_nothing_in_another_program():
    other = HLO
    for part in gdn_reduce.PARTS:
        other = other.replace("/" + part, "")
    assert gdn_reduce.attribute(OP_S, scope_reduce.parse_hlo(other)) == {}
    assert gdn_reduce.part_ms({"reduced": {}}) is None
    assert gdn_reduce.part_of(f"{STEP}/jvp(layer_0)/mlp/dot_general") is None
    assert gdn_reduce.part_of(
        f"{STEP}/transpose(jvp(layer_2))/attn/gdn_scan/while/body/mul"
    ) == "gdn_scan"
    # A part is a whole component: a parameter named after one is not it.
    assert gdn_reduce.part_of(f"{STEP}/optimizer/my_gdn_scan_x/add") is None
