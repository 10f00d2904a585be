"""The ``mla_moe_lm`` kind through the harness in rehearsal, on one and on
four virtual devices, and ``mla_reduce`` on a hand-built HLO and event
list."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import mla_reduce, run, scope_reduce


def _run(*args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), *args],
        env=env, capture_output=True, text=True, timeout=1500, cwd=run.ROOT)


@pytest.mark.parametrize("trace,devices", [("0", 1), ("1", 1), ("0", 4)])
def test_glm_cell_rehearses_end_to_end(trace, devices):
    done = _run("--workload", "glm47flash_t8192", "--seed", "3700000001",
                "--seconds", "1", "--trace", trace, "--rehearse-cpu",
                devices=devices)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["count"] == devices
    lines = done.stdout.splitlines()
    held = [l for l in lines if l.startswith("held experts, first batch")]
    assert len(held) == 3 and all(
        l.endswith("dropped 0 by the bound") for l in held)
    assert "layer mtp_0" in held[-1]
    assert "for a buffer of 1024 = tokens x min(2, 4)" in held[0]
    assert "reference: float32 at precision highest" in done.stdout
    for check in ("ln_f_scale", "mtp_w_eh", "wo_last", "w_kvb_last",
                  "w_shared_down_last"):
        assert f"check (b): {check}:" in done.stdout
    assert done.stdout.count("check (b):") == 5


STEP = "jit(hvd_lm_train_step)"
HLO = f"""HloModule jit_hvd_lm_train_step, is_scheduled=true

%fused_computation.1 (p0: bf16[64,32], p1: bf16[32,8]) -> f32[64,8] {{
  %p0 = bf16[64,32]{{1,0}} parameter(0)
  %p1 = bf16[32,8]{{1,0}} parameter(1)
  ROOT %dot.1 = f32[64,8]{{1,0}} dot(%p0, %p1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{STEP}/transpose(jvp(layer_0))/attn/qkv/mla_q/dot_general"}}
}}

ENTRY %main (a: bf16[64,32], b: bf16[32,8], c: f32[8,8]) -> f32[8,8] {{
  %a = bf16[64,32]{{1,0}} parameter(0)
  %b = bf16[32,8]{{1,0}} parameter(1)
  %c = f32[8,8]{{1,0}} parameter(2)
  %fusion.1 = f32[64,8]{{1,0}} fusion(%a, %b), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{STEP}/optimizer/add"}}
  %mul.2 = f32[8,8]{{1,0}} multiply(%c, %c), metadata={{op_name="{STEP}/jvp(layer_0)/attn/qkv/mul"}}
  %dot.3 = f32[8,8]{{1,0}} dot(%mul.2, %mul.2), metadata={{op_name="{STEP}/jvp(layer_0)/attn/qkv/mla_kv/dot_general"}}
  %cos.4 = f32[8,8]{{1,0}} cosine(%dot.3), metadata={{op_name="{STEP}/jvp(layer_0)/checkpoint/rematted_computation/attn/qkv/mla_rope/cos"}}
  %flash_fwd.5 = f32[8,8]{{1,0}} custom-call(%cos.4), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(layer_0)/attn/flash_attention/flash_fwd/pallas_call"}}
  %dot.6 = f32[8,8]{{1,0}} dot(%flash_fwd.5, %flash_fwd.5), metadata={{op_name="{STEP}/jvp(layer_0)/attn/out/dot_general"}}
  %dot.7 = f32[8,8]{{1,0}} dot(%dot.6, %dot.6), metadata={{op_name="{STEP}/jvp(layer_0)/mlp/mlp_dense/dot_general"}}
  %dot.8 = f32[8,8]{{1,0}} dot(%dot.7, %dot.7), metadata={{op_name="{STEP}/jvp(layer_1)/mlp/moe_router/dot_general"}}
  %moe_gmm.300 = f32[8,8]{{1,0}} custom-call(%dot.8), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(layer_1)/mlp/moe_experts/moe_gmm/pallas_call"}}
  %dot.10 = f32[8,8]{{1,0}} dot(%moe_gmm.300, %moe_gmm.300), metadata={{op_name="{STEP}/jvp(layer_1)/mlp/moe_shared/dot_general"}}
  %add.11 = f32[8,8]{{1,0}} add(%dot.10, %dot.10), metadata={{op_name="{STEP}/jvp(layer_1)/mlp/add"}}
  %dot.12 = f32[8,8]{{1,0}} dot(%add.11, %add.11), metadata={{op_name="{STEP}/jvp()/head/dot_general"}}
  %dot.13 = f32[8,8]{{1,0}} dot(%dot.12, %dot.12), metadata={{op_name="{STEP}/jvp(mtp)/embed/dot_general"}}
  %dot.14 = f32[8,8]{{1,0}} dot(%dot.13, %dot.13), metadata={{op_name="{STEP}/jvp(mtp)/layer_0/attn/qkv/mla_q/dot_general"}}
  %sort.15 = f32[8,8]{{1,0}} sort(%dot.14), dimensions={{0}}, metadata={{op_name="{STEP}/transpose(jvp(mtp))/layer_0/mlp/moe_dispatch/sort"}}
  ROOT %dot.16 = f32[8,8]{{1,0}} dot(%sort.15, %sort.15), metadata={{op_name="{STEP}/jvp(mtp)/head/dot_general"}}
}}
"""

OP_S = {"%fusion.1 fusion f32[64,8]": 1.0,
        "%mul.2 multiply f32[8,8]": 2.0,
        "%dot.3 dot f32[8,8]": 4.0,
        "%cos.4 cosine f32[8,8]": 8.0,
        "%flash_fwd.5 custom-call f32[8,8]": 16.0,
        "%dot.6 dot f32[8,8]": 32.0,
        "%dot.7 dot f32[8,8]": 64.0,
        "%dot.8 dot f32[8,8]": 128.0,
        "%moe_gmm.300 custom-call f32[8,8]": 256.0,
        "%dot.10 dot f32[8,8]": 512.0,
        "%add.11 add f32[8,8]": 1024.0,
        "%dot.12 dot f32[8,8]": 2048.0,
        "%dot.13 dot f32[8,8]": 4096.0,
        "%dot.14 dot f32[8,8]": 8192.0,
        "%sort.15 sort f32[8,8]": 16384.0,
        "%dot.16 dot f32[8,8]": 32768.0,
        "%not-in-the-hlo fusion f32[1]": 0.125}


def test_mla_reduce_books_each_op_by_its_part():
    hlo = scope_reduce.parse_hlo(HLO)
    parts = mla_reduce.attribute(OP_S, hlo)
    # A fusion by the matmul inside it; the first norm, the kernel, the
    # out projection, an expert layer's residual add and the main head in
    # no part; the prediction module's ops in "mtp" and, where they have
    # one, in their part too.
    assert parts == {"mla_q": 1.0 + 8192.0, "mla_kv": 4.0, "mla_rope": 8.0,
                     "mlp_dense": 64.0, "moe_router": 128.0,
                     "moe_experts": 256.0, "moe_shared": 512.0,
                     "moe_dispatch": 16384.0,
                     "mtp": 4096.0 + 8192.0 + 16384.0 + 32768.0}
    table = scope_reduce.attribute(OP_S, hlo)["table"]
    by_scope = {}
    for (scope, _), seconds in table.items():
        by_scope[scope] = by_scope.get(scope, 0.0) + seconds
    # The benchmark's own table answers the model scopes: the module's
    # parts are booked with the main stack's, so the identity holds.
    assert by_scope["attn/qkv"] == 1.0 + 2.0 + 4.0 + 8.0 + 8192.0
    assert by_scope["attn/flash_attention"] == 16.0
    assert by_scope["attn/out"] == 32.0
    assert by_scope["mlp"] == 64.0 + 128.0 + 256.0 + 512.0 + 1024.0 + 16384.0
    assert by_scope["head"] == 2048.0 + 32768.0
    assert by_scope["embed"] == 4096.0
    assert table[("attn/qkv", "remat")] == 8.0


def test_the_adapter_matches_kernels_however_they_are_numbered():
    from perfbench.adapters import mla_moe_lm

    matches = mla_moe_lm.defined("moe_gmm", "flash_fwd")
    for text in ("%moe_gmm.300 = f32[8,8]{1,0} custom-call(%dot.8)",
                 "%moe_gmm = f32[8,8]{1,0} custom-call(%dot.8)",
                 "%flash_fwd.47 = (bf16[20,8192,256]{2,1,0}) custom-call("):
        assert any(m in text for m in matches), text
    # Not the instruction that reads a kernel's result, nor another
    # kernel whose name starts alike.
    for text in ("%dot.10 = f32[8,8]{1,0} dot(%moe_gmm.300, %moe_gmm.300)",
                 "%moe_gmm_nt.300 = f32[8,8]{1,0} custom-call(%dot.8)"):
        assert not any(m in text for m in matches), text


def test_mla_reduce_finds_nothing_in_another_program():
    other = HLO
    for part in mla_reduce.MLA_PARTS:
        other = other.replace("/" + part, "")
    found = mla_reduce.attribute(OP_S, scope_reduce.parse_hlo(other))
    assert not set(found).intersection(mla_reduce.MLA_PARTS)
    assert mla_reduce.part_ms({"reduced": {}}, mla_reduce.MLA_PARTS) is None
    assert mla_reduce.scope_ms({"reduced": {}}, ("mlp",)) is None
    assert mla_reduce.parts_of(f"{STEP}/jvp(layer_0)/mlp/dot_general") == []
    assert mla_reduce.parts_of(
        f"{STEP}/transpose(jvp(mtp))/layer_0/mlp/moe_shared/dot_general"
    ) == ["moe_shared", "mtp"]
    # A part is a whole component: a parameter named after one is not it.
    assert mla_reduce.parts_of(f"{STEP}/optimizer/my_mla_q_x/add") == []
    assert mla_reduce.parts_of(f"{STEP}/optimizer/mtp_w_eh/add") == []
