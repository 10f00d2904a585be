"""The ``dsa_moe_lm`` kind through the harness in rehearsal, on one and on
four virtual devices, and ``dsa_reduce`` on a hand-built HLO and event
list."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import dsa_reduce, run, scope_reduce


def _run(*args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), *args],
        env=env, capture_output=True, text=True, timeout=1500, cwd=run.ROOT)


@pytest.mark.parametrize("trace,devices", [("0", 1), ("1", 1), ("0", 4)])
def test_keye_cell_rehearses_end_to_end(trace, devices):
    done = _run("--workload", "keyevl2_t16k", "--seed", "3900000001",
                "--seconds", "1", "--trace", trace, "--rehearse-cpu",
                devices=devices)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["count"] == devices
    lines = done.stdout.splitlines()
    held = [l for l in lines if l.startswith("held experts, first batch")]
    assert len(held) == 2 and all(
        l.endswith("dropped 0 by the bound") for l in held)
    assert "for a buffer of 1024 = tokens x min(2, 4)" in held[0]
    assert "reference: float32 at precision highest" in done.stdout
    chosen = next(l for l in lines if l.startswith("selection, first layer"))
    # 256 queries of at most 64 keys: 64 x 65 / 2 + 192 x 64.
    assert "keeps 14368 keys" in chosen and "is 14368 (equal)" in chosen
    for check in ("ln_f_scale", "wo_last", "wk_last", "index_wq_last"):
        assert f"check (b): {check}:" in done.stdout
    assert done.stdout.count("check (b):") == 4


STEP = "jit(hvd_lm_train_step)"
FLASH = "attn/flash_attention"
HLO = f"""HloModule jit_hvd_lm_train_step, is_scheduled=true

%fused_computation.1 (p0: bf16[64,32], p1: bf16[32,8]) -> f32[64,8] {{
  %p0 = bf16[64,32]{{1,0}} parameter(0)
  %p1 = bf16[32,8]{{1,0}} parameter(1)
  ROOT %dot.1 = f32[64,8]{{1,0}} dot(%p0, %p1), lhs_contracting_dims={{1}}, rhs_contracting_dims={{0}}, metadata={{op_name="{STEP}/transpose(jvp(layer_0))/attn/qkv/dsa_index_proj/dot_general"}}
}}

ENTRY %main (a: bf16[64,32], b: bf16[32,8], c: f32[8,8]) -> f32[8,8] {{
  %a = bf16[64,32]{{1,0}} parameter(0)
  %b = bf16[32,8]{{1,0}} parameter(1)
  %c = f32[8,8]{{1,0}} parameter(2)
  %fusion.1 = f32[64,8]{{1,0}} fusion(%a, %b), kind=kOutput, calls=%fused_computation.1, metadata={{op_name="{STEP}/optimizer/add"}}
  %dot.2 = f32[8,8]{{1,0}} dot(%c, %c), metadata={{op_name="{STEP}/jvp(layer_0)/attn/qkv/dot_general"}}
  %cos.3 = f32[8,8]{{1,0}} cosine(%dot.2), metadata={{op_name="{STEP}/jvp(layer_0)/attn/qkv/qk_head_norm_rope/cos"}}
  %dsa_index_fwd.4 = f32[8,8]{{1,0}} custom-call(%cos.3), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(layer_0)/{FLASH}/dsa_index_scores/dsa_index_fwd/pallas_call"}}
  %dsa_select_rows.5 = s8[8,8]{{1,0}} custom-call(%dsa_index_fwd.4), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(layer_0)/{FLASH}/dsa_select/dsa_select_rows/pallas_call"}}
  %transpose.6 = s8[8,8]{{0,1}} transpose(%dsa_select_rows.5), dimensions={{1,0}}, metadata={{op_name="{STEP}/jvp(layer_0)/checkpoint/rematted_computation/{FLASH}/dsa_select/transpose"}}
  %dsa_fwd.7 = f32[8,8]{{1,0}} custom-call(%cos.3), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(layer_0)/{FLASH}/dsa_flash/dsa_fwd/pallas_call"}}
  %dsa_bwd_dkv.8 = f32[8,8]{{1,0}} custom-call(%dsa_fwd.7), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/transpose(jvp(layer_0))/{FLASH}/dsa_flash/dsa_bwd_dkv/pallas_call"}}
  %dsa_probs.9 = f32[8,8]{{1,0}} custom-call(%dsa_fwd.7), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(layer_0)/{FLASH}/dsa_index_loss/dsa_probs/pallas_call"}}
  %reduce.10 = f32[8]{{0}} reduce(%dsa_probs.9, %c), dimensions={{1}}, metadata={{op_name="{STEP}/jvp(layer_0)/{FLASH}/dsa_index_loss/reduce_sum"}}
  %dot.11 = f32[8,8]{{1,0}} dot(%dsa_fwd.7, %dsa_fwd.7), metadata={{op_name="{STEP}/jvp(layer_0)/attn/out/dot_general"}}
  %dot.12 = f32[8,8]{{1,0}} dot(%dot.11, %dot.11), metadata={{op_name="{STEP}/jvp(layer_1)/mlp/moe_router/dot_general"}}
  %moe_gmm.300 = f32[8,8]{{1,0}} custom-call(%dot.12), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(layer_1)/mlp/moe_experts/moe_gmm/pallas_call"}}
  %add.14 = f32[8,8]{{1,0}} add(%moe_gmm.300, %moe_gmm.300), metadata={{op_name="{STEP}/jvp(layer_1)/mlp/add"}}
  ROOT %dot.15 = f32[8,8]{{1,0}} dot(%add.14, %add.14), metadata={{op_name="{STEP}/jvp()/head/dot_general"}}
}}
"""

OP_S = {"%fusion.1 fusion f32[64,8]": 1.0,
        "%dot.2 dot f32[8,8]": 2.0,
        "%cos.3 cosine f32[8,8]": 4.0,
        "%dsa_index_fwd.4 custom-call f32[8,8]": 8.0,
        "%dsa_select_rows.5 custom-call s8[8,8]": 16.0,
        "%transpose.6 transpose s8[8,8]": 32.0,
        "%dsa_fwd.7 custom-call f32[8,8]": 64.0,
        "%dsa_bwd_dkv.8 custom-call f32[8,8]": 128.0,
        "%dsa_probs.9 custom-call f32[8,8]": 256.0,
        "%reduce.10 reduce f32[8]": 512.0,
        "%dot.11 dot f32[8,8]": 1024.0,
        "%dot.12 dot f32[8,8]": 2048.0,
        "%moe_gmm.300 custom-call f32[8,8]": 4096.0,
        "%add.14 add f32[8,8]": 8192.0,
        "%dot.15 dot f32[8,8]": 16384.0,
        "%not-in-the-hlo fusion f32[1]": 0.125}


def test_dsa_reduce_books_each_op_by_its_part():
    hlo = scope_reduce.parse_hlo(HLO)
    parts = dsa_reduce.attribute(OP_S, hlo)
    # A fusion by the matmul inside it; q, k and v's projection, the out
    # projection, an expert layer's residual add and the head in no part.
    assert parts == {"dsa_index_proj": 1.0, "qk_head_norm_rope": 4.0,
                     "dsa_index_scores": 8.0, "dsa_select": 16.0 + 32.0,
                     "dsa_flash": 64.0 + 128.0,
                     "dsa_index_loss": 256.0 + 512.0, "moe_router": 2048.0,
                     "moe_experts": 4096.0}
    table = scope_reduce.attribute(OP_S, hlo)["table"]
    by_scope = {}
    for (scope, _), seconds in table.items():
        by_scope[scope] = by_scope.get(scope, 0.0) + seconds
    # The benchmark's own table answers the model scopes: the whole route
    # is booked as the flash route, so the identity holds and nothing is
    # left unattributed.
    assert by_scope["attn/qkv"] == 1.0 + 2.0 + 4.0
    assert by_scope[FLASH] == (8.0 + 16.0 + 32.0 + 64.0 + 128.0 + 256.0
                               + 512.0)
    assert by_scope["attn/out"] == 1024.0
    assert by_scope["mlp"] == 2048.0 + 4096.0 + 8192.0
    assert table[(FLASH, "remat")] == 32.0
    assert table[(FLASH, "bwd")] == 128.0


def test_the_adapter_matches_kernels_however_they_are_numbered():
    from perfbench.adapters import dsa_moe_lm

    matches = dsa_moe_lm.defined("dsa_fwd", "dsa_index_fwd")
    for text in ("%dsa_fwd.300 = bf16[4,8,16384,128]{3,2,1,0} custom-call(",
                 "%dsa_fwd = (bf16[4,8,16384,128]{3,2,1,0}) custom-call(",
                 "%dsa_index_fwd.47 = f32[1,16384,16384]{2,1,0} custom-call("):
        assert any(m in text for m in matches), text
    # Not the instruction that reads a kernel's result, nor another
    # kernel whose name starts alike.
    for text in ("%dot.10 = f32[8,8]{1,0} dot(%dsa_fwd.300, %dsa_fwd.300)",
                 "%dsa_index_bwd.3 = f32[8,8]{1,0} custom-call(%dot.8)"):
        assert not any(m in text for m in matches), text


def test_dsa_reduce_finds_nothing_in_another_program():
    other = HLO
    for part in dsa_reduce.DSA_PARTS:
        other = other.replace("/" + part, "")
    found = dsa_reduce.attribute(OP_S, scope_reduce.parse_hlo(other))
    assert not set(found).intersection(dsa_reduce.DSA_PARTS)
    assert dsa_reduce.part_ms({"reduced": {}}, dsa_reduce.DSA_PARTS) is None
    assert dsa_reduce.scope_ms({"reduced": {}}, ("mlp",)) is None
    assert dsa_reduce.kernel_roofline({"reduced": {}, "cell": None},
                                      "dsa_flash", "x") is None
    assert dsa_reduce.part_of(f"{STEP}/jvp(layer_0)/mlp/dot_general") is None
    # A part is a whole component: a parameter named after one is not it.
    assert dsa_reduce.part_of(f"{STEP}/optimizer/my_dsa_select_x/add") is None


def test_every_new_reader_returns_nothing_without_a_trace():
    """On a program or a run with nothing to read the readers return None
    and do not raise (the parent commit under this benchmark)."""
    import importlib

    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        new = [m["name"] for m in json.load(f)["per_layer"]
               if m.get("workloads") == ["keyevl2_t16k"]]
    assert len(new) == 10
    for name in new:
        reader = importlib.import_module("perfbench.layer_metrics." + name)
        assert reader.read({"reduced": {}, "trace_steps": 2}) is None, name
