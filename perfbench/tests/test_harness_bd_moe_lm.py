"""The ``bd_moe_lm`` kind through the harness in rehearsal, on one and on
four virtual devices, and ``bd_reduce`` on a hand-built HLO and event
list."""

import importlib
import json
import os
import subprocess
import sys

import pytest

from perfbench import bd_reduce, run, scope_reduce


def _run(*args, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), *args],
        env=env, capture_output=True, text=True, timeout=1500, cwd=run.ROOT)


@pytest.mark.parametrize("trace,devices", [("0", 1), ("1", 1), ("0", 4)])
def test_sdar_cell_rehearses_end_to_end(trace, devices):
    done = _run("--workload", "sdar30b_bd8k", "--seed", "4600000001",
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
    # 2 sequences a chip of 256 clean tokens and their copies.
    assert "for a buffer of 2048 = positions x min(2, 4)" in held[0]
    assert "reference: float32 at precision highest" in done.stdout
    assert "of the noised copy is the mask id (511)" in done.stdout
    tiles = next(l for l in lines if l.startswith("flash kernels under"))
    assert "BlockDiffusion(256, 4), blocks of 256" in tiles
    assert "computed 131072 over needed 66560" in tiles
    for check in ("ln_f_scale", "wo_last", "wk_last"):
        assert f"check (b): {check}:" in done.stdout
    assert done.stdout.count("check (b):") == 3


STEP = "jit(hvd_lm_train_step)"
FLASH = "attn/flash_attention"
HLO = f"""HloModule jit_hvd_lm_train_step, is_scheduled=true

ENTRY %main (a: s32[8,8], c: f32[8,8]) -> f32[8,8] {{
  %a = s32[8,8]{{1,0}} parameter(0)
  %c = f32[8,8]{{1,0}} parameter(1)
  %select.1 = s32[8,8]{{1,0}} select(%a, %a, %a), metadata={{op_name="{STEP}/jvp()/embed/diffusion_assemble/select_n"}}
  %gather.2 = f32[8,8]{{1,0}} gather(%c, %select.1), metadata={{op_name="{STEP}/jvp()/embed/gather"}}
  %cos.3 = f32[8,8]{{1,0}} cosine(%gather.2), metadata={{op_name="{STEP}/jvp(layer_0)/attn/qkv/qk_head_norm_rope/cos"}}
  %copy.4 = f32[8,8]{{1,0}} copy(%cos.3), metadata={{op_name="{STEP}/jvp(layer_0)/{FLASH}/broadcast_in_dim"}}
  %flash_fwd.5 = f32[8,8]{{1,0}} custom-call(%copy.4), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(layer_0)/{FLASH}/flash_fwd/pallas_call"}}
  %flash_bwd_dkv.6 = f32[8,8]{{1,0}} custom-call(%flash_fwd.5), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/transpose(jvp(layer_0))/{FLASH}/flash_bwd_dkv/pallas_call"}}
  %dot.7 = f32[8,8]{{1,0}} dot(%flash_fwd.5, %flash_fwd.5), metadata={{op_name="{STEP}/jvp(layer_0)/mlp/moe_router/dot_general"}}
  %moe_gmm.300 = f32[8,8]{{1,0}} custom-call(%dot.7), custom_call_target="tpu_custom_call", metadata={{op_name="{STEP}/jvp(layer_0)/mlp/moe_experts/moe_gmm/pallas_call"}}
  %add.9 = f32[8,8]{{1,0}} add(%moe_gmm.300, %moe_gmm.300), metadata={{op_name="{STEP}/jvp(layer_0)/mlp/add"}}
  %dot.10 = f32[8,8]{{1,0}} dot(%add.9, %add.9), metadata={{op_name="{STEP}/jvp()/head/dot_general"}}
  ROOT %reduce.11 = f32[8]{{0}} reduce(%dot.10, %c), dimensions={{1}}, metadata={{op_name="{STEP}/jvp()/loss/reduce_sum"}}
}}
"""

OP_S = {"%select.1 select s32[8,8]": 1.0,
        "%gather.2 gather f32[8,8]": 2.0,
        "%cos.3 cosine f32[8,8]": 4.0,
        "%copy.4 copy f32[8,8]": 8.0,
        "%flash_fwd.5 custom-call f32[8,8]": 16.0,
        "%flash_bwd_dkv.6 custom-call f32[8,8]": 32.0,
        "%dot.7 dot f32[8,8]": 64.0,
        "%moe_gmm.300 custom-call f32[8,8]": 128.0,
        "%add.9 add f32[8,8]": 256.0,
        "%dot.10 dot f32[8,8]": 512.0,
        "%reduce.11 reduce f32[8]": 1024.0,
        "%not-in-the-hlo fusion f32[1]": 0.125}


def test_bd_reduce_books_each_op_by_its_part():
    hlo = scope_reduce.parse_hlo(HLO)
    parts = bd_reduce.attribute(OP_S, hlo)
    # The embedding's own gather, the glue, the kernels, an expert layer's
    # residual add, the head and the loss are in no part.
    assert parts == {"diffusion_assemble": 1.0, "qk_head_norm_rope": 4.0,
                     "moe_router": 64.0, "moe_experts": 128.0}
    table = scope_reduce.attribute(OP_S, hlo)["table"]
    by_scope = {}
    for (scope, _), seconds in table.items():
        by_scope[scope] = by_scope.get(scope, 0.0) + seconds
    # The benchmark's own table answers the model scopes: the assembly is
    # the embedding's, so the identity holds and nothing is unattributed.
    assert by_scope["embed"] == 1.0 + 2.0
    assert by_scope[FLASH] == 8.0 + 16.0 + 32.0
    assert by_scope["mlp"] == 64.0 + 128.0 + 256.0
    assert by_scope["head"] == 512.0 and by_scope["loss"] == 1024.0


def test_bd_reduce_finds_nothing_in_another_program():
    assert bd_reduce.part_ms({"reduced": {}}, bd_reduce.PARTS) is None
    assert bd_reduce.scope_ms({"reduced": {}}, ("mlp",)) is None
    assert bd_reduce.flash_kernels_ms({"reduced": {}}) is None
    assert bd_reduce.part_of(f"{STEP}/jvp(layer_0)/mlp/dot_general") is None
    # A part is a whole component: a parameter named after one is not it.
    assert bd_reduce.part_of(
        f"{STEP}/optimizer/my_diffusion_assemble_x/add") is None


def test_every_new_reader_returns_nothing_without_a_trace():
    """On a program or a run with nothing to read the readers return None
    and do not raise (the parent commit under this benchmark)."""
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        new = [m["name"] for m in json.load(f)["per_layer"]
               if m.get("workloads") == ["sdar30b_bd8k"]]
    assert len(new) == 8 and all(n.startswith("bd_") for n in new)
    for name in new:
        reader = importlib.import_module("perfbench.layer_metrics." + name)
        assert reader.read({"reduced": {}, "trace_steps": 2,
                            "cell": None}) is None, name
