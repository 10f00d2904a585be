"""The ``cca_moe_lm`` kind through the harness in rehearsal, on one and on
four virtual devices, its controls through the harness's comparison, and
``cca_reduce`` on a hand-built HLO and event list."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import cca_reduce, run, scope_reduce
from perfbench.adapters import cca_moe_lm
from perfbench.controls_cca_moe_lm import CONTROLS
from perfbench.reference import cca_moe_lm as reference


def _run(*args, devices=1, script="run.py"):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    return subprocess.run(
        [sys.executable, os.path.join(run.HERE, script), *args],
        env=env, capture_output=True, text=True, timeout=1500, cwd=run.ROOT)


@pytest.mark.parametrize("trace,devices", [("0", 1), ("1", 1), ("0", 4)])
def test_zaya_cell_rehearses_end_to_end(trace, devices):
    done = _run("--workload", "zaya1_8b_t16k", "--seed", "5300000001",
                "--seconds", "1", "--trace", trace, "--rehearse-cpu",
                devices=devices)
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["correct"] is True and last["failed"] == 0
    assert last["device"]["count"] == devices
    assert "reference: float32 at precision highest, 3 layers" in done.stdout
    assert done.stdout.count("held experts, first batch, layer") == 3
    assert "tokens skip" in done.stdout
    # The key temperature is read and printed, the other seven are held.
    for check in reference.CHECKED:
        held = check not in cca_moe_lm.READ_NOT_HELD
        assert (f"check (b): {check}:" in done.stdout) == held
        assert (f"read, not held: {check}:" in done.stdout) != held
    assert done.stdout.count("check (b):") == 7


def test_each_control_goes_through_the_harness_comparison():
    """At the rehearsal's sizes and tolerances the outcomes mean little;
    what holds anywhere: every control is run, and the program passes."""
    done = _run("--workload", "zaya1_8b_t16k", "--seed", "5300000001",
                "--rehearse-cpu", script="controls_cca_moe_lm.py")
    assert done.returncode == 0, done.stderr[-2000:]
    rows = [json.loads(l) for l in done.stdout.splitlines()
            if l.startswith('{"control"')]
    assert [r["control"] for r in rows] == list(CONTROLS) + ["program"]
    assert len(CONTROLS) == 14
    for row in rows:
        if row["control"] == "program":
            assert row["correct"] is True and row["refused_by"] == []
        if row["control"] == "plain_add":
            assert ("gradient_matches_reference:merge2_out_scale_last"
                    in row["refused_by"])


HLO = """HloModule jit_hvd_lm_train_step

%fused_computation (p: bf16[8,8]) -> bf16[8,8] {
  %p = bf16[8,8] parameter(0)
  ROOT %dot.1 = bf16[8,8] dot(%p, %p), metadata={op_name="jit(s)/jvp(layer_0)/attn/qkv/dot_general"}
}

%fused_computation.1 (p: bf16[8,8]) -> bf16[8,8] {
  %p.1 = bf16[8,8] parameter(0)
  ROOT %dot.2 = bf16[8,8] dot(%p.1, %p.1), metadata={op_name="jit(s)/jvp(layer_0)/attn/qkv/cca_mix/dot_general"}
}

ENTRY %main (a: bf16[8,8]) -> bf16[8,8] {
  %a = bf16[8,8] parameter(0)
  %fusion.1 = bf16[8,8] fusion(%a), kind=kOutput, calls=%fused_computation
  %fusion.2 = bf16[8,8] fusion(%fusion.1), kind=kOutput, calls=%fused_computation.1
  %fusion.3 = bf16[8,8] multiply(%fusion.2, %fusion.2), metadata={op_name="jit(s)/jvp(layer_0)/attn/qkv/cca_norm_rope/mul"}
  %fusion.4 = bf16[8,8] multiply(%fusion.3, %fusion.3), metadata={op_name="jit(s)/jvp(layer_0)/attn/flash_attention/broadcast_in_dim"}
  %flash_fwd.5 = bf16[8,8] custom-call(%fusion.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/jvp(layer_0)/attn/flash_attention/flash_fwd/pallas_call"}
  %fusion.6 = bf16[8,8] multiply(%flash_fwd.5, %flash_fwd.5), metadata={op_name="jit(s)/jvp(layer_0)/attn/out/dot_general"}
  %fusion.7 = bf16[8,8] multiply(%fusion.6, %fusion.6), metadata={op_name="jit(s)/jvp(layer_0)/attn/out/res_scale/mul"}
  %fusion.8 = bf16[8,8] multiply(%fusion.7, %fusion.7), metadata={op_name="jit(s)/jvp(layer_0)/mlp/moe_router/router_mlp/erf"}
  %fusion.9 = bf16[8,8] multiply(%fusion.8, %fusion.8), metadata={op_name="jit(s)/jvp(layer_0)/mlp/moe_router/router_state/dot_general"}
  %moe_gmm.10 = bf16[8,8] custom-call(%fusion.9), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/jvp(layer_0)/mlp/moe_experts/moe_gmm/pallas_call"}
  %fusion.11 = bf16[8,8] multiply(%moe_gmm.10, %moe_gmm.10), metadata={op_name="jit(s)/jvp(layer_0)/mlp/moe_skip/mul"}
  %fusion.12 = bf16[8,8] multiply(%fusion.11, %fusion.11), metadata={op_name="jit(s)/jvp(layer_0)/mlp/res_scale/add"}
  %fusion.13 = bf16[8,8] multiply(%fusion.12, %fusion.12), metadata={op_name="jit(s)/jvp(head)/dot_general"}
  %fusion.14 = bf16[8,8] multiply(%fusion.13, %fusion.13), metadata={op_name="jit(s)/jvp(loss)/reduce_sum"}
  %fusion.15 = bf16[8,8] multiply(%fusion.14, %fusion.14), metadata={op_name="jit(s)/jvp(embed)/gather"}
  ROOT %fusion.16 = bf16[8,8] multiply(%fusion.15, %fusion.15), metadata={op_name="jit(s)/optimizer/mul"}
}
"""


def test_cca_reduce_books_every_op_to_one_part():
    hlo = scope_reduce.parse_hlo(HLO)
    want = {"fusion.1": "proj", "fusion.2": "mix", "fusion.3": "mix",
            "fusion.4": "mix", "flash_fwd.5": "flash", "fusion.6": "proj",
            "fusion.7": "merge", "fusion.8": "router", "fusion.9": "router",
            "moe_gmm.10": "experts", "fusion.11": "experts",
            "fusion.12": "merge", "fusion.13": "head", "fusion.14": "head",
            "fusion.15": "other", "fusion.16": "other"}
    assert {name: cca_reduce.part_of(name, hlo) for name in want} == want
    op_s = {f"%{name} fusion bf16[8,8]": 1.0 for name in want}
    op_s["%gone.1 fusion bf16[8,8]"] = 0.5
    parts = cca_reduce.attribute(op_s, hlo)
    assert parts == {"proj": 2.0, "mix": 3.0, "flash": 1.0, "merge": 2.0,
                     "router": 2.0, "experts": 2.0, "head": 2.0,
                     "other": 2.5}
    assert sum(parts.values()) == sum(op_s.values())
    assert set(parts) <= set(cca_reduce.PARTS)


def test_cca_reduce_finds_nothing_on_another_program():
    """The parent's program has no ``cca_mix``: every reader returns None
    and the line leaves the metric out."""
    import importlib

    assert cca_reduce.for_ctx({"reduced": {}}) is None
    assert cca_reduce.part_ms({"reduced": None}, ("flash",)) is None
    assert cca_reduce.moe_part_ms({"reduced": None}, ("moe_experts",)) is None
    ctx = {"reduced": None, "cell": None, "peaks": {}, "trace_steps": 2}
    for name in ("cca_ms_per_step", "cca_proj_ms_per_step",
                 "cca_mix_ms_per_step", "cca_flash_ms_per_step",
                 "cca_flash_roofline", "zaya_router_ms_per_step",
                 "zaya_experts_ms_per_step", "zaya_merge_ms_per_step",
                 "zaya_head_ms_per_step"):
        reader = importlib.import_module("perfbench.layer_metrics." + name)
        assert reader.read(ctx) is None, name
