"""The ``moe_lm`` kind through the harness in rehearsal, and
``moe_reduce`` on a hand-built HLO and event list."""

import json
import os
import subprocess
import sys

import pytest

from perfbench import moe_reduce, run, scope_reduce


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    return subprocess.run(
        [sys.executable, os.path.join(run.HERE, "run.py"), *args],
        env=env, capture_output=True, text=True, timeout=900, cwd=run.ROOT)


@pytest.mark.parametrize("trace", ("0", "1"))
def test_olmoe_cell_rehearses_end_to_end(trace):
    done = _run("--workload", "olmoe_t4096", "--seed", "3000000001",
                "--seconds", "1", "--trace", trace, "--rehearse-cpu")
    assert done.returncode == 0, done.stderr[-2000:]
    last = json.loads(done.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and "metrics" not in last
    assert last["correct"] is True and last["failed"] == 0
    loads = [l for l in done.stdout.splitlines()
             if l.startswith("expert load, first batch, layer")]
    assert len(loads) == 2 and all("dropped 0" in l for l in loads)
    assert "1024 assignments of 512 tokens x 2" in loads[0]
    for check in ("ln_f_scale", "w_down_last", "router_last"):
        assert f"check (b): {check}:" in done.stdout


HLO = """HloModule jit_hvd_lm_train_step, is_scheduled=true

%fused_computation.1 (p0: bf16[64,32], p1: bf16[32,8]) -> f32[64,8] {
  %p0 = bf16[64,32]{1,0} parameter(0)
  %p1 = bf16[32,8]{1,0} parameter(1)
  ROOT %dot.1 = f32[64,8]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(hvd_lm_train_step)/jvp(layer_0)/mlp/moe_router/dot_general"}
}

%fused_computation.2 (p0: bf16[128,16]) -> bf16[128,16] {
  %p0 = bf16[128,16]{1,0} parameter(0)
  ROOT %mul.2 = bf16[128,16]{1,0} multiply(%p0, %p0), metadata={op_name="jit(hvd_lm_train_step)/transpose(jvp(layer_1))/mlp/moe_experts/mul"}
}

ENTRY %main (a: bf16[64,32], b: bf16[32,8], c: bf16[128,16]) -> bf16[128,16] {
  %a = bf16[64,32]{1,0} parameter(0)
  %b = bf16[32,8]{1,0} parameter(1)
  %c = bf16[128,16]{1,0} parameter(2)
  %fusion.1 = f32[64,8]{1,0} fusion(%a, %b), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(hvd_lm_train_step)/optimizer/add"}
  %sort.3 = s32[128]{0} sort(%c), dimensions={0}, metadata={op_name="jit(hvd_lm_train_step)/jvp(layer_0)/mlp/moe_dispatch/sort"}
  %copy.4 = bf16[128,16]{1,0} copy(%c)
  %moe_gmm.5 = bf16[128,16]{1,0} custom-call(%copy.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(hvd_lm_train_step)/jvp(layer_0)/mlp/moe_experts/moe_gmm/pallas_call"}
  %fusion.2 = bf16[128,16]{1,0} fusion(%moe_gmm.5), kind=kLoop, calls=%fused_computation.2
  %gather.6 = bf16[128,16]{1,0} gather(%fusion.2, %sort.3), metadata={op_name="jit(hvd_lm_train_step)/jvp(layer_0)/mlp/moe_combine/gather"}
  %add.7 = bf16[128,16]{1,0} add(%gather.6, %c), metadata={op_name="jit(hvd_lm_train_step)/jvp(layer_0)/mlp/add"}
  ROOT %dot.8 = bf16[128,16]{1,0} dot(%add.7, %add.7), metadata={op_name="jit(hvd_lm_train_step)/jvp()/head/dot_general"}
}
"""


def test_moe_reduce_books_each_op_by_the_part_under_mlp():
    hlo = scope_reduce.parse_hlo(HLO)
    # A fusion is booked by the matmul inside it, not by its own name.
    assert moe_reduce.op_name_of("fusion.1", hlo).endswith(
        "mlp/moe_router/dot_general")
    # An instruction without an op_name goes where its result is needed.
    assert moe_reduce.op_name_of("copy.4", hlo).endswith(
        "moe_gmm/pallas_call")
    op_s = {"%fusion.1 fusion f32[64,8]": 1.0,
            "%sort.3 sort s32[128]": 2.0,
            "%copy.4 copy bf16[128,16]": 0.5,
            "%moe_gmm.5 custom-call bf16[128,16]": 8.0,
            "%fusion.2 fusion bf16[128,16]": 1.5,
            "%gather.6 gather bf16[128,16]": 3.0,
            "%add.7 add bf16[128,16]": 0.25,
            "%dot.8 dot bf16[128,16]": 16.0,
            "%not-in-the-hlo fusion f32[1]": 4.0}
    parts = moe_reduce.attribute(op_s, hlo)
    assert parts == {"moe_router": 1.0, "moe_dispatch": 2.0,
                     "moe_experts": 10.0, "moe_combine": 3.0,
                     moe_reduce.OTHER: 0.25}
    # The benchmark's own table says mlp for all of them.
    table = scope_reduce.attribute(op_s, hlo)["table"]
    assert sum(v for (scope, _), v in table.items()
               if scope == "mlp") == sum(parts.values())


def test_moe_reduce_finds_nothing_in_a_dense_program():
    dense = HLO
    for part in moe_reduce.SUB_SCOPES:
        dense = dense.replace("/" + part, "")
    ctx = {"reduced": {"op_s": {"%sort.3 sort s32[128]": 2.0}},
           "trace_steps": 1, "trace_file": "/nonexistent.xplane.pb"}
    assert moe_reduce.part_ms({"reduced": {}}, ("moe_experts",)) is None
    hlo = scope_reduce.parse_hlo(dense)
    parts = moe_reduce.attribute(ctx["reduced"]["op_s"], hlo)
    assert set(parts) == {moe_reduce.OTHER}
