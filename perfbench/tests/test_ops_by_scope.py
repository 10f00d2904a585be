"""``python -m perfbench.ops_by_scope``: the executed ops under a scope,
joined to the optimized HLO, on the hand-written module of
``test_scope_reduce`` and through the command on a real (CPU) trace file,
which has the HLO and no device events."""

import pytest

from perfbench import ops_by_scope, scope_reduce
from test_scope_reduce import HLO, cpu_trace  # noqa: F401  (the fixture)

# Seconds over a window of two steps, keyed as trace_reduce keys them.
OP_S = {
    "%fusion.1 = bf16[8,64]{1,0} fusion": 0.004,
    "%fusion.1.remat = bf16[8,64]{1,0} fusion": 0.002,
    "%fusion.2 = f32[64,64]{1,0} fusion": 0.006,
    "%copy-done.1 = bf16[64,64]{1,0} copy-done": 0.001,
    "%flash_fwd.6 = bf16[8,64]{1,0} custom-call": 0.010,
    "%fusion.3 = f32[64]{0} fusion": 0.003,
}


def test_the_ops_under_a_scope_by_kind():
    hlo = scope_reduce.parse_hlo(HLO)
    found = ops_by_scope.rows(OP_S, hlo, "mlp", steps=2)
    by_name = {(r["name"], r["phase"]): r for r in found}
    # The weight-gradient fusion is the matmul's (bwd), not its fused
    # update's; the prefetch is booked where its data is needed.
    assert by_name[("fusion", "bwd")]["ms"] == pytest.approx(3.0)
    assert by_name[("fusion", "bwd")]["holds"].startswith("convolution")
    assert by_name[("fusion", "fwd")]["ms"] == pytest.approx(2.0)
    assert by_name[("fusion.1.remat", "remat")]["ms"] == pytest.approx(1.0)
    assert by_name[("copy-done", "fwd")]["ms"] == pytest.approx(0.5)
    assert found[0]["ms"] >= found[-1]["ms"]
    assert sum(r["ms"] for r in found) == pytest.approx(6.5)
    # Whole components only, and several joined by "/".
    assert ops_by_scope.rows(OP_S, hlo, "ml", 2) == []
    assert [r["name"] for r in ops_by_scope.rows(
        OP_S, hlo, "attn/flash_attention", 2)] == ["flash_fwd"]
    assert [r["name"] for r in ops_by_scope.rows(
        OP_S, hlo, "layer_0", 2) if r["opcode"] == "custom-call"] == [
            "flash_fwd"]
    text = ops_by_scope.format_table(found, "mlp", 2)
    assert text.startswith("ops under 'mlp': 6.500 ms a step over 2 step(s)")
    assert "bwd 3.000" in text and "mlp/dot_general" in text


def test_the_command_reads_a_trace_file(cpu_trace, capsys):  # noqa: F811
    assert ops_by_scope.main([cpu_trace, "mlp", "3"]) == 0
    assert capsys.readouterr().out.startswith(
        "ops under 'mlp': 0.000 ms a step over 3 step(s)")
    assert ops_by_scope.main([]) == 2
