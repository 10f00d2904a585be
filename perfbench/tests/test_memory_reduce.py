"""``perfbench/memory_reduce.py`` by hand: ``from_trace`` on a hand-built
``HloProto`` (wire bytes) and on a real CPU trace, the six readers through
a ctx as ``run.py`` makes it, and ``gpt67_t8192`` compiled with a dump for
the described v5e (same fixture as ``test_chip_compile.py``; nothing runs;
about 25 seconds).  The rules themselves are tier-1's
(``tests/test_step_memory.py``).
"""

import importlib
import os
import types

import numpy as np
import pytest

from perfbench import memory_reduce, run, scope_reduce
from test_chip_compile import topo  # noqa: F401  (the fixture)
from test_scope_reduce import cpu_trace  # noqa: F401  (the fixture)

SIX = ["hbm_state_gib", "hbm_temp_gib", "hbm_peak_fwd_gib",
       "hbm_peak_bwd_gib", "hbm_peak_update_gib", "hbm_peak_unplaced_gib"]


# --- a protobuf writer, the twin of scope_reduce._fields --------------------

def varint(value: int) -> bytes:
    out = bytearray()
    while True:
        byte, value = value & 0x7F, value >> 7
        out.append(byte | (0x80 if value else 0))
        if not value:
            return bytes(out)


def message(*fields) -> bytes:
    """``(number, int | bytes | str)`` pairs to wire bytes; zero ints are
    left out, as proto3 leaves them."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            if value:
                out += varint(number << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


HLO = '''HloModule jit_hvd_lm_train_step, is_scheduled=true

ENTRY %main (w: f32[256], t: s32[8]) -> f32[256] {
  %params__w__.1 = f32[256]{0} parameter(0), metadata={op_name="params[\\'w\\']"}
  %tokens.1 = s32[8]{0} parameter(1), metadata={op_name="tokens"}
  %act = (f32[128]{0}, f32[64]{0}, f32[]) fusion(%params__w__.1), kind=kLoop, calls=%f, metadata={op_name="jit(hvd_lm_train_step)/jvp(layer_0)/mlp/add"}
  %logits = f32[512]{0} broadcast(%act), metadata={op_name="jit(hvd_lm_train_step)/transpose(jvp())/loss/broadcast_in_dim"}
  %dynamic-update-slice = f32[512]{0} dynamic-update-slice(%logits, %act, %tokens.1), metadata={op_name="jit(hvd_lm_train_step)/transpose(jvp())/loss/scatter"}
  %grad = f32[256]{0} multiply(%dynamic-update-slice, %act), metadata={op_name="jit(hvd_lm_train_step)/transpose(jvp(layer_0))/mlp/mul"}
  ROOT %new_w = f32[256]{0} subtract(%params__w__.1, %grad), metadata={op_name="jit(hvd_lm_train_step)/optimizer/sub"}
}
'''
IDS = {"params__w__.1": (1 << 32) + 1, "tokens.1": (1 << 32) + 2,
       "act": (1 << 32) + 3, "logits": (1 << 32) + 4,
       "dynamic-update-slice": (1 << 32) + 5, "grad": (1 << 32) + 6,
       "new_w": (1 << 32) + 7}
ALLOC, FREE, SHARE_WITH = 0, 1, 2


def _hlo_proto(with_heap_trace=True) -> bytes:
    module = message((1, "jit_hvd_lm_train_step"), (3, message(
        (1, "main"), *[(2, message((1, name), (35, number)))
                       for name, number in IDS.items()])))

    def logical(number, size, name, index=None, color=0):
        where = message((4, IDS[name]), *(
            [(3, varint(index))] if index is not None else []))
        return (1, message((1, number), (2, size), (3, where), (4, color)))

    def assigned(number, offset, size):
        return (9, message((1, number), (2, offset), (3, size)))

    def event(kind, number, name, share=0):
        return (1, message((1, kind), (2, number), (3, "main"), (4, name),
                           (5, share)))

    buffers = [logical(10, 1024, "params__w__.1"), logical(11, 32, "tokens.1"),
               logical(12, 512, "act", 0), logical(13, 256, "act", 1),
               logical(14, 2048, "logits"),
               logical(15, 2048, "dynamic-update-slice"),
               logical(16, 1024, "grad"), logical(17, 1024, "new_w"),
               logical(18, 4096, "act", 0, color=1),
               logical(19, 512, "act", 2)]
    allocations = [
        (3, message((1, 0), (2, 1024), (5, 1), (6, 0), (7, 1), (13, 1),
                    assigned(10, 0, 1024), assigned(17, 0, 1024))),
        (3, message((1, 1), (2, 32), (5, 1), (6, 1), assigned(11, 0, 32))),
        (3, message((1, 2), (2, 4096), assigned(12, 0, 512),
                    assigned(13, 512, 256), assigned(14, 1024, 2048),
                    assigned(15, 1024, 2048), assigned(16, 3072, 1024),
                    assigned(19, 768, 512))),
        (3, message((1, 3), (2, 8192), (8, 1), assigned(18, 0, 4096))),
        (3, message((1, 4), (2, 4), (12, 1))),
        (3, message((1, 5), (2, 8), (3, 1)))]
    # act{0}, act{1} | logits | dynamic-update-slice shares logits' place,
    # then logits and act{1} are freed | grad, then the rest.  Buffer 19
    # (a scalar the simulation never saw) has no event.
    trace = message(
        event(ALLOC, 12, "act"), event(ALLOC, 13, "act"),
        event(ALLOC, 14, "logits"),
        event(SHARE_WITH, 15, "dynamic-update-slice", 14),
        event(FREE, 14, "logits"), event(FREE, 13, "act"),
        event(ALLOC, 16, "grad"), event(FREE, 15, "dynamic-update-slice"),
        event(FREE, 12, "act"), event(FREE, 16, "grad"),
        (2, 1), (3, 2))
    vmem = message(event(ALLOC, 18, "act"), event(FREE, 18, "act"), (3, 3))
    assignment = message(*buffers, *allocations, *(
        [(4, vmem), (4, trace)] if with_heap_trace else []))
    return message((1, module), (3, assignment))


def test_from_a_hand_built_hlo_proto():
    proto = scope_reduce._grouped(_hlo_proto())
    assignment = memory_reduce.parse_proto(
        proto, scope_reduce.parse_hlo(HLO), "by hand")
    kinds = [(a.number, a.kind, a.parameter, a.color, a.live_out)
             for a in assignment.allocations]
    assert kinds == [(0, "argument", 0, 0, True),
                     (1, "argument", 1, 0, False),
                     (2, "temporary", None, 0, False),
                     (3, "temporary", None, 1, False),
                     (4, "constant", None, 0, False),
                     (5, "thread-local", None, 0, False)]
    temporary, = memory_reduce.hbm_temporaries(assignment)
    buffers = {(b.name, b.index): b for b in temporary.buffers}
    # One instant per instruction that is given a place: act, logits,
    # dynamic-update-slice, grad.
    assert assignment.sequence == ["act", "logits", "dynamic-update-slice",
                                   "grad"]
    assert (buffers["act", "0"].start, buffers["act", "0"].end) == (0, 3)
    assert (buffers["act", "1"].start, buffers["act", "1"].end) == (0, 2)
    assert (buffers["logits", ""].start, buffers["logits", ""].end) == (1, 2)
    assert buffers["dynamic-update-slice", ""][2:4] == (1024, 2048)
    assert buffers["act", "1"].shape == "f32[64]"
    assert buffers["act", "2"][2:] == (768, 512, "f32[]", None, None)

    memory = memory_reduce.reduce(assignment, 1, 1)
    # At the in-place update both logits buffers and both activations are
    # live: 512 + 256 + 2048 by slot, 2048 more by XLA's count.
    assert memory["peak"][:2] == (3, "grad")
    assert memory["occupied"] == 512 + 2048 + 1024
    assert memory["naive"] == (2, 512 + 256 + 2048 + 2048)
    assert memory_reduce.total_occupancy(assignment)[2] == 512 + 256 + 2048
    assert memory["table"] == {("mlp", "fwd"): 512, ("loss", "bwd"): 2048,
                               ("mlp", "bwd"): 1024}
    assert (memory["state"], memory["batch"], memory["temp"]) == (
        1024, 32, 4096)
    assert memory["arguments"] == {"params": 1024, "tokens": 32}
    assert memory["other_spaces"] == 8192 and memory["unranged"] == 512
    assert (memory["constants"], memory["thread_local"]) == (4, 8)


def test_a_proto_without_heap_traces_gives_sizes_only():
    proto = scope_reduce._grouped(_hlo_proto(with_heap_trace=False))
    assignment = memory_reduce.parse_proto(proto,
                                           scope_reduce.parse_hlo(HLO))
    assert not memory_reduce.has_live_ranges(assignment)
    memory = memory_reduce.reduce(assignment, 1, 1)
    assert memory["peak"] is None and memory["temp"] == 4096
    values = memory_reduce.metrics(memory)
    assert values["hbm_temp_gib"] == 4096 / memory_reduce.GIB


def test_a_cpu_trace_holds_allocations_and_no_live_ranges(cpu_trace):  # noqa: F811
    assignment = memory_reduce.from_trace(cpu_trace)
    assert assignment is not None
    assert {a.kind for a in assignment.allocations} >= {"argument",
                                                        "temporary"}
    assert not memory_reduce.has_live_ranges(assignment)
    arguments = [a for a in assignment.allocations if a.kind == "argument"]
    assert sorted(a.parameter for a in arguments) == [0, 1]
    assert memory_reduce.from_trace(os.devnull) is None


def _toy_cell():
    import jax
    import jax.numpy as jnp

    def hvd_toy_step(w, x):
        def loss(w):
            with jax.named_scope("mlp"):
                h = jnp.tanh(x @ w)
            with jax.named_scope("loss"):
                return jnp.mean(h * h)
        value, grad = jax.value_and_grad(loss)(w)
        with jax.named_scope("optimizer"):
            return w - 0.1 * grad, value

    return types.SimpleNamespace(
        step=jax.jit(hvd_toy_step, donate_argnums=0),
        state_shapes=(jax.ShapeDtypeStruct((128, 128), jnp.float32),),
        batch_shapes=(jax.ShapeDtypeStruct((64, 128), jnp.float32),))


def test_the_six_readers_on_a_cpu_trace(cpu_trace, capsys):  # noqa: F811
    """The CPU's trace carries no heap simulator trace, so the readers
    take the fallback: the step compiled once more with a dump."""
    ctx = {"reduced": {}, "trace_steps": 3, "cell": _toy_cell(),
           "trace_file": cpu_trace}
    values = {m: importlib.import_module(
        "perfbench.layer_metrics." + m).read(ctx) for m in SIX}
    out = capsys.readouterr().out
    assert out.count("memory: ") == 1                       # made once
    assert "front end: a compile with xla_dump_to" in out
    assert "remainder 0 = 0.0000%" in out
    assert values["hbm_state_gib"] * memory_reduce.GIB == (
        128 * 128 * 4 + 64 * 128 * 4)
    assert values["hbm_temp_gib"] > 0
    assert values["hbm_peak_fwd_gib"] + values["hbm_peak_bwd_gib"] > 0
    assert sum(values[m] for m in SIX[2:]) == pytest.approx(
        values["hbm_temp_gib"], abs=1e-12)
    assert all(importlib.import_module("perfbench.layer_metrics." + m).read(
        {"reduced": {}, "trace_steps": 3, "cell": None}) is None
        for m in SIX)


def test_gpt67_t8192_for_the_v5e_holds_two_logits_slots_at_the_peak(
        topo, tmp_path):  # noqa: F811
    from jax.sharding import Mesh

    _, entry, config, mix = run._cell_files("gpt67_t8192", rehearse=False)
    mesh = Mesh(np.asarray(topo.devices[:entry["chips"]]),
                tuple(mix["mesh_axes"]))
    for key in run.HARNESS_KEYS:
        mix.pop(key)
    adapter = importlib.import_module("perfbench.adapters." + config["kind"])
    cell = adapter.build(config, mix, mesh)
    compiled = memory_reduce.compile_with_dump(cell, str(tmp_path))
    assignment = memory_reduce.from_dump(str(tmp_path))
    memory = memory_reduce.reduce(
        assignment, memory_reduce._leaves(cell.state_shapes),
        memory_reduce._leaves(cell.batch_shapes))
    analysis = memory_reduce.analysis_of(compiled)
    print(memory_reduce.format_table(memory, analysis))

    logits = 8192 * 50257 * 4
    big = [s for s in memory["slots"] if s[0] >= logits]
    assert len(big) == 2 and all(s[5:7] == ("bwd", "loss") for s in big)
    assert memory["occupied"] / memory_reduce.GIB == pytest.approx(
        5.27, abs=0.01)
    # XLA's own count holds a third logits-sized buffer: the in-place
    # update of the first.
    assert memory["naive"][1] - memory["occupied"] > 0.99 * logits
    assert len(memory["plateaus"]) == 3
    assert memory["phase"]["unattributed"] == 0
    # The rows against memory_analysis(): arguments and outputs to the
    # byte, the temporaries less the compiler's own reserve.
    assert memory["state"] + memory["batch"] == analysis[
        "argument_size_in_bytes"]
    assert memory["outputs"] == (analysis["output_size_in_bytes"]
                                 - analysis["alias_size_in_bytes"])
    assert 0 <= analysis["temp_size_in_bytes"] - memory["temp"] < 2 ** 24
    assert memory["other_spaces"] > 2 ** 26                   # VMEM, left out
    six = memory_reduce.metrics(memory)
    assert six["hbm_peak_unplaced_gib"] < 0.02 * 13.47
