"""Where a compiled step's bytes live (docs/timeline.md, "Where the step's
bytes live"): ``perfbench/memory_reduce.py`` on the tiny LM step of
``tests/test_step_scopes.py`` compiled with ``xla_dump_to``, and its rules
on hand-built allocation listings.
"""

import types

import jax
import jax.numpy as jnp
import optax
import pytest

from perfbench import memory_reduce, scope_reduce

STEP = "jit(hvd_lm_train_step)/"


@pytest.fixture(scope="module", params=[
    ("none", False), ("full", False), ("none", True)],
    ids=["plain", "remat_full", "zero"])
def dumped(request, tmp_path_factory):
    """``(assignment, memory_analysis() by name, cell)`` of a tiny LM
    step on 4 of the 8 virtual CPU devices."""
    import horovod_tpu as hvd
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.topology import build_mesh

    remat, shard_optimizer = request.param
    hvd.init()
    try:
        cfg = tfm.TransformerConfig(vocab_size=256, d_model=64, n_heads=2,
                                    n_layers=2, d_ff=128, max_seq=128,
                                    dtype=jnp.bfloat16)
        mesh = build_mesh(axes=("data",), devices=jax.devices()[:4])
        optimizer = optax.sgd(0.01, momentum=0.9)
        step, _, _ = tfm.make_train_step(
            cfg, optimizer, mesh, attention="local", remat=remat,
            shard_optimizer=shard_optimizer)
        params = tfm.init_abstract(cfg)
        opt_state = jax.eval_shape(
            step.init if shard_optimizer else optimizer.init, params)
        tokens = jax.ShapeDtypeStruct((8, 128), jnp.int32)
        cell = types.SimpleNamespace(step=step,
                                     state_shapes=(params, opt_state),
                                     batch_shapes=(tokens, tokens))
        directory = str(tmp_path_factory.mktemp("dump"))
        compiled = memory_reduce.compile_with_dump(cell, directory)
        yield (memory_reduce.from_dump(directory),
               memory_reduce.analysis_of(compiled), cell)
    finally:
        hvd.shutdown()


def _reduce(dumped):
    assignment, analysis, cell = dumped
    return memory_reduce.reduce(
        assignment, memory_reduce._leaves(cell.state_shapes),
        memory_reduce._leaves(cell.batch_shapes))


def test_identity_holds_to_the_byte(dumped):
    _, analysis, _ = dumped
    memory = _reduce(dumped)
    assert memory["state"] + memory["batch"] == analysis[
        "argument_size_in_bytes"]
    assert memory["outputs"] == (analysis["output_size_in_bytes"]
                                 - analysis["alias_size_in_bytes"])
    assert memory["temp"] == analysis["temp_size_in_bytes"]
    # Every byte of the temporaries is occupied and booked once, or
    # fragmentation.
    assert sum(memory["table"].values()) == memory["occupied"]
    assert sum(memory["phase"].values()) == memory["occupied"]
    assert memory["occupied"] + memory["fragmentation"] == memory["temp"]
    assert 0 < memory["occupied"] <= memory["naive"][1]
    text = memory_reduce.format_table(memory, analysis)
    assert "remainder" in text and "arguments 0, outputs less aliased 0, " \
        "temporaries 0 bytes" in text
    six = memory_reduce.metrics(memory)
    assert six["hbm_peak_fwd_gib"] + six["hbm_peak_bwd_gib"] + six[
        "hbm_peak_update_gib"] + six["hbm_peak_unplaced_gib"] == \
        pytest.approx(six["hbm_temp_gib"], abs=1e-12)
    assert six["hbm_peak_fwd_gib"] > 0


def test_arguments_are_classed_by_parameter_number(dumped):
    assignment, _, cell = dumped
    memory = _reduce(dumped)
    state_leaves = memory_reduce._leaves(cell.state_shapes)
    arguments = [a for a in assignment.allocations if a.kind == "argument"]
    assert sorted(a.parameter for a in arguments) == list(
        range(state_leaves + 2))
    assert memory["state"] == sum(a.size for a in arguments
                                  if a.parameter < state_leaves)
    assert memory["batch"] == sum(a.size for a in arguments
                                  if a.parameter >= state_leaves)
    assert memory["batch"] == 2 * 2 * 128 * 4      # two int32[2, 128] shards
    # Without the counts: what no output is aliased to is the batch.
    assert memory_reduce.reduce(assignment)["batch"] == memory["batch"]
    assert set(memory["arguments"]) == {"params", "opt_state", "tokens",
                                        "labels"}
    assert sum(memory["arguments"].values()) == (memory["state"]
                                                 + memory["batch"])
    # Never by their first user: what is booked at the peak is defined by
    # no entry parameter.
    entry = {b.name for a in arguments for b in a.buffers
             if assignment.hlo.instructions[b.name].opcode == "parameter"}
    assert len(entry) == len(arguments)
    for allocation in memory_reduce.hbm_temporaries(assignment):
        assert not entry & {b.name for b in memory_reduce.booked(
            allocation, memory["peak"][0])}
    with pytest.raises(ValueError, match="parameter"):
        memory_reduce.reduce(assignment, 1, 1)


# A step in small, scheduled as numbered: two saved activations, the
# logits broadcast and updated in place, a gradient, a recomputed
# activation that sits in the donated momentum's allocation, an update.
HLO = '''HloModule jit_hvd_lm_train_step, is_scheduled=true

ENTRY %main (w: f32[256], m: f32[256], t: s32[8]) -> (f32[256], f32[256]) {
  %params__w__.1 = f32[256]{0} parameter(0), metadata={op_name="params[\\'w\\']"}
  %opt_state_0__trace__w__.1 = f32[256]{0} parameter(1), metadata={op_name="opt_state[0].trace[\\'w\\']"}
  %tokens.1 = s32[8]{0} parameter(2), metadata={op_name="tokens"}
  %act.1 = f32[128]{0} add(%params__w__.1, %params__w__.1), metadata={op_name="jit(hvd_lm_train_step)/jvp(layer_0)/mlp/add"}
  %act.2 = f32[64]{0} multiply(%act.1, %act.1), metadata={op_name="jit(hvd_lm_train_step)/jvp(layer_0)/attn/qkv/mul"}
  %logits = f32[512]{0} broadcast(%act.2), metadata={op_name="jit(hvd_lm_train_step)/transpose(jvp())/loss/broadcast_in_dim"}
  %dynamic-update-slice = f32[512]{0} dynamic-update-slice(%logits, %act.2, %tokens.1), metadata={op_name="jit(hvd_lm_train_step)/transpose(jvp())/loss/scatter"}
  %act.1.remat = f32[128]{0} add(%params__w__.1, %params__w__.1), metadata={op_name="jit(hvd_lm_train_step)/jvp(layer_0)/mlp/add"}
  %grad = f32[256]{0} multiply(%dynamic-update-slice, %act.1.remat), metadata={op_name="jit(hvd_lm_train_step)/transpose(jvp(layer_0))/mlp/mul"}
  %scratch = f32[256]{0} copy(%grad)
  %packed = f32[256]{0} all-reduce(%grad), metadata={op_name="jit(hvd_lm_train_step)/grad_mean/psum"}
  %new_m = f32[256]{0} add(%opt_state_0__trace__w__.1, %packed), metadata={op_name="jit(hvd_lm_train_step)/optimizer/add"}
  %new_w = f32[256]{0} subtract(%params__w__.1, %new_m), metadata={op_name="jit(hvd_lm_train_step)/optimizer/sub"}
  ROOT %out = (f32[256]{0}, f32[256]{0}) tuple(%new_w, %new_m)
}
'''

LISTING = '''BufferAssignment:
allocation 0: size 1024, parameter 0, shape |f32[256]| at ShapeIndex {}, maybe-live-out:
 value: <0 params__w__.1 @0> (size=1024,offset=0): f32[256]{0}
 value: <12 new_w @0> (size=1024,offset=0): f32[256]{0}
allocation 1: size 1024, parameter 1, shape |f32[256]| at ShapeIndex {}, maybe-live-out:
 value: <1 opt_state_0__trace__w__.1 @0> (size=1024,offset=0): f32[256]{0}
 value: <7 act.1.remat @0> (size=512,offset=0): f32[128]{0}
 value: <11 new_m @0> (size=1024,offset=0): f32[256]{0}
allocation 2: size 32, parameter 2, shape |s32[8]| at ShapeIndex {}:
 value: <2 tokens.1 @0> (size=32,offset=0): s32[8]{0}
allocation 3: size 16, output shape is |(f32[256], f32[256])|, maybe-live-out:
 value: <13 out @0> (size=16,offset=0): (f32[256]{0}, f32[256]{0})
allocation 4: size 4, constant:
 value: <14 constant.1 @0> (size=4,offset=0): f32[]
allocation 5: size 4, thread-local:
 value: <15 add.9 @0> (size=4,offset=0): f32[]
allocation 6: size 4096, preallocated-temp:
 value: <3 act.1 @0> (size=512,offset=0): f32[128]{0}
 value: <4 act.2 @0> (size=256,offset=512): f32[64]{0}
 value: <5 logits @0> (size=2048,offset=1024): f32[512]{0}
 value: <6 dynamic-update-slice @0> (size=2048,offset=1024): f32[512]{0}
 value: <8 grad @0> (size=1024,offset=3072): f32[256]{0}
 value: <9 scratch @0> (size=1024,offset=1024): f32[256]{0}
 value: <10 packed @0> (size=1024,offset=2048): f32[256]{0}
allocation 7: size 8192, color 1, preallocated-temp:
 value: <16 vmem.1 @1> (size=8192,offset=0): f32[2048]{0:S(1)}

Total bytes used: 15392 (15.03KiB)

Used values:
<0 params__w__.1 @0>
 positions:
  params__w__.1
 uses:
  act.1, operand 0
 from instruction: %params__w__.1 = f32[256]{0} parameter(0)

HloLiveRange (max 14):
  InstructionSequence:
    0:params__w__.1
    1:opt_state_0__trace__w__.1
    2:tokens.1
    3:act.1
    4:act.2
    5:logits
    6:dynamic-update-slice
    7:act.1.remat
    8:grad
    9:scratch
    10:packed
    11:new_m
    12:new_w
    13:out
  BufferLiveRange:
    params__w__.1{}:0-14
    opt_state_0__trace__w__.1{}:0-11
    tokens.1{}:0-14
    act.1{}:3-4
    act.2{}:4-6
    logits{}:5-6
    dynamic-update-slice{}:6-8
    act.1.remat{}:7-8
    grad{}:8-10
    scratch{}:9-9
    packed{}:10-11
    new_m{}:11-14
    new_w{}:12-14
    out{}:13-14
    vmem.1{}:3-12
  Live ranges at 6 (peak):
    logits{}: 2048 bytes (cumulative: 2048 bytes)
    dynamic-update-slice{}: 2048 bytes (cumulative: 4096 bytes)
    act.2{}: 256 bytes (cumulative: 4352 bytes)
'''


@pytest.fixture(scope="module")
def toy():
    assignment = memory_reduce.parse_assignment(
        LISTING, scope_reduce.parse_hlo(HLO), "toy")
    return assignment, memory_reduce.reduce(assignment, 2, 1)


def test_a_shared_slot_is_counted_once(toy):
    assignment, memory = toy
    temporary, = memory_reduce.hbm_temporaries(assignment)
    used = memory_reduce.occupancy(temporary, 15)
    # At 6 the update is made in place: logits and dynamic-update-slice
    # share offset 1024 and size 2048, act.2 is still read.
    assert used[6] == 2048 + 256
    naive = memory_reduce.naive_sums(assignment)
    assert naive[6] == 2048 + 2048 + 256 > used[6]
    assert memory["naive"] == (6, 4352)
    # XLA's own section says the same 4352, which no memory holds.
    assert "cumulative: 4352 bytes" in LISTING
    # Each occupied byte once, to the buffer defined last.
    held = {b.name: n for b, n in
            memory_reduce.booked(temporary, 6).items()}
    assert held == {"dynamic-update-slice": 2048, "act.2": 256}
    slot = memory_reduce.slots_at(assignment, 6)[0]
    assert (slot.size, [b.name for b in slot.buffers]) == (
        2048, ["logits", "dynamic-update-slice"])


def test_the_peak_is_booked_by_phase_and_scope(toy):
    _, memory = toy
    # Instant 8: the updated logits 2048, the gradient 1024; the
    # recomputed activation is live but sits in the momentum's allocation.
    assert memory["peak"] == (8, "grad", "bwd", "mlp")
    assert memory["occupied"] == 3072
    assert memory["table"] == {("loss", "bwd"): 2048, ("mlp", "bwd"): 1024}
    assert memory["fragmentation"] == 4096 - 3072
    # All of it a hole: %grad sits at the allocation's end.
    assert memory["reach"] == 4096
    assert "1024" not in memory_reduce.format_table(memory).split(
        "fragmentation: ")[1].split("\n")[0]        # it prints GiB
    six = memory_reduce.metrics(memory)
    gib = memory_reduce.GIB
    assert six == {
        "hbm_state_gib": (1024 + 1024 + 32) / gib,
        "hbm_temp_gib": 4096 / gib, "hbm_peak_fwd_gib": 0.0,
        "hbm_peak_bwd_gib": 3072 / gib, "hbm_peak_update_gib": 0.0,
        "hbm_peak_unplaced_gib": 1024 / gib}


def test_a_temporary_in_a_donated_arguments_allocation_adds_nothing(toy):
    assignment, memory = toy
    momentum = assignment.allocations[1]
    assert momentum.kind == "argument" and momentum.live_out
    assert "act.1.remat" in [b.name for b in momentum.buffers]
    assert (memory["state"], memory["batch"]) == (2048, 32)
    assert memory["arguments"] == {"params": 1024, "opt_state": 1024,
                                   "tokens": 32}
    assert not any(phase == "remat" for _, phase in memory["table"])
    assert memory["phase"]["remat"] == 0
    assert memory["temp"] == 4096


def test_other_memory_spaces_are_left_out(toy):
    assignment, memory = toy
    assert [a.number for a in memory_reduce.hbm_temporaries(
        assignment)] == [6]
    assert memory["other_spaces"] == 8192
    assert (memory["outputs"], memory["constants"],
            memory["thread_local"]) == (16, 4, 4)
    text = memory_reduce.format_table(memory)
    assert "temporaries of other memory spaces (VMEM, flags) 8192" in text


def test_an_unplaced_buffer_is_unattributed(toy):
    assignment, _ = toy
    # %scratch has no op_name and its successor is none: at instant 9 it
    # is booked, under no scope, unattributed.
    temporary, = memory_reduce.hbm_temporaries(assignment)
    held = {b.name: n for b, n in
            memory_reduce.booked(temporary, 9).items()}
    assert held == {"scratch": 1024, "grad": 1024}
    assert scope_reduce.classify("scratch", assignment.hlo)[:2] == (
        "unattributed", "")


def _plateau_toy():
    """One allocation, three plateaus: A + B + base, then C + B + base,
    then D + base."""
    def buffer(name, offset, size, start, end):
        return memory_reduce.Buffer(name, "", offset, size,
                                    f"f32[{size // 4}]", start, end)

    allocation = memory_reduce.Allocation(
        0, 1600, "temporary", None, 0, False, (
            buffer("base", 0, 100, 0, 9), buffer("B", 100, 500, 0, 5),
            buffer("A", 600, 1000, 0, 2), buffer("C", 600, 980, 3, 5),
            buffer("D", 100, 1400, 6, 8)))
    sequence = [f"i.{n}" for n in range(10)]
    return memory_reduce.Assignment(
        [allocation], sequence, scope_reduce.parse_hlo(""), "plateaus")


def test_next_plateaus_on_three_plateaus():
    assignment = _plateau_toy()
    total = memory_reduce.total_occupancy(assignment)
    assert total == [1600] * 3 + [1580] * 3 + [1500] * 3 + [100]
    assert memory_reduce.plateaus(total, within=0.07) == [(0, 8)]
    assert memory_reduce.plateaus(total) == [(0, 5)]
    memory = memory_reduce.reduce(assignment)
    assert memory["peak"][:2] == (0, "i.0")
    assert [s[4] for s in memory["slots"]] == ["A", "B", "base"]
    # Taking A away buys 20 bytes: C + B set the peak then; taking B too,
    # 100: D does.
    assert memory["next"] == [(1, 1580, 3), (2, 1500, 6)]
    text = memory_reduce.format_table(memory)
    assert "the 1 largest slot not live" in text
    assert "the 2 largest slots not live" in text
    assert "no live ranges" not in text
    # No HLO: nothing has a name to be placed by.
    assert memory["phase"]["unattributed"] == 1600


def test_a_slot_handed_on_in_place_is_one_slot_for_the_plateaus(toy):
    assignment, _ = toy
    total = memory_reduce.total_occupancy(assignment)
    slots = memory_reduce.slots_at(assignment, 6)
    held = memory_reduce.chain(assignment, slots[0])
    # logits -> dynamic-update-slice at the same place; %scratch comes to
    # the same offset later and is no part of it.
    assert sorted(b.name for b in held) == ["dynamic-update-slice",
                                            "logits"]
    (n, best, where), = memory_reduce.next_plateaus(
        assignment, total, slots, depth=1)
    assert (n, best, where) == (1, 2048, 9)


def test_a_source_without_live_ranges_gives_sizes_only():
    listing = LISTING[:LISTING.index("HloLiveRange")]
    assignment = memory_reduce.parse_assignment(
        listing, scope_reduce.parse_hlo(HLO))
    assert not memory_reduce.has_live_ranges(assignment)
    memory = memory_reduce.reduce(assignment, 2, 1)
    assert memory["peak"] is None and memory["temp"] == 4096
    assert memory["fragmentation"] == 4096
    assert "no live ranges" in memory_reduce.format_table(memory)


@pytest.mark.parametrize("name,op_name,group", [
    ("params__layers___0___w1__.1", "", "params"),
    ("opt_state_0__trace__embed__.1", "", "opt_state"),
    ("tokens.1", "", "tokens"),
    ("param.40", "params['embed']", "params"),
    ("param.9", "opt_state[0].trace['embed']", "opt_state"),
    ("param.3", "jit(step)/mul", "param"), ("", "", "(unnamed)")])
def test_argument_group(name, op_name, group):
    assert memory_reduce.argument_group(name, op_name) == group
