"""The phase x scope reduction on hand-written HLO and event lists, its
join to a real (CPU) trace file's own HLO, and the readers."""

import glob
import importlib
import json
import os
import re
import subprocess
import sys

import pytest

from perfbench import scope_reduce, trace_reduce

STEP = "jit(hvd_lm_train_step)/"

# What the TPU compiler makes of a step, in small: a forward matmul
# fusion, a weight-gradient fusion whose root is the fused update, the
# same forward fusion rematerialised, a prefetch with no op_name, a
# compiler-made loop with no op_name, the kernels, an all-reduce.
HLO = '''HloModule jit_hvd_lm_train_step, is_scheduled=true

%fused_fwd (p0: bf16[8,64], p1: bf16[64,64]) -> bf16[8,64] {
  %p0 = bf16[8,64]{1,0} parameter(0)
  %p1 = bf16[64,64]{1,0} parameter(1)
  ROOT %convolution.1 = bf16[8,64]{1,0} convolution(%p0, %p1), dim_labels=bf_io->bf, metadata={op_name="jit(hvd_lm_train_step)/jvp(layer_0)/mlp/dot_general"}
}

%fused_wgrad (p0: bf16[8,64], p1: bf16[8,64], p2: f32[64,64]) -> f32[64,64] {
  %p0 = bf16[8,64]{1,0} parameter(0)
  %p1 = bf16[8,64]{1,0} parameter(1)
  %p2 = f32[64,64]{1,0} parameter(2)
  %scale = bf16[8,64]{1,0} multiply(%p0, %p0), metadata={op_name="jit(hvd_lm_train_step)/jvp(layer_0)/mlp/mul"}
  %convolution.2 = f32[64,64]{1,0} convolution(%scale, %p1), dim_labels=fb_io->bf, metadata={op_name="jit(hvd_lm_train_step)/transpose(jvp(layer_0))/mlp/dot_general"}
  ROOT %add.9 = f32[64,64]{1,0} add(%p2, %convolution.2), metadata={op_name="jit(hvd_lm_train_step)/optimizer/add"}
}

%fused_update (p0: f32[64], p1: f32[64]) -> f32[64] {
  %p0 = f32[64]{0} parameter(0)
  %p1 = f32[64]{0} parameter(1)
  ROOT %add.3 = f32[64]{0} add(%p0, %p1), metadata={op_name="jit(hvd_lm_train_step)/optimizer/add"}
}

%loop_body (w: (f32[8,64])) -> (f32[8,64]) {
  %w = (f32[8,64]{1,0}) parameter(0)
  %gte.1 = f32[8,64]{1,0} get-tuple-element(%w), index=0
  %dynamic-update-slice.5 = f32[8,64]{1,0} dynamic-update-slice(%gte.1, %gte.1)
  ROOT %tuple.2 = (f32[8,64]{1,0}) tuple(%dynamic-update-slice.5)
}

%loop_cond (w: (f32[8,64])) -> pred[] {
  %w.1 = (f32[8,64]{1,0}) parameter(0)
  ROOT %lt = pred[] constant(false)
}

ENTRY %main (a: bf16[8,64], b: bf16[64,64], c: f32[64,64], d: f32[64]) -> f32[64,64] {
  %a = bf16[8,64]{1,0} parameter(0), metadata={op_name="tokens"}
  %b = bf16[64,64]{1,0} parameter(1), metadata={op_name="params['embed']"}
  %c = f32[64,64]{1,0} parameter(2)
  %d = f32[64]{0} parameter(3)
  %copy-start.1 = (bf16[64,64]{1,0}, bf16[64,64]{1,0}, u32[]) copy-start(%b)
  %copy-done.1 = bf16[64,64]{1,0} copy-done(%copy-start.1)
  %fusion.1 = bf16[8,64]{1,0} fusion(%a, %copy-done.1), kind=kOutput, calls=%fused_fwd, metadata={op_name="jit(hvd_lm_train_step)/jvp(layer_0)/mlp/dot_general"}
  %flash_fwd.6 = bf16[8,64]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", metadata={op_name="jit(hvd_lm_train_step)/jvp(layer_0)/attn/flash_attention/flash_fwd/pallas_call"}
  %copy.7 = bf16[8,64]{0,1} copy(%flash_fwd.6), metadata={op_name="jit(hvd_lm_train_step)/jvp(layer_0)/attn/flash_attention/transpose"}
  %tuple.1 = (f32[8,64]{1,0}) tuple(%copy.7)
  %while.2 = (f32[8,64]{1,0}) while(%tuple.1), condition=%loop_cond, body=%loop_body
  %gte.2 = f32[8,64]{1,0} get-tuple-element(%while.2), index=0
  %reduce.4 = f32[] reduce(%gte.2, %gte.2), to_apply=%loop_cond, metadata={op_name="jit(hvd_lm_train_step)/jvp(loss)/reduce_sum"}
  %flash_bwd_dq.6 = bf16[8,64]{1,0} custom-call(%copy.7), custom_call_target="tpu_custom_call", metadata={op_name="jit(hvd_lm_train_step)/transpose(jvp(layer_0))/attn/flash_attention/flash_bwd_dq/pallas_call"}
  %fusion.1.remat = bf16[8,64]{1,0} fusion(%a, %copy-done.1), kind=kOutput, calls=%fused_fwd, metadata={op_name="jit(hvd_lm_train_step)/jvp(layer_0)/mlp/dot_general"}
  %fusion.2 = f32[64,64]{1,0} fusion(%fusion.1.remat, %flash_bwd_dq.6, %c), kind=kOutput, calls=%fused_wgrad, metadata={op_name="jit(hvd_lm_train_step)/optimizer/add"}
  %psum_invariant.3 = f32[64,64]{1,0} all-reduce(%fusion.2), channel_id=1, to_apply=%loop_cond, metadata={op_name="jit(hvd_lm_train_step)/shard_map/grad_mean/psum_invariant"}
  %fusion.3 = f32[64]{0} fusion(%d, %d), kind=kLoop, calls=%fused_update, metadata={op_name="jit(hvd_lm_train_step)/optimizer/add"}
  %mystery.1 = f32[64]{0} negate(%d)
  ROOT %out = f32[64,64]{1,0} copy(%psum_invariant.3)
}
'''


@pytest.mark.parametrize("op_name,name,opcode,phase", [
    (STEP + "jvp(layer_0)/mlp/dot_general", "fusion.1", "fusion", "fwd"),
    (STEP + "transpose(jvp(layer_0))/jvp(layer_0)/checkpoint/mlp/mul",
     "fusion.2", "fusion", "bwd"),
    (STEP + "transpose(jvp(layer_0))/jvp(layer_0)/checkpoint/"
     "rematted_computation/mlp/tanh", "fusion.3", "fusion", "remat"),
    (STEP + "jvp(layer_0)/mlp/dot_general", "fusion.7.remat", "fusion",
     "remat"),
    (STEP + "jvp(layer_0)/mlp/dot_general", "fusion.7.remat2", "fusion",
     "remat"),
    (STEP + "shard_map/grad_mean/psum_invariant", "x", "all-reduce",
     "grad_mean"),
    (STEP + "shard_map/grad_mean/mul", "x", "fusion", "grad_mean"),
    (STEP + "shard_map/loss_mean/psum", "x", "all-reduce", "grad_mean"),
    # A collective is the gradient mean's by opcode alone.
    (STEP + "shard_map/grad_reduce_scatter/psum_scatter", "x",
     "reduce-scatter", "grad_mean"),
    ("", "all-gather-start.1", "all-gather-start", "grad_mean"),
    (STEP + "shard_map/grad_reduce_scatter/concatenate", "x", "fusion",
     "optimizer"),
    (STEP + "shard_map/param_all_gather/reshape", "x", "fusion",
     "optimizer"),
    (STEP + "shard_map/step_guard/select_n", "x", "fusion", "optimizer"),
    (STEP + "optimizer/add", "x", "fusion", "optimizer"),
    (STEP + "embed/gather", "x", "fusion", "fwd"),
    ("jit(_one_step)/jvp()/dot_general", "x", "fusion", "fwd"),
    ("jit(_one_step)/transpose(jvp())/dot_general", "x", "fusion", "bwd"),
    ("jit(_one_step)/add", "x", "fusion", "unattributed"),
    ("params['optimizer']", "x", "parameter", "unattributed"),
    ("", "copy.5", "copy", "unattributed"),
])
def test_phase_rules(op_name, name, opcode, phase):
    assert scope_reduce.phase_of(op_name, name, opcode) == phase


@pytest.mark.parametrize("op_name,scope", [
    (STEP + "jvp(layer_3)/attn/qkv/dot_general", "attn/qkv"),
    (STEP + "transpose(jvp(layer_3))/attn/flash_attention/flash_bwd_dq/"
     "pallas_call", "attn/flash_attention"),
    (STEP + "jvp(layer_0)/attn/ulysses_attention/attn/flash_attention/mul",
     "attn/flash_attention"),
    (STEP + "jvp(layer_0)/attn/ring_flash_attention/while",
     "attn/ring_flash_attention"),
    (STEP + "jvp(head)/dot_general", "head"),
    (STEP + "transpose(jvp(loss))/jit(log_softmax)/sub", "loss"),
    (STEP + "jvp(layer_1)/remat2", "layer"),
    ("jit(hvd_train_step)/shard_map/jvp(ResNet)/BottleneckBlock_3/Conv_0/"
     "conv_general_dilated", "ResNet/BottleneckBlock_3"),
    ("jit(hvd_train_step)/shard_map/transpose(jvp(ResNet))/conv_init/"
     "conv_general_dilated", "ResNet/conv_init"),
    ("jit(hvd_train_step)/shard_map/jvp(ResNet)/reduce_window_max",
     "ResNet"),
    ("opt_state[0].trace['embed']", ""),
    ("jit(_one_step)/jvp()/dot_general", ""),
])
def test_scope_of(op_name, scope):
    assert scope_reduce.scope_of(op_name) == scope


def _classify(name):
    return scope_reduce.classify(name, scope_reduce.parse_hlo(HLO))


def test_a_fusion_is_booked_by_its_matmul_not_its_root():
    phase, scope, kernel, inside = _classify("fusion.2")
    assert (phase, scope, kernel) == ("bwd", "mlp", None)
    assert inside == {"fwd", "bwd", "optimizer"}          # mixed
    assert _classify("fusion.3")[:2] == ("optimizer", "optimizer")
    assert _classify("fusion.3")[3] == {"optimizer"}


def test_xla_rematerialisation_is_found_by_the_instruction_name():
    assert _classify("fusion.1")[:2] == ("fwd", "mlp")
    phase, scope, _, inside = _classify("fusion.1.remat")
    assert (phase, scope, inside) == ("remat", "mlp", {"remat"})


def test_an_instruction_without_op_name_goes_where_it_is_needed():
    # The prefetch's wait is booked to the fusion that reads it.
    assert _classify("copy-done.1")[:2] == ("fwd", "mlp")
    assert _classify("copy-start.1")[:2] == ("fwd", "mlp")
    # A compiler-made loop: body -> root -> the while -> its user.
    assert _classify("dynamic-update-slice.5")[:2] == ("fwd", "loss")
    assert _classify("while.2")[:2] == ("fwd", "loss")
    # Nothing to inherit from, and not in the HLO at all.
    assert _classify("mystery.1")[:2] == ("unattributed", "")
    assert _classify("fusion.999") == ("unattributed", "", None,
                                       frozenset())


def test_kernels_are_found_by_their_names():
    assert _classify("flash_fwd.6")[:3] == (
        "fwd", "attn/flash_attention", "flash_fwd")
    assert _classify("flash_bwd_dq.6")[:3] == (
        "bwd", "attn/flash_attention", "flash_bwd_dq")
    assert _classify("copy.7")[:3] == ("fwd", "attn/flash_attention", None)


def _events():
    """Two steps on each of two devices, one op after the other."""
    order = ["copy-done.1", "fusion.1", "flash_fwd.6", "copy.7", "while.2",
             "reduce.4", "flash_bwd_dq.6", "fusion.1.remat", "fusion.2",
             "psum_invariant.3", "fusion.3", "mystery.1"]
    ms = {"copy-done.1": 1, "fusion.1": 10, "flash_fwd.6": 4, "copy.7": 2,
          "while.2": 3, "reduce.4": 1, "flash_bwd_dq.6": 5,
          "fusion.1.remat": 10, "fusion.2": 20, "psum_invariant.3": 8,
          "fusion.3": 2, "mystery.1": 1}
    instructions = scope_reduce.parse_hlo(HLO).instructions
    events, at = [], 0.0
    for _ in range(2):
        for name in order:
            i = instructions[name]
            text = f"%{name} = {i.shape}{{1,0}} {i.opcode}(%x)"
            if i.opcode == "custom-call":
                text += ', custom_call_target="tpu_custom_call"'
            events.append((text, at, at + ms[name] * 1e6))
            at += ms[name] * 1e6
    # The loop's body runs inside the while's span.
    start = next(s for t, s, _ in events if t.startswith("%while.2"))
    events.append(("%dynamic-update-slice.5 = f32[8,64]{1,0} "
                   "dynamic-update-slice(%gte.1)", start, start + 2e6))
    return {"/device:TPU:0": events, "/device:TPU:1": list(events)}


def _attribute(reduced):
    return scope_reduce.attribute(reduced["op_s"],
                                  scope_reduce.parse_hlo(HLO))


def test_identity_and_table_on_a_hand_built_event_list():
    reduced = trace_reduce.reduce_events(
        _events(), [], {"flash": ['custom_call_target="tpu_custom_call"']})
    scopes = _attribute(reduced)
    ms = {p: v * 1e3 / 2 for p, v in scopes["phase_s"].items()}
    assert ms == pytest.approx({
        "fwd": 1 + 10 + 4 + 2 + 3 + 1, "bwd": 5 + 20, "remat": 10,
        "optimizer": 2, "grad_mean": 8, "unattributed": 1})
    # fwd + bwd + remat + optimizer + grad_mean + unattributed =
    # xla_ms_per_step + flash_ms_per_step + collective_ms_per_step.
    kernels = sum(reduced["kernel_s"].values())
    xla = (sum(reduced["op_s"].values()) - kernels
           - reduced["collective_s"])
    assert sum(scopes["phase_s"].values()) == pytest.approx(
        xla + kernels + reduced["collective_s"], rel=1e-9)
    assert kernels * 1e3 / 2 == pytest.approx(9)
    assert {k: v * 1e3 / 2 for k, v in scopes["kernel_s"].items()} == (
        pytest.approx({"flash_fwd": 4, "flash_bwd_dq": 5}))
    table = {k: v * 1e3 / 2 for k, v in scopes["table"].items()}
    assert table[("mlp", "bwd")] == pytest.approx(20)
    assert table[("mlp", "remat")] == pytest.approx(10)
    assert table[("attn/flash_attention", "fwd")] == pytest.approx(6)
    # The while's self time (3 - 2 of its child) and the child: loss.
    assert table[("loss", "fwd")] == pytest.approx(4)
    assert table[("(no scope)", "unattributed")] == pytest.approx(1)
    assert scopes["mixed_s"] == pytest.approx({"fwd+bwd+optimizer": 0.04})
    assert scopes["unjoined_s"] == 0 and scopes["has_scopes"]
    text = scope_reduce.format_table(dict(scopes, modules=["m"]), 2)
    assert "mixed" in text and "fwd+bwd+optimizer 20.000" in text
    assert text.splitlines()[1].split() == (
        ["scope"] + list(scope_reduce.PHASES) + ["total"])


def test_collectives_are_counted_with_their_bytes():
    calls, total = scope_reduce.collectives(_events())
    assert (calls, total) == (2, 2 * 64 * 64 * 4)
    pair = {"/device:TPU:0": [
        ("%ag-start = (bf16[8], bf16[32]) all-gather-start(%x)", 0, 1),
        ("%ag-done = bf16[32]{0} all-gather-done(%ag-start)", 1, 2)]}
    assert scope_reduce.collectives(pair) == (1, 64)


@pytest.mark.parametrize("shape,size", [
    ("f32[16384,4096]", 16384 * 4096 * 4),
    ("(f32[4,2048], bf16[4,2048,4096])", 4 * 2048 * 4 + 4 * 2048 * 4096 * 2),
    ("pred[7]", 7), ("f32[]", 4), ("f8e4m3fn[16]", 16), ("()", 0),
])
def test_shape_bytes(shape, size):
    assert scope_reduce.shape_bytes(shape) == size


def test_a_program_without_scopes_reads_phases_but_no_scope():
    parent = re.sub(r"layer_\d+|loss", "", HLO).replace(
        "hvd_lm_train_step", "_one_step")
    for scope in ("attn/flash_attention/flash_fwd/",
                  "attn/flash_attention/flash_bwd_dq/",
                  "attn/flash_attention/", "mlp/", "optimizer/",
                  "grad_mean/"):
        parent = parent.replace(scope, "")
    reduced = trace_reduce.reduce_events(_events(), [], None)
    scopes = scope_reduce.attribute(reduced["op_s"],
                                    scope_reduce.parse_hlo(parent))
    assert not scopes["has_scopes"] and not scopes["kernel_s"]
    assert scopes["phase_s"]["fwd"] > 0 and scopes["phase_s"]["bwd"] > 0
    assert scopes["phase_s"]["remat"] == pytest.approx(0.020)


# --- against a real trace file and through the readers ---------------------

NEW_METRICS = [
    "fwd_ms_per_step", "bwd_ms_per_step", "remat_ms_per_step",
    "optimizer_ms_per_step", "head_ms_per_step", "flash_glue_ms_per_step",
    "flash_fwd_ms_per_step", "flash_dq_ms_per_step",
    "flash_dkv_ms_per_step", "collective_calls_per_step",
    "collective_gb_per_step", "unattributed_ms_per_step"]
PR22_METRICS = [
    "lower_s", "compile_s", "dispatch_ms", "xla_ms_per_step",
    "flash_ms_per_step", "flash_roofline", "collective_ms_per_step",
    "exposed_collective_ms_per_step", "device_idle_pct"]


def _read(metric, ctx):
    return importlib.import_module(
        "perfbench.layer_metrics." + metric).read(ctx)


TOY_STEPS = """
import sys
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

step = jax.jit(hvd_toy_step)
w, x = jnp.ones((128, 128)), jnp.ones((64, 128))
w, _ = step(w, x)
jax.profiler.start_trace(sys.argv[1])
for _ in range(3):
    w, value = step(w, x)
jax.block_until_ready(value)
jax.profiler.stop_trace()
"""


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory):
    """A trace of three tiny named steps on the CPU, taken in a process
    of its own (a trace file describes every module its process knows):
    no device plane, but ``/host:metadata`` holds the step's HLO."""
    directory = str(tmp_path_factory.mktemp("trace"))
    subprocess.run([sys.executable, "-c", TOY_STEPS, directory], check=True,
                   env=dict(os.environ, JAX_PLATFORMS="cpu"))
    return glob.glob(os.path.join(directory, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]


def test_the_trace_file_holds_the_executed_hlo_with_its_names(cpu_trace):
    texts = [t for t in scope_reduce.trace_hlo(cpu_trace)
             if t.startswith("HloModule jit_hvd_toy_step")]
    assert len(texts) == 1
    names = {i.op_name for i in
             scope_reduce.parse_hlo(texts[0]).instructions.values()}
    assert any("jvp(mlp)/dot_general" in n for n in names)
    assert any("transpose(jvp(mlp))" in n for n in names)
    assert any(n.endswith("optimizer/sub") for n in names)
    assert "'XLA Ops'" not in scope_reduce.event_stat_names(cpu_trace)
    assert "/host:metadata" in scope_reduce.event_stat_names(cpu_trace)


def _ctx(cpu_trace, **extra):
    """A ctx as run.py makes it, its reduction hand-built over the real
    trace file's own instructions (a CPU trace has no device events)."""
    text = next(t for t in scope_reduce.trace_hlo(cpu_trace)
                if t.startswith("HloModule jit_hvd_toy_step"))
    instructions, computations, _ = scope_reduce.parse_hlo(text)
    entry = next(names for c, names in computations.items()
                 if c.startswith("main"))
    op_s = {f"%{n} {instructions[n].opcode} {instructions[n].shape}": 0.003
            for n in entry if instructions[n].opcode != "parameter"}
    reduced = {"devices": 1, "window_s": 1.0, "busy_s": 0.9,
               "collective_s": 0.0, "exposed_collective_s": 0.0,
               "op_s": op_s, "kernel_s": {}, "idle_gaps": {}}
    return dict({"reduced": reduced, "trace_steps": 3,
                 "timings": {"lower_s": 1.0, "compile_s": 2.0},
                 "dispatch_s": [0.001, 0.002, 0.003], "cell": None,
                 "peaks": {}}, **extra)


def test_readers_of_the_new_metrics(cpu_trace, capsys):
    ctx = _ctx(cpu_trace, trace_file=cpu_trace)
    values = {m: _read(m, ctx) for m in NEW_METRICS}
    out = capsys.readouterr().out
    assert out.count("scopes: module jit_hvd_toy_step") == 1   # made once
    assert "identity: " in out and "difference 0.0000%" in out
    for metric in ("fwd_ms_per_step", "bwd_ms_per_step"):
        assert values[metric] > 0
    assert values["remat_ms_per_step"] == 0
    assert values["optimizer_ms_per_step"] > 0
    assert values["head_ms_per_step"] > 0                      # the loss
    assert values["flash_glue_ms_per_step"] == 0
    for metric in ("flash_fwd_ms_per_step", "flash_dq_ms_per_step",
                   "flash_dkv_ms_per_step", "collective_calls_per_step",
                   "collective_gb_per_step"):
        assert values[metric] is None
    total = sum(values[m] for m in (
        "fwd_ms_per_step", "bwd_ms_per_step", "remat_ms_per_step",
        "optimizer_ms_per_step", "unattributed_ms_per_step"))
    assert total == pytest.approx(
        sum(ctx["reduced"]["op_s"].values()) * 1e3 / 3)
    json.dumps(values)


def test_readers_return_none_where_there_is_nothing_to_read(cpu_trace):
    empty = {"reduced": {}, "trace_steps": 3}
    no_hlo = dict(_ctx(cpu_trace), trace_file=os.devnull)
    for metric in NEW_METRICS:
        assert _read(metric, empty) is None
        assert _read(metric, no_hlo) is None


def test_the_metrics_of_pr22_read_the_same_with_the_new_ctx_keys(cpu_trace):
    """New keys of ``ctx`` (a later harness may hand over the trace file
    and the compiled step) change nothing for the nine older readers."""
    plain = _ctx(cpu_trace)
    plain["reduced"]["kernel_s"] = {"flash": 0.006}
    plain["reduced"]["devices"] = 2
    plain["reduced"]["collective_s"] = 0.012
    plain["reduced"]["exposed_collective_s"] = 0.009
    plain["peaks"] = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}

    class Cell:
        kernels = {"flash": {"flops": 1e9, "bytes": 1e6, "match": []}}

    plain["cell"] = Cell()
    richer = dict(plain, trace_file=cpu_trace, compiled=object())
    before = {m: _read(m, plain) for m in PR22_METRICS}
    after = {m: _read(m, richer) for m in PR22_METRICS}
    assert before == after
    assert all(v is not None for v in before.values())
