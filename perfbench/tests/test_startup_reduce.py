"""``perfbench/startup_reduce.py``: the seven readers on a hand-built report
(the cut, the union, a step traced twice, a run with no cache) and on a
rehearsal of one LM cell and of the ResNet cell in a process of its own; and
every ``per_layer`` name of BENCHMARK.json has a reader."""

import importlib
import json
import math
import os
import subprocess
import sys

import pytest

from perfbench import run, startup_reduce

NAMES = ("hvd_import_s", "hvd_init_s", "step_trace_s", "step_mlir_s",
         "other_programs_s", "programs_built", "setup_in_program_pct")
STEP = "hvd_lm_train_step"


def _span(i, name, t0, t1, parent=None):
    return {"id": i, "name": name, "t0": t0, "t1": t1, "parent": parent,
            "attrs": {}}


def _row(fun, stage, t0, t1, cache="none", read=0.0):
    return {"fun_name": fun, "stage": stage, "t0": t0, "t1": t1,
            "cache": cache, "cache_read_s": read, "parent": None,
            "role": "step" if fun == STEP else None}


# import ends at 100; the stages after it are laid end to end from there:
# tpu_start 100-107, mesh 107-108, build 108-110, init 110-114, lower
# 114-117, compile 117-118, check 118-124, warmup 124-125.
TIMINGS = {"import_s": 3.0, "tpu_start_s": 7.0, "mesh_s": 1.0,
           "build_s": 2.0, "init_s": 4.0, "lower_s": 3.0, "compile_s": 1.0,
           "check_s": 6.0, "warmup_s": 1.0}


def report(cache="hit"):
    return {
        "clock": "monotonic", "now": 200.0,
        "spans": [
            _span(1, "import", 99.5, 100.0),
            _span(2, "init", 107.0, 107.5),
            _span(3, "init/backend", 107.1, 107.4, parent=2),
            _span(4, "build_mesh", 107.5, 107.75),
            _span(5, "make_train_step", 108.0, 108.25),
        ],
        "parts": {"attention": {"count": 3, "seconds": 0.5}},
        "nested_traces": {"add": {"count": 400, "seconds": 0.01}},
        "compiles": [
            # The state's program: inside init_s.
            _row("make", "trace", 110.0, 110.5),
            _row("make", "mlir", 110.5, 111.0),
            _row("make", "backend_compile", 111.0, 112.0, cache, 0.75),
            # The step, traced twice (1.5 + 0.25 s), one lowering.
            _row(STEP, "trace", 114.0, 115.5),
            # A helper's program made while the step is traced: inside it.
            _row("helper", "trace", 114.5, 114.625),
            _row("helper", "backend_compile", 114.625, 114.75, cache, 0.1),
            _row(STEP, "trace", 115.5, 115.75),
            _row(STEP, "mlir", 115.75, 116.75),
            _row(STEP, "backend_compile", 117.0, 118.0, cache, 0.5),
            # The reference's program: inside check_s.
            _row("reference", "trace", 118.0, 119.0),
            _row("reference", "backend_compile", 119.0, 121.0, cache, 1.0),
            # After set-up: the memory readers compile the step once more,
            # and a row that straddles the cut is cut at it (124.5-125).
            _row("late", "trace", 124.5, 126.0),
            _row(STEP, "backend_compile", 130.0, 131.0, cache, 0.5),
        ],
        "cache": {"dir": "/c", "bytes": 100, "entries": 4, "cap_bytes": 400},
        "dropped": {"spans": 0, "compiles": 0},
    }


def test_the_stages_are_laid_end_to_end_from_the_import_span():
    laid = startup_reduce.stages_of(report(), TIMINGS)
    assert laid[0] == ("import_s", 97.0, 100.0)
    assert laid[1] == ("tpu_start_s", 100.0, 107.0)
    assert laid[-1] == ("warmup_s", 124.0, 125.0)
    assert startup_reduce.stages_of({"spans": [], "compiles": []},
                                    TIMINGS) is None


def test_the_seven_metrics_on_a_hand_built_report():
    reduced = startup_reduce.reduce(report(), TIMINGS)
    m = reduced["metrics"]
    assert m["hvd_import_s"] == 0.5
    assert m["hvd_init_s"] == 1.0          # init + build_mesh + make_...
    assert m["step_trace_s"] == 1.75       # traced twice: a union
    assert m["step_mlir_s"] == 1.0
    # make 2.0 + reference 3.0 + late 0.5 inside the cut; the helper lies
    # inside the step's trace and is the step's.
    assert m["other_programs_s"] == 5.5
    assert m["programs_built"] == 4.0      # the late step compile is out
    assert reduced["step_compile_s"] == 1.0
    # Inside rows: import 0.5 + init/mesh 0.75 + make_train_step 0.25
    # + make 2 + the step 2.75 + 1 + reference 3 + late 0.5 = 10.75 of
    # the 21 s that are set-up (28 less tpu_start_s).
    assert reduced["setup_s"] == 21.0
    assert m["setup_in_program_pct"] == pytest.approx(100 * 10.75 / 21.0)
    assert reduced["cache_states"] == {"hit": 4}
    assert reduced["cache_read_s"] == 2.35


def test_the_table_closes_stage_by_stage(capsys):
    reduced = startup_reduce.reduce(report(), TIMINGS)
    by_name = {s["name"]: s for s in reduced["stages"]}
    for stage in reduced["stages"]:
        assert stage["covered_s"] + stage["uncovered_s"] == pytest.approx(
            stage["seconds"])
        assert stage["seconds"] == TIMINGS[stage["name"]]
    assert by_name["lower_s"]["covered_s"] == 2.75
    assert by_name["tpu_start_s"]["covered_s"] == 0.0
    assert by_name["warmup_s"]["covered_s"] == 0.5     # late, cut at 125
    assert by_name["mesh_s"]["rows"][0][:2] == ("span init", 0.5)
    text = startup_reduce.format_table(reduced, TIMINGS)
    assert text.startswith("startup:")
    assert "trace + lowering 2.750 against the harness's lower_s 3.000" in text
    assert "4 read from the cache" in text and "25.0% full" in text
    assert "attention 0.500 s x3" in text and "add 0.010 x400" in text


def test_a_run_with_no_cache_reads_the_same_seconds():
    reduced = startup_reduce.reduce(report(cache="none"), TIMINGS)
    assert reduced["cache_states"] == {"none": 4}
    assert reduced["metrics"]["programs_built"] == 4.0
    assert reduced["built_s"]["none"] == pytest.approx(4.125)
    no_dir = dict(report(cache="none"), cache=None)
    assert "compile cache at the start" not in startup_reduce.format_table(
        startup_reduce.reduce(no_dir, TIMINGS), TIMINGS)


def test_every_reader_gives_a_finite_number_above_zero(capsys):
    ctx = {"timings": dict(TIMINGS), "startup_report": report()}
    for name in NAMES:
        value = importlib.import_module(
            "perfbench.layer_metrics." + name).read(ctx)
        assert math.isfinite(value) and value > 0, name
    assert capsys.readouterr().out.count("startup:") == 1   # memoised
    # What can read 0 is left out of the line, never printed as 0.
    empty = dict(report(), compiles=[])
    assert startup_reduce.metric(
        {"timings": dict(TIMINGS), "startup_report": empty},
        "step_trace_s") is None


def test_a_program_without_the_report_leaves_the_metrics_out(monkeypatch):
    """The parent of the PR that brought the report: no reader raises."""
    import horovod_tpu as hvd

    monkeypatch.delattr(hvd, "startup_report")
    ctx = {"timings": dict(TIMINGS)}
    for name in NAMES:
        assert importlib.import_module(
            "perfbench.layer_metrics." + name).read(ctx) is None


def test_every_per_layer_name_has_a_reader():
    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    ours = [m for m in bench["per_layer"] if m["name"] in NAMES]
    assert [m["name"] for m in ours] == list(NAMES)
    for m in ours:
        assert (m["layer"], m["moves"], m["source"]) == (
            "start-up and mesh", "setup_s", "host_clock")
        assert "workloads" not in m
    for m in bench["per_layer"]:
        reader = importlib.import_module("perfbench.layer_metrics."
                                         + m["name"])
        assert callable(reader.read), m["name"]


# A rehearsal in a process of its own, as on the chip; the harness prints
# no metric in rehearsal, so the readers are run on its stages afterwards.
REHEARSE = """
import contextlib, importlib, io, json, re, sys
from perfbench import run
out = io.StringIO()
with contextlib.redirect_stdout(out):
    code = run.main(["--workload", sys.argv[1], "--seed", "3", "--seconds",
                     "1", "--trace", "1", "--rehearse-cpu"])
assert code == 0, out.getvalue()[-2000:]
stages = next(line for line in out.getvalue().splitlines()
              if line.startswith("set-up: ")).split(";")[0]
ctx = {"timings": {k: float(v)
                   for k, v in re.findall(r"(\\w+_s) ([\\d.]+)", stages)}}
print(json.dumps({name: importlib.import_module(
    "perfbench.layer_metrics." + name).read(ctx) for name in sys.argv[2:]}))
"""


@pytest.mark.parametrize("workload", ["gpt67_t8192", "resnet50_b256"])
def test_the_readers_on_a_rehearsal(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=run.ROOT,
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, "-c", REHEARSE, workload, *NAMES], env=env,
        capture_output=True, text=True, timeout=600, cwd=run.ROOT)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = done.stdout.strip().splitlines()
    values = json.loads(lines[-1])
    for name in NAMES:
        assert math.isfinite(values[name]) and values[name] > 0, name
    assert values["programs_built"] >= 3     # the state, the step, the check
    assert 0 < values["setup_in_program_pct"] <= 100
    table = [line for line in lines if line.startswith("  ")]
    stages = [line.split()[0] for line in table if line.split()[0].endswith(
        "_s") and line.split()[0] in ("import_s", "tpu_start_s", "mesh_s",
                                       "build_s", "init_s", "lower_s",
                                       "compile_s", "check_s", "warmup_s")]
    assert stages == ["import_s", "tpu_start_s", "mesh_s", "build_s",
                      "init_s", "lower_s", "compile_s", "check_s",
                      "warmup_s"]
    # The step's own rows explain the harness's two laps around it.
    closing = next(line for line in lines if "the step: trace + lowering"
                   in line)
    assert "against the harness's lower_s" in closing
