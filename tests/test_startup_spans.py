"""The start-up's host side (``telemetry/spans.py``, "Start-up"): the one
host-span primitive, the compile ledger made from JAX's own monitoring
events, and ``hvd.startup_report()``, which reads both.
"""

import glob
import importlib
import json
import os
import subprocess
import sys
import threading

import jax
import jax.numpy as jnp
import optax
import pytest

from horovod_tpu import telemetry
from horovod_tpu.telemetry import scopes

spans = importlib.import_module("horovod_tpu.telemetry.spans")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def fresh():
    """No phase span, total or ledger row of an earlier test."""
    spans.reset_startup_for_tests()
    yield
    spans.reset_startup_for_tests()


@pytest.fixture()
def enabled_telemetry():
    telemetry.registry().clear()
    telemetry.configure(enabled_flag=True)
    yield telemetry
    telemetry.configure(enabled_flag=False)
    telemetry.registry().clear()


def _by_name(report):
    return {s["name"]: s for s in report["spans"]}


def _rows(report, fun, since=0):
    return [(r["stage"], r["role"]) for r in report["compiles"][since:]
            if r["fun_name"] == fun]


def test_span_records_parent_and_attributes(fresh):
    with telemetry.span("outer", axes=("data",)) as outer:
        with telemetry.span("outer/inner"):
            pass
        outer.attrs["devices"] = 4
    with telemetry.span("next"):
        pass
    got = _by_name(telemetry.startup_report())
    assert got["outer"]["parent"] is None
    assert got["outer/inner"]["parent"] == got["outer"]["id"]
    assert got["next"]["parent"] is None      # outer had closed
    assert got["outer"]["attrs"] == {"axes": ("data",), "devices": 4}
    assert (got["outer"]["t0"] <= got["outer/inner"]["t0"]
            <= got["outer/inner"]["t1"] <= got["outer"]["t1"])


def test_span_as_a_decorator_is_one_record_a_call(fresh):
    @telemetry.span("build", step="s")
    def build(x):
        return x + 1

    assert build(1) == 2 and build(2) == 3
    named = [s for s in telemetry.startup_report()["spans"]
             if s["name"] == "build"]
    assert len(named) == 2 and named[0]["id"] != named[1]["id"]
    assert named[0]["attrs"] == {"step": "s"}


def test_a_span_on_another_thread_has_its_own_parent(fresh):
    """The parent is the span open **on this thread**: a thread's spans
    nest among themselves and never under another thread's."""
    def worker():
        with telemetry.span("worker"):
            with telemetry.span("worker/child"):
                pass

    with telemetry.span("main"):
        thread = threading.Thread(target=worker)
        thread.start()
        thread.join()
        with telemetry.span("main/child"):
            pass
    got = _by_name(telemetry.startup_report())
    assert got["worker"]["parent"] is None
    assert got["worker/child"]["parent"] == got["worker"]["id"]
    assert got["main/child"]["parent"] == got["main"]["id"]


def test_phase_spans_are_kept_with_every_variable_unset(fresh, monkeypatch):
    """No switch: the phase spans are there with telemetry off, and the
    per-collective recorder's no-op contract stands beside them."""
    for var in ("HOROVOD_TRACE", "HOROVOD_TRACE_DIR", "HOROVOD_TRACE_RPC",
                "HOROVOD_METRICS", "HOROVOD_METRICS_FILE",
                "HOROVOD_METRICS_PORT", "HOROVOD_METRICS_RPC"):
        monkeypatch.delenv(var, raising=False)
    telemetry.reset_for_tests()
    assert telemetry.spans() is None and not telemetry.enabled()
    with telemetry.span("build_mesh"):
        pass
    assert "build_mesh" in _by_name(telemetry.startup_report())
    assert telemetry.spans() is None
    assert telemetry.metrics_snapshot() == {}


def test_with_the_recorder_on_a_phase_span_is_in_its_document(
        fresh, monkeypatch):
    recorder = spans.SpanRecorder(rank=0)
    monkeypatch.setattr(telemetry, "_spans", recorder)
    recorder.record("grad/dense0", "wait", 0, 1.0, 2.0, 64)
    with telemetry.span("init") as init:
        with telemetry.span("init/backend", platform="cpu"):
            pass
    doc = recorder.document()
    assert doc["schema"] == "horovod_tpu.trace.v1"
    by_name = {s["name"]: s for s in doc["spans"]}
    child = by_name["init/backend"]
    assert child["phase"] == spans.STARTUP_PHASE
    assert child["parent"] == init.id == by_name["init"]["seq"]
    assert child["attrs"] == {"platform": "cpu"}
    assert by_name["init"]["parent"] is None
    # A collective's record carries neither optional field.
    assert "parent" not in by_name["grad/dense0"]
    # The merger shows it, and the critical path leaves it out.
    from horovod_tpu.telemetry import critical_path, trace_merge
    events = trace_merge.spans_doc_to_events(doc)
    shown = next(e for e in events if e["name"] == "init/backend:startup")
    assert shown["args"]["parent"] == init.id
    found = critical_path.analyze({0: doc})
    assert found["steps"] == 1
    assert [s["name"] for s in found["slowest_steps"]] == ["grad/dense0"]


def test_init_twice_registers_one_listener(hvd, fresh):
    from jax._src import monitoring

    def ours(listeners):
        return [f for f in listeners
                if getattr(f, "__module__", "") == spans.__name__]

    hvd.init()
    hvd.shutdown()
    hvd.init()
    assert len(ours(monitoring.get_event_duration_listeners())) == 1
    assert len(ours(monitoring.get_event_listeners())) == 1
    # An init that finds the process initialized opens no span.
    before = len(telemetry.startup_report()["spans"])
    hvd.init()
    assert len(telemetry.startup_report()["spans"]) == before


def test_init_and_build_mesh_open_their_spans(fresh):
    import horovod_tpu as hvd
    from horovod_tpu.topology import build_mesh

    hvd.shutdown()
    hvd.init()
    try:
        build_mesh(axes=("data", "model"), shape=(2, 2),
                   devices=jax.devices()[:4])
        got = _by_name(hvd.startup_report())
        assert got["init"]["attrs"] == {"rank": 0, "size": 1}
        assert got["init/backend"]["parent"] == got["init"]["id"]
        assert got["build_mesh"]["attrs"] == {
            "axes": ("data", "model"), "shape": (2, 2), "devices": 4,
            "platform": "cpu"}
    finally:
        hvd.shutdown()


def test_the_package_records_its_own_import():
    """``import horovod_tpu`` top to bottom is the first span a process
    has, with nothing set and nothing called."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("HOROVOD_")}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c",
         "import json, time; t = time.monotonic(); import horovod_tpu as h;"
         " print(json.dumps([t, h.startup_report()]))"],
        env=env, cwd=ROOT, capture_output=True, text=True, check=True)
    began, report = json.loads(out.stdout.splitlines()[-1])
    (only,) = report["spans"]
    assert only["name"] == "import" and only["parent"] is None
    assert began <= only["t0"] < only["t1"] <= report["now"]
    assert report["compiles"] == [] and report["cache"] is None


def test_a_second_shape_is_a_recompile_under_the_functions_name(hvd, fresh):
    """What an operator looks for after a slow step: the rows of the
    program that was made, by name."""
    @jax.jit
    def hvd_test_recompiled(x):
        return x * 2 + 1

    hvd_test_recompiled(jnp.ones((4,))).block_until_ready()
    first = len(telemetry.startup_report()["compiles"])
    assert _rows(telemetry.startup_report(), "hvd_test_recompiled") == [
        ("trace", None), ("mlir", None), ("backend_compile", None)]
    hvd_test_recompiled(jnp.ones((4,))).block_until_ready()
    assert len(telemetry.startup_report()["compiles"]) == first
    hvd_test_recompiled(jnp.ones((8,))).block_until_ready()
    report = telemetry.startup_report()
    assert _rows(report, "hvd_test_recompiled", since=first) == [
        ("trace", None), ("mlir", None), ("backend_compile", None)]
    row = report["compiles"][-1]
    assert row["t0"] <= row["t1"] <= report["now"]
    assert row["cache"] in ("hit", "miss", "none")


@pytest.fixture()
def tiny_step(hvd, fresh):
    """``make_train_step`` of a two-layer LM on a four-device mesh,
    compiled, with its arguments placed."""
    from horovod_tpu.models import transformer as tfm
    from horovod_tpu.topology import build_mesh

    cfg = tfm.TransformerConfig(vocab_size=64, d_model=32, n_heads=2,
                                n_layers=2, d_ff=64, max_seq=16)
    mesh = build_mesh(axes=("data",), devices=jax.devices()[:4])
    optimizer = optax.sgd(0.1)
    step, _, _ = tfm.make_train_step(cfg, optimizer, mesh,
                                     attention="local", donate=False)
    params = tfm.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((4, 16), jnp.int32)
    args = (params, optimizer.init(params), tokens, tokens)
    compiled = step.lower(*args).compile()
    return compiled, args


def test_the_steps_rows_carry_role_step(tiny_step):
    report = telemetry.startup_report()
    assert _rows(report, scopes.LM_TRAIN_STEP) == [
        ("trace", "step"), ("mlir", "step"), ("backend_compile", "step")]
    # Nothing else is the step: the helpers of init_params are programs
    # of their own with no role.
    assert all(r["role"] is None for r in report["compiles"]
               if r["fun_name"] not in scopes.STEP_NAMES)
    built = _by_name(report)["make_train_step"]
    assert built["attrs"] == {"step": scopes.LM_TRAIN_STEP}
    # The parts were traced under their names, as running totals.
    assert set(report["parts"]) == {"attention", "mlp"}
    for total in report["parts"].values():
        assert total["count"] >= 1 and total["seconds"] > 0
    # Rows nest: every part was traced inside the step's trace.
    trace = next(r for r in report["compiles"] if r["role"] == "step")
    assert sum(t["seconds"] for t in report["parts"].values()) <= (
        trace["t1"] - trace["t0"])


def test_ten_steps_add_no_span_and_no_row(tiny_step):
    compiled, (params, opt_state, tokens, labels) = tiny_step
    # The first call moves the arguments onto the mesh, which is a program.
    params, opt_state, _ = compiled(params, opt_state, tokens, labels)
    tokens, labels = (jax.device_put(x, compiled.input_shardings[0][2])
                      for x in (tokens, labels))
    params, opt_state, loss = compiled(params, opt_state, tokens, labels)
    before = telemetry.startup_report()
    for _ in range(10):
        params, opt_state, loss = compiled(params, opt_state, tokens, labels)
    loss.block_until_ready()
    after = telemetry.startup_report()
    assert len(after["compiles"]) == len(before["compiles"])
    assert len(after["spans"]) == len(before["spans"])
    assert after["parts"] == before["parts"]


def test_the_lists_stay_at_their_bound(fresh):
    for i in range(spans.PHASE_SPANS_KEPT + 40):
        spans.record_phase(f"p{i}", float(i), float(i) + 0.5)
    for i in range(spans.LEDGER_ROWS_KEPT + 25):
        spans._on_duration("/jax/core/compile/jaxpr_trace_duration", 0.001,
                           fun_name=f"f{i}")
    report = telemetry.startup_report()
    assert len(report["spans"]) == spans.PHASE_SPANS_KEPT
    assert len(report["compiles"]) == spans.LEDGER_ROWS_KEPT
    assert report["dropped"] == {"spans": 40, "compiles": 25}
    # The start-up (the first half) stays, and so does the newest row.
    names = [s["name"] for s in report["spans"]]
    assert names[0] == "p0" and names[-1] == f"p{spans.PHASE_SPANS_KEPT + 39}"
    funs = [r["fun_name"] for r in report["compiles"]]
    assert funs[0] == "f0" and funs[-1] == f"f{spans.LEDGER_ROWS_KEPT + 24}"


def test_cache_events_are_booked_to_the_enclosing_compile(fresh):
    """The persistent cache's events fire inside ``backend_compile`` on
    the compiling thread; the row that closes next on that thread takes
    them, and the one after starts clean."""
    compile_event = "/jax/core/compile/backend_compile_duration"
    use = "/jax/compilation_cache/compile_requests_use_cache"
    spans._on_event(use)
    spans._on_event("/jax/compilation_cache/cache_hits")
    spans._on_duration("/jax/compilation_cache/cache_retrieval_time_sec",
                       0.25)
    spans._on_duration(compile_event, 0.3, fun_name="jit(read)")
    spans._on_event(use)
    spans._on_event("/jax/compilation_cache/cache_misses")
    spans._on_duration(compile_event, 2.0, fun_name="jit(built)")
    spans._on_duration(compile_event, 0.1, fun_name="jit(uncached)")
    rows = {r["fun_name"]: r for r in telemetry.startup_report()["compiles"]}
    assert (rows["read"]["cache"], rows["read"]["cache_read_s"]) == (
        "hit", 0.25)
    assert (rows["built"]["cache"], rows["built"]["cache_read_s"]) == (
        "miss", 0.0)
    assert rows["uncached"]["cache"] == "none"
    assert rows["built"]["t1"] - rows["built"]["t0"] == pytest.approx(2.0)


def test_enable_compile_cache_says_what_the_cache_holds(
        fresh, enabled_telemetry, tmp_path, monkeypatch):
    from horovod_tpu.utils.compile_cache import enable_compile_cache

    (tmp_path / "a-cache").write_bytes(b"x" * 100)
    (tmp_path / "a-atime").write_bytes(b"t" * 8)
    (tmp_path / "b-cache").write_bytes(b"y" * 50)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_compilation_cache_max_size)
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_compilation_cache_max_size", 4096)
    try:
        assert enable_compile_cache() == str(tmp_path)
    finally:
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update("jax_compilation_cache_max_size", was[1])
    assert telemetry.startup_report()["cache"] == {
        "dir": str(tmp_path), "bytes": 158, "entries": 2, "cap_bytes": 4096}
    snapshot = telemetry.metrics_snapshot()
    assert snapshot["hvd_compile_cache_bytes"]["values"][0]["value"] == 158
    assert snapshot["hvd_compile_cache_entries"]["values"][0]["value"] == 2
    assert snapshot["hvd_compile_cache_cap_bytes"]["values"][0][
        "value"] == 4096


def test_with_metrics_on_the_report_is_in_the_registry(
        hvd, fresh, enabled_telemetry):
    @jax.jit
    def hvd_test_metered(x):
        return x - 1

    with telemetry.span("build_mesh"):
        hvd_test_metered(jnp.ones((3,))).block_until_ready()
    spans.part_traced("attention", 0.5)
    text = telemetry.render_prometheus()
    assert 'hvd_startup_seconds{phase="build_mesh"}' in text
    assert 'hvd_startup_seconds{phase="trace_part/attention"} 0.5' in text
    for stage in ("trace", "mlir", "backend_compile"):
        assert (f'hvd_compile_seconds{{fun="hvd_test_metered",'
                f'stage="{stage}"}}') in text
    assert 'hvd_compiles_total{cache="' in text
    # The row knows the phase span it was made under.
    row = next(r for r in telemetry.startup_report()["compiles"]
               if r["fun_name"] == "hvd_test_metered")
    assert row["parent"] == _by_name(
        telemetry.startup_report())["build_mesh"]["id"]


def test_under_the_profiler_a_phase_span_is_on_the_host_plane(
        hvd, fresh, tmp_path):
    """``hvd:<name>`` sits on ``/host:CPU``, on the device trace's clock,
    as the harness's ``perfbench:*`` spans do."""
    from horovod_tpu.topology import build_mesh

    jax.profiler.start_trace(str(tmp_path))
    try:
        build_mesh(axes=("data",), devices=jax.devices()[:2])
        jnp.ones((8,)).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    host = next(p for p in data.planes if p.name == "/host:CPU")
    names = {event.name for line in host.lines for event in line.events}
    assert "hvd:build_mesh" in names
