"""The reduction from trace events to numbers, on an event list built by
hand in the form a v5e trace has (PERF.md, "Reading a trace"): event names
are whole HLO instructions, times are nanoseconds."""

import pytest

from perfbench import trace_reduce as tr

FUSION = ("%fusion.1 = (f32[4,2048]{1,0:T(4,128)S(1)}, bf16[4,2048,4096]"
          "{2,1,0:T(8,128)(2,1)}) fusion(bf16[4,2048]{1,0} %p), "
          "kind=kOutput, calls=%fused_computation.1")
KERNEL = ('%jvp__.7 = (bf16[128,2048,128]{2,1,0}, f32[128,1,2048]{2,1,0}) '
          'custom-call(bf16[128,2048,128]{2,1,0} %b), '
          'custom_call_target="tpu_custom_call"')
ALLREDUCE = ("%psum_invariant.3 = f32[4096,4096]{1,0:T(8,128)} "
             "all-reduce(f32[4096,4096]{1,0} %fusion.9), channel_id=1, "
             "replica_groups={{0,1,2,3}}")
WHILE = "%while.2 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), body=%b"
AR_START = ("%all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} %x),"
            " channel_id=2")
AR_DONE = "%all-reduce-done.1 = f32[8]{0} all-reduce-done(f32[8]{0} %s)"


def test_parse_and_short_name():
    assert tr.parse(FUSION) == ("%fusion.1", "fusion",
                                "(f32[4,2048], bf16[4,2048,4096])")
    assert tr.parse(ALLREDUCE)[1] == "all-reduce"
    assert tr.short_name(KERNEL) == (
        "%jvp__.7 custom-call (bf16[128,2048,128], f32[128,1,2048])")
    assert tr.parse("perfbench:fence") == ("perfbench:fence", "", "")
    assert tr.is_collective(ALLREDUCE) and tr.is_collective(AR_DONE)
    # A fusion that merely reads an all-reduce's result is compute.
    assert not tr.is_collective(FUSION.replace("%p", "%all-reduce.1"))


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 7)]) == [(0, 3), (5, 7)]
    assert tr.measure([(0, 2), (1, 3), (5, 7)]) == 5
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]


def test_self_time_of_a_container():
    timed = tr.self_times([(WHILE, 100, 400), (FUSION, 100, 200),
                           (KERNEL, 250, 400)])
    assert [(tr.parse(n)[1], s) for n, s, _ in timed] == [
        ("while", 50), ("fusion", 100), ("custom-call", 150)]


def test_reduce_events_by_hand():
    ops = [
        (FUSION, 0, 100),                    # compute
        (WHILE, 100, 400),                   # container of the next three
        (FUSION, 100, 200),
        (KERNEL, 200, 300),
        (ALLREDUCE, 300, 400),               # synchronous: all exposed
        # idle 400..500: the host was in its fence for 60 of it
        (AR_START, 500, 501),                # asynchronous, in flight
        (FUSION, 501, 600),                  # ... beside this fusion
        (AR_DONE, 600, 650),                 # ... then waited for
    ]
    asyncs = [(AR_START, 500, 650)]
    spans = [("perfbench:dispatch", 0, 30), ("perfbench:fence", 440, 700)]
    r = tr.reduce_events(
        {"/device:TPU:0": ops, "/device:TPU:1": []}, spans,
        {"flash": ['custom_call_target="tpu_custom_call"']},
        {"/device:TPU:0": asyncs})
    ns = 1e-9
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(650 * ns)
    assert r["busy_s"] == pytest.approx(550 * ns)
    assert r["kernel_s"] == {"flash": pytest.approx(100 * ns)}
    # 300..400 synchronous plus 500..650 from start to done.
    assert r["collective_s"] == pytest.approx(250 * ns)
    # ... of which 501..600 ran beside a fusion.
    assert r["exposed_collective_s"] == pytest.approx(151 * ns)
    assert r["idle_gaps"] == {"perfbench:fence": pytest.approx(60 * ns),
                              "(no span)": pytest.approx(40 * ns)}
    assert r["op_s"]["%fusion.1 fusion (f32[4,2048], bf16[4,2048,4096])"] \
        == pytest.approx(299 * ns)
    assert r["op_s"]["%while.2 while (s32[], f32[8])"] == 0
    assert sum(r["op_s"].values()) == pytest.approx(r["busy_s"])


def test_two_devices_are_averaged_and_no_device_is_nothing():
    one = [(FUSION, 0, 100)]
    two = [(FUSION, 0, 50), (ALLREDUCE, 50, 100)]
    r = tr.reduce_events({"/device:TPU:0": one, "/device:TPU:1": two}, [])
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx(100e-9)
    assert r["collective_s"] == pytest.approx(25e-9)
    assert tr.reduce_events({}, []) == {}


def test_layer_metric_readers_on_a_reduction():
    import importlib

    from perfbench.cell import Cell

    cell = Cell(step=None, state_shapes=(), batch_shapes=(), make=None,
                flops_per_step=0.0, item="tokens", items_per_step=1,
                grad_per_delta=1.0, checked=None, reference=None,
                kernels={"flash": {"flops": 197e12 * 0.010,
                                   "bytes": 1.0, "match": ["x"]}})
    ctx = {"reduced": {"devices": 4, "window_s": 1.0, "busy_s": 0.98,
                       "collective_s": 0.2, "exposed_collective_s": 0.15,
                       "op_s": {"a": 0.5, "b": 0.48},
                       "kernel_s": {"flash": 0.08}, "idle_gaps": {}},
           "trace_steps": 2, "timings": {"lower_s": 4.5, "compile_s": 0.9},
           "dispatch_s": [0.004, 0.002, 0.003], "cell": cell,
           "peaks": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}

    def read(name):
        module = importlib.import_module("perfbench.layer_metrics." + name)
        return module.read(ctx)

    assert read("lower_s") == 4.5 and read("compile_s") == 0.9
    assert read("dispatch_ms") == pytest.approx(3.0)
    assert read("device_idle_pct") == pytest.approx(2.0)
    assert read("collective_ms_per_step") == pytest.approx(100.0)
    assert read("exposed_collective_ms_per_step") == pytest.approx(75.0)
    assert read("flash_ms_per_step") == pytest.approx(40.0)
    # Least 10 ms per step against 40 ms taken.
    assert read("flash_roofline") == pytest.approx(25.0)
    assert read("xla_ms_per_step") == pytest.approx(350.0)
    ctx["reduced"] = dict(ctx["reduced"], devices=1, kernel_s={})
    assert read("collective_ms_per_step") is None
    assert read("flash_ms_per_step") is None
    assert read("flash_roofline") is None
