#!/usr/bin/env python
"""The eager plane's host tool: allreduce bandwidth over the native TCP/shm
data plane, on loopback.

Two drivers, each spawning its configurations as launcher jobs of this
same file (``python -m horovod_tpu.runner -np N python tools/bench_eager.py
--worker ...``)::

    python tools/bench_eager.py [--np 2] [--quick] [--out sweep.json]
    python tools/bench_eager.py --transport [--out transport.json]

The default sweep is payload size x fusion threshold x hierarchical on/off
x autotune, with the autotuner's pinned configuration against the defaults
(reference anchor: the tunables surface of
``horovod/common/parameter_manager.h:33-246`` and the autotune CSV wiring
``horovod/run/run.py:474-477``).  ``--transport`` is the backend A/B that
``ci/run_tests.sh`` gates on: single socket (CRC-framed and unframed), the
shm ring, and the striped transport at 1/2/4 stripes; every worker asserts
that the forced backend carried the bytes.

All numbers are LOOPBACK on one host: they measure the runtime's protocol
and memory path (framing, fusion, negotiation, ring arithmetic), not a NIC
and not a chip, and belong in a CI artifact, never beside
``PERF_LEDGER.jsonl``.

Bus bandwidth uses the standard ring accounting: each rank moves
``2 (n-1)/n x bytes`` through its slowest link, so
``busbw = algbw x 2(n-1)/n`` where ``algbw = payload_bytes / time``.
"""

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULT_MARKER = "EAGER_RESULT "


# ---------------------------------------------------------------------------
# Worker
# ---------------------------------------------------------------------------


def _time_reps(fn, warmup, reps, barrier):
    """Best-of-reps wall time of ``fn`` with a barrier fencing each rep
    (both ranks start together; the slowest rank defines the rep).  Best,
    not median: on a contended 1-core host the distribution is one-sided
    scheduler noise and the minimum estimates the plane itself."""
    for _ in range(warmup):
        fn()
    best = float("inf")
    for _ in range(reps):
        barrier()
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _sweep_worker(args):
    import numpy as np
    # Simulated 2-host topology (the hierarchical path groups by
    # LOCAL_SIZE; same trick as tests/distributed/hier_check_np4.py).
    if args.fake_hosts:
        rank = int(os.environ["HOROVOD_RANK"])
        size = int(os.environ["HOROVOD_SIZE"])
        os.environ["HOROVOD_LOCAL_SIZE"] = str(size // 2)
        os.environ["HOROVOD_LOCAL_RANK"] = str(rank % (size // 2))
    import horovod_tpu as hvd
    hvd.init()
    rank, size = hvd.rank(), hvd.size()
    mode = args.worker
    barrier = lambda: hvd.barrier()
    ring = 2.0 * (size - 1) / size
    out = {"mode": mode, "np": size}

    if mode == "large":
        # One big tensor per size: the pure data-plane path (negotiation
        # amortized by the response cache after the first round).
        sizes_mb = [float(s) for s in args.sizes_mb.split(",")]
        rows = []
        for mb in sizes_mb:
            n = int(mb * (1 << 20) / 4)
            x = np.random.default_rng(rank).standard_normal(n) \
                .astype(np.float32)
            fn = lambda: hvd.allreduce(x, op=hvd.Sum,
                                       name=f"bench.large.{n}")
            t = _time_reps(fn, warmup=3, reps=10, barrier=barrier)
            algbw = n * 4 / t / 1e9
            rows.append({"mb": mb, "sec": round(t, 6),
                         "algbw_gbs": round(algbw, 3),
                         "busbw_gbs": round(algbw * ring, 3)})
        out["rows"] = rows
        from horovod_tpu import basics
        out["chunk_bytes"] = basics.runtime().tuned_config() \
            .get("chunk_bytes", 0)

    elif mode == "fused":
        # Fusion-buffer workload: many small named tensors in flight at
        # once, same names every step (steady-state cache) — the shape
        # of a DP gradient bucket the tuner actually optimizes.
        n_tensors, kb = 64, 256
        n = kb * 1024 // 4
        xs = [np.random.default_rng(rank * 1000 + i)
              .standard_normal(n).astype(np.float32)
              for i in range(n_tensors)]

        def step():
            hs = [hvd.allreduce_async(x, op=hvd.Sum,
                                      name=f"bench.fused.{i}")
                  for i, x in enumerate(xs)]
            for h in hs:
                hvd.synchronize(h)

        autotune = os.environ.get("HOROVOD_AUTOTUNE") == "1"
        if autotune:
            # Drive the tuner to convergence before timing: warmup +
            # trials x samples x steps busy cycles (the driver's reduced
            # schedule needs ~190), then measure the PINNED configuration.
            for _ in range(200):
                step()
        # Streaming throughput, not barrier-fenced latency: steps run
        # back-to-back (the shape of a training loop, and the metric the
        # autotuner's bytes/usec score optimizes).  Best block of several
        # — on a 1-core host the scheduler's noise floor is ~2x, and the
        # best block is the least-perturbed estimate of the plane itself.
        blocks, steps_per_block = 6, 8
        for _ in range(5):
            step()
        t = float("inf")
        for _ in range(blocks):
            barrier()
            t0 = time.perf_counter()
            for _ in range(steps_per_block):
                step()
            t = min(t, (time.perf_counter() - t0) / steps_per_block)
        payload = n_tensors * n * 4
        algbw = payload / t / 1e9
        out.update({
            "n_tensors": n_tensors, "tensor_kb": kb,
            "step_payload_mb": round(payload / (1 << 20), 1),
            "sec_per_step": round(t, 6),
            "algbw_gbs": round(algbw, 3),
            "busbw_gbs": round(algbw * ring, 3),
            "fusion_threshold_mb":
                int(os.environ.get("HOROVOD_FUSION_THRESHOLD", str(64 << 20)))
                / (1 << 20),
            "cycle_time_ms": float(os.environ.get("HOROVOD_CYCLE_TIME",
                                                  "1.0")),
            "autotune": autotune,
        })
        if autotune:
            # Online-adaptation snapshot: the tuner is expected to be
            # PINNED-and-monitoring here (exploring False), with the
            # steady-state cache fast path carrying the announcements.
            from horovod_tpu import basics
            out["tuned"] = basics.runtime().tuned_config()

    if args.fake_hosts:
        from horovod_tpu import basics
        out["hierarchical_engaged"] = bool(
            basics.runtime().hierarchical_enabled())
    barrier()
    if rank == 0:
        print(RESULT_MARKER + json.dumps(out), flush=True)
    hvd.shutdown()


def _transport_backend_totals(rt) -> dict:
    """Sum ``Runtime.transport_counters()`` across levels into one
    ``{backend: {bytes, seconds, ops}}`` dict (zero-filled)."""
    totals = {b: {"bytes": 0, "seconds": 0.0, "ops": 0}
              for b in ("socket", "shm", "striped")}
    for (backend, _level), kinds in rt.transport_counters().items():
        row = totals[backend]
        row["bytes"] += kinds["bytes"]
        row["seconds"] += kinds["seconds"]
        row["ops"] += kinds["ops"]
    return totals


def _transport_worker(args):
    """Worker half of ``--transport``.

    Times eager allreduces per payload size under whatever transport the
    driver forced via ``HOROVOD_TRANSPORT``/``HOROVOD_TRANSPORT_STRIPES``,
    asserts the expected backend actually carried the bytes (``--expect``;
    a silent fallback would invalidate the A/B), and snapshots the
    transport counters around each timed loop so every row also reports
    link-level pump bandwidth — the end-to-end number folds in
    submit/fusion/reduce costs shared by all lanes, the link number
    isolates the wire.  Rank 0 prints one result line per row."""
    import numpy as np

    import horovod_tpu as hvd
    from horovod_tpu import basics

    hvd.init()
    rank = hvd.rank()
    rt = basics.runtime()
    expect = args.expect
    stripes = int(os.environ.get("HOROVOD_TRANSPORT_STRIPES", "0"))
    cfg = rt.tuned_config()
    if expect == "shm":
        assert cfg.get("transport_shm"), \
            f"rank {rank}: no shm links negotiated: {cfg}"
    elif expect == "striped":
        assert cfg.get("transport_striped"), \
            f"rank {rank}: no striped links negotiated: {cfg}"
        assert cfg.get("transport_stripes") == stripes, \
            f"rank {rank}: negotiated {cfg.get('transport_stripes')} " \
            f"stripes, wanted {stripes}"

    rng = np.random.default_rng(rank)
    rows = []
    streams = stripes if expect == "striped" else 1
    sizes, iters = (1 << 20, 1 << 24), 6      # float32 elements: 4 and 64 MB

    def timed(label, tensors, names):
        before = _transport_backend_totals(rt)
        t0 = time.perf_counter()
        for x, name in zip(tensors, names):
            hvd.allreduce(x, average=False, name=name)
        wall = time.perf_counter() - t0
        after = _transport_backend_totals(rt)
        nbytes = sum(int(x.nbytes) for x in tensors)
        link_bytes = sum(after[b]["bytes"] - before[b]["bytes"]
                         for b in after)
        # Link seconds are THREAD-CPU seconds (transport::PumpClockUs),
        # so bytes/seconds is per-stream bandwidth on a dedicated core —
        # stable under scheduler pressure — and the aggregate (x streams)
        # is what concurrent stripes deliver with cores/NIC queues of
        # their own.
        link_secs = sum(after[b]["seconds"] - before[b]["seconds"]
                        for b in after)
        link_bw = (link_bytes / link_secs / 2**20
                   if link_secs > 0 else 0.0)
        rows.append({
            "label": label,
            "payload_bytes": nbytes,
            "streams": streams,
            "sec_per_op": wall / len(tensors),
            "algbw_mb_per_sec": nbytes / wall / 2**20,
            "link_mb_per_sec": link_bw,
            "aggregate_link_mb_per_sec": link_bw * streams,
        })

    for n in sizes:
        x = rng.standard_normal(n).astype(np.float32)
        for i in range(2):
            hvd.allreduce(x, average=False, name=f"tb.warm{i}.{n}")
        timed(f"{n * 4 // 2**20}MB",
              [x] * iters, [f"tb.{i}.{n}" for i in range(iters)])
    # Sub-granule burst: 64 x 4 KiB ops measure per-op overhead on the
    # small-tensor path (ring slot reuse / stripe frame headers).
    small = [rng.standard_normal(1024).astype(np.float32)
             for _ in range(64)]
    for i, x in enumerate(small):
        hvd.allreduce(x, average=False, name=f"tb.smallwarm.{i}")
    timed("64x4KB", small, [f"tb.small.{i}" for i in range(64)])

    totals = _transport_backend_totals(rt)
    by_bytes = {b: totals[b]["bytes"] for b in totals}
    if expect == "shm":
        assert by_bytes["shm"] > 0 and by_bytes["socket"] == 0, \
            f"rank {rank}: shm lane leaked to sockets: {by_bytes}"
    elif expect == "striped":
        assert by_bytes["striped"] > 0 and by_bytes["shm"] == 0, \
            f"rank {rank}: striped lane engagement wrong: {by_bytes}"
    else:
        assert by_bytes["socket"] > 0 and by_bytes["shm"] == 0 \
            and by_bytes["striped"] == 0, \
            f"rank {rank}: socket lane engagement wrong: {by_bytes}"
    hvd.shutdown()
    if rank == 0:
        for r in rows:
            print(RESULT_MARKER + json.dumps(r), flush=True)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


def _launch(name, np_, worker_args, env, timeout=600):
    """Run one worker configuration under the launcher; returns the
    payloads of rank 0's result lines (or raises with the captured
    tail)."""
    full_env = dict(os.environ)
    full_env.update(env)
    full_env["PYTHONPATH"] = REPO
    # Host-side numpy plane only: the ranks must stay off the chips,
    # which belong to one process (docs/running.md, "Ranks and chips").
    full_env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, "-m", "horovod_tpu.runner", "-np", str(np_),
           sys.executable, os.path.abspath(__file__)] + worker_args
    res = subprocess.run(cmd, env=full_env, capture_output=True,
                         text=True, timeout=timeout, cwd=REPO)
    # A marker from a job that then failed (e.g. one rank crashed in
    # shutdown) is not a clean number — the job must also exit 0.
    rows = [json.loads(line.split(RESULT_MARKER, 1)[1])
            for line in res.stdout.splitlines() if RESULT_MARKER in line]
    if res.returncode != 0 or not rows:
        raise RuntimeError(
            f"config {name}: no clean result (rc={res.returncode})\n"
            f"stdout tail: {res.stdout[-2000:]}\n"
            f"stderr tail: {res.stderr[-2000:]}")
    return rows


def _write(doc, out):
    line = json.dumps(doc)
    print(line)
    if out:
        os.makedirs(os.path.dirname(os.path.abspath(out)), exist_ok=True)
        with open(out, "w") as f:
            f.write(line + "\n")


def run_sweep(args):
    sizes = "1,4" if args.quick else "1,4,16,64,128,256"
    autotune_log = os.path.join(tempfile.gettempdir(),
                                f"bench_eager_autotune_{os.getpid()}.csv")
    # Reduced tuner schedule so convergence fits the worker's settle
    # loop: 2 warmup + <=12 trials x 3 samples x 5 steps ~ 190 busy cycles.
    tuner_env = {
        "HOROVOD_AUTOTUNE": "1",
        "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "2",
        "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "5",
        "HOROVOD_AUTOTUNE_SAMPLES": "3",
        "HOROVOD_AUTOTUNE_BAYES_TRIALS": "12",
        "HOROVOD_AUTOTUNE_LOG": autotune_log,
    }
    large = ["--worker", "large", "--sizes-mb"]
    fused = ["--worker", "fused"]
    configs = [
        ("large_defaults", args.np, large + [sizes], {}),
        # Pipelined transport off: the pre-chunking data plane reduces
        # each ring exchange only after the whole payload lands — the
        # before/after pair for the >=64 MB bandwidth cliff.
        ("large_no_chunk", args.np,
         large + ["1,4" if args.quick else "16,64,128"],
         {"HOROVOD_EAGER_CHUNK_BYTES": "0"}),
        ("fused_defaults", args.np, fused, {}),
        ("fused_no_fusion", args.np, fused,
         {"HOROVOD_FUSION_THRESHOLD": "0"}),
        ("fused_2mb", args.np, fused,
         {"HOROVOD_FUSION_THRESHOLD": str(2 << 20)}),
        ("fused_no_cache", args.np, fused, {"HOROVOD_CACHE_CAPACITY": "0"}),
        ("fused_autotune", args.np, fused, tuner_env),
    ]
    if not args.quick:
        hier = large + ["16", "--fake-hosts"]
        configs += [
            ("hier_off_np4_16mb", 4, hier, {}),
            ("hier_on_np4_16mb", 4, hier,
             {"HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
              "HOROVOD_HIERARCHICAL_ALLREDUCE_THRESHOLD": "0"}),
        ]

    results = []
    for name, np_, worker_args, env in configs:
        print(f"--- {name} (np={np_})", file=sys.stderr, flush=True)
        try:
            results.append(dict(_launch(name, np_, worker_args, env)[0],
                                config=name))
        except Exception as e:  # keep sweeping; record the failure
            results.append({"config": name, "error": str(e)[:2000]})
        print(json.dumps(results[-1]), file=sys.stderr, flush=True)

    # Attach the tuner's trial log (trial rows + the pinned row) so the
    # artifact shows WHAT the tuner chose, not just that it helped.
    pinned = None
    phases = {}
    try:
        import csv
        with open(autotune_log) as f:
            for row in csv.DictReader(f):
                phase = row.get("phase", "")
                phases[phase] = phases.get(phase, 0) + 1
                if row.get("pinned") == "1":
                    pinned = {
                        "cycle_time_ms": float(row["cycle_time_ms"]),
                        "fusion_threshold_mb":
                            float(row["fusion_threshold_mb"]),
                        "chunk_kb": float(row.get("chunk_kb", 0) or 0),
                        "cache_enabled": row["cache_enabled"] == "1",
                        "hier_allreduce": row.get("hier_allreduce") == "1",
                        "hier_allgather": row.get("hier_allgather") == "1",
                    }
        os.unlink(autotune_log)
    except (OSError, ValueError, KeyError, TypeError):
        # A truncated row (worker killed mid-write) must not lose the
        # whole sweep's artifact.
        pass

    _write({"bench": "eager_allreduce_tcp_loopback",
            "host_cores": os.cpu_count(),
            "note": ("loopback TCP on one host; measures the runtime's "
                     "protocol+memory path, not a NIC. On a 1-core host "
                     "both ranks and the kernel share the core: absolute "
                     "GB/s is environment-capped, read the RELATIVE "
                     "comparisons (fusion/cycle/autotune)"),
            "autotune_pinned": pinned,
            # trial-log phase counts: "explore" rows are live trials,
            # "pinned" the convergence, "reopen" drift-triggered restarts
            # (the tuner monitors forever; a steady bench stays at 0).
            "autotune_phases": phases,
            "results": results}, args.out)
    return 1 if any("error" in r for r in results) else 0


def run_transport(args):
    """Transport-backend A/B (docs/performance.md, 'Transport backends'):
    one ``-np 2`` loopback run of :func:`_transport_worker` per lane.

    ``stripes=1`` deliberately resolves to the plain socket backend
    (``transport::Enabled``), so the striped ratio is measured against
    an identical code path minus the frame/reassembly machinery.  Each
    worker asserts the forced backend actually carried the bytes, so a
    passing run certifies both the numbers and the selection plumbing.
    The ratios are written into the result, not enforced here:
    ``ci/run_tests.sh`` asserts on them."""
    lanes = [
        ("socket", "socket", {"HOROVOD_TRANSPORT": "socket"}),
        # Checksum A/B: `socket` above rides the default CRC32C-framed
        # engine (HOROVOD_TRANSPORT_CHECKSUM=auto -> on); this lane is
        # the unframed fast path, so socket/socket_nocrc isolates the
        # wire-integrity overhead (docs/performance.md target < 5%).
        ("socket_nocrc", "socket", {"HOROVOD_TRANSPORT": "socket",
                                    "HOROVOD_TRANSPORT_CHECKSUM": "off"}),
        ("shm", "shm", {"HOROVOD_TRANSPORT": "shm"}),
        ("striped1", "socket", {"HOROVOD_TRANSPORT": "striped",
                                "HOROVOD_TRANSPORT_STRIPES": "1"}),
        ("striped2", "striped", {"HOROVOD_TRANSPORT": "striped",
                                 "HOROVOD_TRANSPORT_STRIPES": "2"}),
        ("striped4", "striped", {"HOROVOD_TRANSPORT": "striped",
                                 "HOROVOD_TRANSPORT_STRIPES": "4"}),
    ]
    by_lane = {}
    for name, expect, knobs in lanes:
        rows = _launch(name, 2, ["--worker", "transport", "--expect",
                                 expect], knobs)
        by_lane[name] = {r["label"]: r for r in rows}
        for label, r in by_lane[name].items():
            print(f"{name:>12} {label:>7}: "
                  f"{r['algbw_mb_per_sec']:8.1f} MB/s algbw, "
                  f"{r['link_mb_per_sec']:8.1f} MB/s link, "
                  f"{r['sec_per_op'] * 1e3:7.2f} ms/op",
                  file=sys.stderr, flush=True)

    big = "64MB"
    # Headline ratios come from the link counters (thread-CPU seconds,
    # see _transport_worker): per-stream pump bandwidth for the
    # shm-vs-socket A/B (one stream each), aggregate across stripes for
    # the striping A/B.  Wall-clock algbw ratios ride along for context
    # but on a single-core CI rig they measure the scheduler, not the
    # transport: every pump thread timeshares one core, so stripe
    # parallelism can never show up in wall time there.
    shm_vs_socket = (by_lane["shm"][big]["link_mb_per_sec"]
                     / by_lane["socket"][big]["link_mb_per_sec"])
    striped4_vs_1 = (by_lane["striped4"][big]["aggregate_link_mb_per_sec"]
                     / by_lane["striped1"][big]["aggregate_link_mb_per_sec"])
    # CRC overhead = lost link bandwidth fraction vs the unframed fast
    # path (clamped at 0: on a noisy rig the framed lane can win).
    checksum_overhead = max(
        0.0, 1.0 - (by_lane["socket"][big]["link_mb_per_sec"]
                    / by_lane["socket_nocrc"][big]["link_mb_per_sec"]))
    _write({
        "metric": "transport_backend_algbw",
        "np": 2,
        "rig": "loopback CPU",
        "cores": os.cpu_count(),
        "lanes": {name: sorted(rows.values(),
                               key=lambda r: r["payload_bytes"])
                  for name, rows in by_lane.items()},
        "shm_vs_socket_64mb": round(shm_vs_socket, 3),
        "shm_vs_socket_64mb_wall": round(
            by_lane["shm"][big]["algbw_mb_per_sec"]
            / by_lane["socket"][big]["algbw_mb_per_sec"], 3),
        "striped4_vs_striped1_64mb": round(striped4_vs_1, 3),
        "striped4_vs_striped1_64mb_wall": round(
            by_lane["striped4"][big]["algbw_mb_per_sec"]
            / by_lane["striped1"][big]["algbw_mb_per_sec"], 3),
        "checksum_overhead_64mb": round(checksum_overhead, 4),
        "backend_engagement_asserted": True,   # every worker asserted it
        "note": "link bandwidth = bytes / thread-CPU pump seconds, i.e. "
                "per-dedicated-core throughput; aggregate = x streams. "
                "Wall ratios are scheduler-bound on single-core rigs.",
    }, args.out)
    return 0


def main():
    ap = argparse.ArgumentParser(
        description=__doc__.split("\n\n")[0],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    ap.add_argument("--np", type=int, default=2,
                    help="ranks for the sweep's non-hierarchical configs")
    ap.add_argument("--out", default=None,
                    help="write the result JSON here too (a CI artifact "
                         "path; stdout always gets it)")
    ap.add_argument("--quick", action="store_true",
                    help="sweep: small sizes / fewer configs (CI smoke)")
    ap.add_argument("--transport", action="store_true",
                    help="run the transport backend A/B, not the sweep")
    # What the drivers pass to the ranks they launch.
    ap.add_argument("--worker", choices=("large", "fused", "transport"),
                    help=argparse.SUPPRESS)
    ap.add_argument("--sizes-mb", help=argparse.SUPPRESS)
    ap.add_argument("--fake-hosts", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--expect", choices=("socket", "shm", "striped"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.worker == "transport":
        return _transport_worker(args)
    if args.worker:
        return _sweep_worker(args)
    return run_transport(args) if args.transport else run_sweep(args)


if __name__ == "__main__":
    sys.exit(main())
