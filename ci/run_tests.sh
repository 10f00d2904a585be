#!/usr/bin/env bash
# CI entry point (reference .buildkite/gen-pipeline.sh: build, then run the
# pytest suites and the example scripts under the launcher).
set -euo pipefail
cd "$(dirname "$0")/.."

echo "--- hvdlint (distributed-correctness static analysis;
--- docs/static_analysis.md: rank-divergent collectives, env-var
--- registry drift, telemetry catalogue drift)"
python -m tools.hvdlint

echo "--- build native runtime (warnings are errors in CI)"
make -C horovod_tpu/native/cc clean >/dev/null
make -C horovod_tpu/native/cc WERROR=1
python -m horovod_tpu.native.build

#  (The Bayesian-optimizer grid-search oracle gate runs inside the fast
#   lane: tests/test_autotune.py::test_bayes_vs_grid_oracle -> make
#   -C native/cc unittest.)

echo "--- capability report"
python -m horovod_tpu.runner --check-build

echo "--- unit + SPMD suites, fast lane (8-device virtual CPU mesh)"
python -m pytest tests/ -q

echo "--- slow lane (multi-minute end-to-end oracles; pyproject addopts
--- deselects these by default, CI runs them explicitly)"
python -m pytest tests/ -q -m slow

echo "--- chaos lane (fault-injection harness; single host, subprocess
--- ranks, each test bounded <=30s.  These also run in the fast lane —
--- this explicit pass keeps the failure-path suite visible and green
--- on its own)"
JAX_PLATFORMS=cpu python -m pytest tests/ -x -q -m chaos

echo "--- distributed op matrix under the launcher (the reference's
--- 'pytest under horovodrun' trick, gen-pipeline.sh:120-190).  The
--- schedule verifier rides along armed: a valid suite must never trip
--- it (false-abort regression gate, docs/static_analysis.md)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" HOROVOD_SCHEDULE_CHECK=1 \
  python -m horovod_tpu.runner -np 2 \
  python -m pytest tests/distributed -x -q

echo "--- keras binding on the JAX backend (the TPU-native Keras 3 path)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" KERAS_BACKEND=jax \
  python -m horovod_tpu.runner -np 2 \
  python -m pytest tests/distributed/test_keras_binding.py -x -q

#  (The joint launcher+SPMD certification — hvdrun --jax-distributed with
#   tests/distributed/spmd_np2_check.py — runs inside the slow lane via
#   tests/test_distributed.py::test_jax_distributed_spmd_under_launcher.)

echo "--- hierarchical allreduce + allgather correctness (4 ranks, 2x2 hosts)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  HOROVOD_HIERARCHICAL_ALLREDUCE=1 HOROVOD_HIERARCHICAL_ALLGATHER=1 \
  HOROVOD_HIERARCHICAL_ALLREDUCE_THRESHOLD=0 \
  python -m horovod_tpu.runner -np 4 \
  python tests/distributed/hier_check_np4.py

echo "--- topology-aware hierarchical gate (np=4, 2 slots/host over fake
--- ssh): launcher must inject HOROVOD_TOPOLOGY, workers verify
--- hvd.topology() leader election, the hier and flat eager allreduces
--- must be BITWISE identical, and the merged telemetry must show
--- cross-host bytes == flat bytes / local_size exactly via
--- hvd_collective_bytes_total{plane=eager,level}
--- (docs/performance.md, 'Hierarchical collectives')"
HIER_DIR="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  HOROVOD_SSH_CMD="ci/fake_ssh.sh" \
  HOROVOD_HIER_GATE_DIR="$HIER_DIR" \
  HOROVOD_METRICS_FILE="$HIER_DIR/hier.json" \
  HOROVOD_HIERARCHICAL_ALLREDUCE=1 \
  HOROVOD_HIERARCHICAL_ALLREDUCE_THRESHOLD=0 \
  python -m horovod_tpu.runner -np 4 -H localhost:2,127.0.1.1:2 \
  python tests/distributed/hierarchical_np4.py
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  HOROVOD_SSH_CMD="ci/fake_ssh.sh" \
  HOROVOD_HIER_GATE_DIR="$HIER_DIR" \
  HOROVOD_METRICS_FILE="$HIER_DIR/flat.json" \
  HOROVOD_HIERARCHICAL_ALLREDUCE=0 \
  python -m horovod_tpu.runner -np 4 -H localhost:2,127.0.1.1:2 \
  python tests/distributed/hierarchical_np4.py
python tools/check_metrics.py "$HIER_DIR/hier.json" 4
python tools/check_metrics.py "$HIER_DIR/flat.json" 4
PYTHONPATH="$PWD" python - "$HIER_DIR" <<'EOF'
import json, pathlib, sys
import numpy as np
from horovod_tpu.telemetry import aggregate

d = pathlib.Path(sys.argv[1])
# Bit parity: integer-valued float32 payloads make every partial sum
# exact, so the two routings must agree byte for byte on every rank.
for r in range(4):
    for n in (65536, 1000003):
        a = np.load(d / f"out_hier_r{r}_n{n}.npy")
        b = np.load(d / f"out_flat_r{r}_n{n}.npy")
        assert a.dtype == b.dtype and a.shape == b.shape, (r, n)
        assert (a.view(np.uint8) == b.view(np.uint8)).all(), \
            f"hier vs flat allreduce differ bitwise (rank {r}, n {n})"

def eager_bytes(path, level):
    doc = json.load(open(path))
    return aggregate.counter_total(
        doc["merged"], "hvd_collective_bytes_total",
        {"plane": "eager", "kind": "allreduce", "level": level})

cross = eager_bytes(d / "hier.json", "cross")
flat = eager_bytes(d / "flat.json", "flat")
# Ops that stay flat even under hier routing (the 64-byte bootstrap
# topology agreement runs before SetTopology exists) book identically in
# both runs; subtracting the hier run's flat residue isolates exactly
# the traffic that SWITCHED planes, which must shrink by local_size=2
# (logical per-level accounting, see data_plane.h).
residue = eager_bytes(d / "hier.json", "flat")
assert cross > 0 and flat > residue > 0, (cross, flat, residue)
assert 2 * cross == flat - residue, \
    f"cross {cross} != (flat {flat} - residue {residue}) / 2"
print(f"HIER_NP4_OK cross_bytes={cross:.0f} flat_bytes={flat:.0f} "
      f"residue={residue:.0f}")
EOF
rm -rf "$HIER_DIR"

echo "--- transport gate (2 ranks intra-host): the shm ring must engage
--- (shm bytes > 0, data-plane socket bytes == 0), forced striping must
--- negotiate the requested stripe count, and all three backends must
--- produce BITWISE identical allreduce outputs; the shm run's merged
--- telemetry must show hvd_transport_bytes_total{backend=shm} > 0
--- (docs/performance.md, 'Transport backends')"
TRANSPORT_DIR="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  TRANSPORT_GATE_DIR="$TRANSPORT_DIR" \
  TRANSPORT_GATE_EXPECT=socket HOROVOD_TRANSPORT=socket \
  python -m horovod_tpu.runner -np 2 \
  python tests/distributed/transport_np2.py
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  TRANSPORT_GATE_DIR="$TRANSPORT_DIR" \
  HOROVOD_METRICS_FILE="$TRANSPORT_DIR/shm.json" \
  TRANSPORT_GATE_EXPECT=shm HOROVOD_TRANSPORT=shm \
  python -m horovod_tpu.runner -np 2 \
  python tests/distributed/transport_np2.py
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  TRANSPORT_GATE_DIR="$TRANSPORT_DIR" \
  TRANSPORT_GATE_EXPECT=striped HOROVOD_TRANSPORT=striped \
  HOROVOD_TRANSPORT_STRIPES=2 \
  python -m horovod_tpu.runner -np 2 \
  python tests/distributed/transport_np2.py
python tools/check_metrics.py "$TRANSPORT_DIR/shm.json" 2
PYTHONPATH="$PWD" python - "$TRANSPORT_DIR" <<'EOF'
import json, pathlib, sys
import numpy as np
from horovod_tpu.telemetry import aggregate

d = pathlib.Path(sys.argv[1])
# The transport layer must never change the math: byte-for-byte parity
# across socket / shm / striped on every rank.
for r in range(2):
    ref = np.load(d / f"out_socket_r{r}.npy")
    for backend in ("shm", "striped"):
        got = np.load(d / f"out_{backend}_r{r}.npy")
        assert got.dtype == ref.dtype and got.shape == ref.shape, \
            (backend, r)
        assert (got.view(np.uint8) == ref.view(np.uint8)).all(), \
            f"{backend} vs socket allreduce differ bitwise (rank {r})"

doc = json.load(open(d / "shm.json"))
shm_bytes = aggregate.counter_total(
    doc["merged"], "hvd_transport_bytes_total", {"backend": "shm"})
assert shm_bytes > 0, "merged telemetry shows no shm transport bytes"
sock_bytes = aggregate.counter_total(
    doc["merged"], "hvd_transport_bytes_total", {"backend": "socket"})
assert sock_bytes == 0, \
    f"intra-host shm run leaked {sock_bytes} bytes onto sockets"
print(f"TRANSPORT_GATE_SUMMARY_OK shm_bytes={shm_bytes:.0f}")
EOF
rm -rf "$TRANSPORT_DIR"

echo "--- transport chaos gate (2 ranks, striped x2): a stripe_kill
--- mid-allreduce plus corrupted frames must be absorbed IN-PROCESS —
--- no elastic restart, merged failovers >= 1, retransmits >= 1 — and
--- the chaos run's outputs must be BITWISE identical to the clean run
--- (docs/fault_tolerance.md, 'Transport self-healing')"
CHAOS_DIR="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  TRANSPORT_GATE_DIR="$CHAOS_DIR" TRANSPORT_CHAOS_MODE=clean \
  HOROVOD_TRANSPORT=striped HOROVOD_TRANSPORT_STRIPES=2 \
  python -m horovod_tpu.runner -np 2 \
  python tests/distributed/transport_chaos_np2.py
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  TRANSPORT_GATE_DIR="$CHAOS_DIR" TRANSPORT_CHAOS_MODE=chaos \
  HOROVOD_TRANSPORT=striped HOROVOD_TRANSPORT_STRIPES=2 \
  HOROVOD_FAULT_SPEC="rank=0,site=transport,after=3,kind=stripe_kill:1;rank=1,site=transport,kind=frame_corrupt:2" \
  python -m horovod_tpu.runner -np 2 \
  python tests/distributed/transport_chaos_np2.py
python - "$CHAOS_DIR" <<'EOF'
import pathlib, sys
import numpy as np

d = pathlib.Path(sys.argv[1])
# Self-healing must never change the math: the run that lost a stripe
# and retransmitted corrupted frames ends bit-identical to the clean
# run on every rank.
for r in range(2):
    ref = np.load(d / f"chaos_clean_r{r}.npy")
    got = np.load(d / f"chaos_r{r}.npy")
    assert got.dtype == ref.dtype and got.shape == ref.shape, r
    assert (got.view(np.uint8) == ref.view(np.uint8)).all(), \
        f"chaos vs clean allreduce differ bitwise (rank {r})"
print("TRANSPORT_CHAOS_SUMMARY_OK")
EOF
rm -rf "$CHAOS_DIR"

echo "--- TF1-session async collectives (2 ranks, pruned-sync reaping)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" HOROVOD_TF1_ASYNC=1 \
  python -m horovod_tpu.runner -np 2 \
  python tests/distributed/tf1_async_check_np2.py

echo "--- stalled-cached-tensor watchdog (2 ranks)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  python -m horovod_tpu.runner -np 2 \
  python tests/distributed/stall_check_np2.py

echo "--- schedule-divergence verifier (2 ranks): a rank-divergent
--- signature must abort within one coordination cycle and divergent
--- names within the quiet window, both with a first-divergence report
--- (ranks, call index, field/name) — no stall timeout"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  python -m horovod_tpu.runner -np 2 \
  python tests/distributed/schedule_check_np2.py field
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  python -m horovod_tpu.runner -np 2 \
  python tests/distributed/schedule_check_np2.py order

echo "--- telemetry gate (2 ranks): per-rank + merged metrics JSON with
--- nonzero collective counters (docs/metrics.md)"
METRICS_DIR="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  HOROVOD_METRICS_FILE="$METRICS_DIR/metrics.json" \
  python -m horovod_tpu.runner -np 2 \
  python tests/distributed/metrics_workload_np2.py
python tools/check_metrics.py "$METRICS_DIR/metrics.json" 2
rm -rf "$METRICS_DIR"

echo "--- distributed-tracing gate (2 ranks): merged skew-corrected
--- Perfetto trace with cross-rank trace_id correlation, critical-path
--- straggler report, and the disabled-path no-write negative
--- (docs/timeline.md, 'Distributed tracing')"
TRACE_DIR="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  python -m horovod_tpu.runner -np 2 --trace "$TRACE_DIR" \
  python tests/distributed/trace_workload_np2.py
PYTHONPATH="$PWD" python - "$TRACE_DIR" <<'EOF'
import importlib, json, sys
d = sys.argv[1]
spans_mod = importlib.import_module("horovod_tpu.telemetry.spans")
doc = json.load(open(f"{d}/trace.json"))          # merged trace loads
by_tid = {}
for ev in doc["traceEvents"]:
    if ev.get("ph") != "X":
        continue
    tid = (ev.get("args") or {}).get("trace_id")
    if tid:
        by_tid.setdefault(tid, set()).add(ev["pid"])
# every named collective correlates across BOTH ranks by trace_id
for name in [f"trace.step{i}" for i in range(5)] + ["trace.gather"]:
    tid = spans_mod.trace_id(name, 0)
    assert by_tid.get(tid) == {0, 1}, \
        f"{name}: ranks {by_tid.get(tid)} (want both)"
cp = json.load(open(f"{d}/critical_path.json"))
assert cp["ranks"] == [0, 1] and cp["steps"] >= 6, cp["steps"]
assert cp["attribution"], "no straggler attribution rows"
print(f"TRACE_GATE_OK correlated={len(by_tid)} steps={cp['steps']}")
EOF
# offline analyzer re-derives the report and names a rank and a phase
PYTHONPATH="$PWD" python -m tools.hvdtrace "$TRACE_DIR" \
  | tee "$TRACE_DIR/report.txt"
grep -q "slowest rank:" "$TRACE_DIR/report.txt"
grep -Eq "rank [0-9]+ / (submit|negotiate|fuse|local|cross|transport|wait):" \
  "$TRACE_DIR/report.txt"
# negative: without --trace the recorder must stay off and no span
# file may appear (the workload asserts the recorder is None itself)
NEG_DIR="$(mktemp -d)"
(cd "$NEG_DIR" && JAX_PLATFORMS=cpu PYTHONPATH="$OLDPWD" \
  python -m horovod_tpu.runner -np 2 \
  python "$OLDPWD/tests/distributed/trace_workload_np2.py")
if ls "$NEG_DIR"/spans.rank*.json 2>/dev/null; then
  echo "span files written without --trace"; exit 1
fi
rm -rf "$TRACE_DIR" "$NEG_DIR"

echo "--- online-autotune gate (2 ranks): Bayesian explorer pins, the
--- drift detector re-opens after a 128x payload shift, the cache hit
--- ratio climbs, and the merged summary carries the hvd_autotune_*
--- tuned-config gauges (docs/performance.md, 'Adaptive control plane')"
AUTOTUNE_DIR="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  HOROVOD_METRICS_FILE="$AUTOTUNE_DIR/metrics.json" \
  HOROVOD_AUTOTUNE_WARMUP_SAMPLES=1 HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE=3 \
  HOROVOD_AUTOTUNE_SAMPLES=3 HOROVOD_AUTOTUNE_BAYES_TRIALS=10 \
  python -m horovod_tpu.runner -np 2 \
  --autotune --autotune-log-file "$AUTOTUNE_DIR/autotune.csv" \
  python tests/distributed/autotune_workload_np2.py
python tools/check_metrics.py "$AUTOTUNE_DIR/metrics.json" 2
grep -q ",reopen$" "$AUTOTUNE_DIR/autotune.csv"
python - "$AUTOTUNE_DIR/metrics.json" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
for rank in ("0", "1"):
    metrics = doc["ranks"][rank]["metrics"]
    for gauge in ("hvd_autotune_cycle_time_ms",
                  "hvd_autotune_fusion_threshold_bytes",
                  "hvd_autotune_chunk_bytes",
                  "hvd_autotune_cache_hit_ratio"):
        assert metrics.get(gauge, {}).get("values"), (rank, gauge)
print("AUTOTUNE_METRICS_OK")
EOF
rm -rf "$AUTOTUNE_DIR"

echo "--- ZeRO-1 gate (2 ranks x 8-device virtual mesh): sharded-update
--- trajectory == replicated, 1/8 per-rank state, merged telemetry shows
--- hvd_fusion_* + hvd_zero_* (docs/performance.md)"
ZERO_METRICS_DIR="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  HOROVOD_METRICS_FILE="$ZERO_METRICS_DIR/metrics.json" \
  python -m horovod_tpu.runner -np 2 \
  python tests/distributed/zero_workload_np2.py
python tools/check_metrics.py "$ZERO_METRICS_DIR/metrics.json" 2
rm -rf "$ZERO_METRICS_DIR"

echo "--- gradient-compression gate (2 ranks x 8-device virtual mesh):
--- int8 error-feedback LM microstep over the ZeRO wire — loss parity
--- vs the uncompressed codec within 1% at equal steps, merged
--- telemetry shows hvd_compression_bytes_out < bytes_in and the int8
--- hvd_collective_bytes_total plane below none (docs/performance.md)"
COMPRESSION_METRICS_DIR="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  HOROVOD_METRICS_FILE="$COMPRESSION_METRICS_DIR/metrics.json" \
  python -m horovod_tpu.runner -np 2 \
  python tests/distributed/compression_workload_np2.py
python tools/check_metrics.py "$COMPRESSION_METRICS_DIR/metrics.json" 2
rm -rf "$COMPRESSION_METRICS_DIR"

echo "--- self-healing gate (2 ranks x 8-device virtual mesh): guarded
--- step + coordinated NaN rollback + divergence-sentinel heal + async
--- checkpoint, merged telemetry shows hvd_guard_* / hvd_rollback_* /
--- hvd_sentinel_* (docs/fault_tolerance.md)"
RESILIENCE_METRICS_DIR="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  HOROVOD_METRICS_FILE="$RESILIENCE_METRICS_DIR/metrics.json" \
  python -m horovod_tpu.runner -np 2 \
  python tests/distributed/resilience_workload_np2.py
python tools/check_metrics.py "$RESILIENCE_METRICS_DIR/metrics.json" 2
rm -rf "$RESILIENCE_METRICS_DIR"

echo "--- warm-restart gate (2 ranks, elastic): rank 1 SIGKILLed after
--- committing step 4 while the disk checkpoint holds step 1; the np=1
--- relaunch must recover from the PEER SPILL at the committed step (no
--- orbax read), apply the 2->1 continuity policy, and converge — the
--- workload asserts all of it, the merged telemetry must show
--- hvd_warm_restart_* (docs/fault_tolerance.md)"
WARM_DIR="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  HOROVOD_METRICS_FILE="$WARM_DIR/metrics.json" \
  HOROVOD_SSH_CMD="ci/fake_ssh.sh" \
  WARM_GATE_CKPT="$WARM_DIR/ckpt" \
  HOROVOD_TERMINATE_GRACE_SECONDS=3 \
  python -m horovod_tpu.runner -np 2 -H localhost:1,127.0.1.1:1 \
  --elastic-restarts 2 --min-np 1 \
  python tests/distributed/warm_restart_np2.py \
  | tee "$WARM_DIR/out.log"
grep -q "WARM_OK attempt=1 rank=0 size=1 source=spill committed=4" \
  "$WARM_DIR/out.log"
rm -rf "$WARM_DIR"

echo "--- fail-in-place gate (np=3 -> 2 over fake ssh): a rank_kill
--- chaos rule SIGKILLs rank 2 from inside an armed transport exchange
--- mid-training; the survivors must reform the collective world
--- IN-PROCESS — zero elastic restarts, membership epoch 0 -> 1,
--- exactly one reformation in the merged metrics — recover the
--- committed step from peer spills and train to the uninterrupted
--- run's final state (docs/fault_tolerance.md, 'Fail-in-place')"
FIP_DIR="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  HOROVOD_SSH_CMD="ci/fake_ssh.sh" \
  HOROVOD_METRICS_FILE="$FIP_DIR/metrics.json" \
  HOROVOD_TERMINATE_GRACE_SECONDS=3 \
  HOROVOD_FAULT_SPEC="rank=2,site=transport,kind=rank_kill,after=140" \
  timeout 150 \
  python -m horovod_tpu.runner -np 3 -H localhost:2,127.0.1.1:1 \
  --heartbeat-interval 0.2 --min-np 2 --on-rank-failure shrink \
  python tests/distributed/failinplace_np3.py \
  2> "$FIP_DIR/err.log" | tee "$FIP_DIR/out.log"
cat "$FIP_DIR/err.log" >&2
grep -q "firing kind=rank_kill at site=transport" \
  "$FIP_DIR/out.log" "$FIP_DIR/err.log"
grep -q "reforming the world in-process as epoch 1 with 2 rank(s)" \
  "$FIP_DIR/err.log"
grep -q "absorbed by in-process reformation (2 survivor(s) continue)" \
  "$FIP_DIR/err.log"
test "$(grep -c "FIP_OK rank=[01] size=2 epoch=1 source=spill" \
  "$FIP_DIR/out.log")" -eq 2
PYTHONPATH="$PWD" python - "$FIP_DIR/metrics.json" <<'PYEOF'
import json, sys
from horovod_tpu.telemetry import aggregate
doc = json.load(open(sys.argv[1]))
m = doc["merged"]
# The tentpole claim: the shrink was an IN-PROCESS event, not a
# relaunch — one reformation, zero elastic restarts, both survivors
# timed their reformation.
assert aggregate.counter_total(
    m, "hvd_failinplace_reformations_total") == 1, sorted(m.keys())
assert aggregate.counter_total(m, "hvd_elastic_restarts_total") == 0, \
    "an elastic restart leaked into the fail-in-place gate"
h, = m["hvd_failinplace_reformation_seconds"]["values"]
assert h["count"] == 2, h
print("FAILINPLACE_METRICS_OK reformations=1 elastic_restarts=0 "
      f"reform_seconds_mean={h['sum'] / h['count']:.2f}")
PYEOF
rm -rf "$FIP_DIR"

echo "--- coordination protocol simulator, fast lane (docs/
--- control_plane.md): agreement safety, bounded fan-in, chaos
--- convergence — pure-Python virtual network, no sockets"
JAX_PLATFORMS=cpu python -m pytest tests/test_coordsim.py \
  tests/test_coordination.py -x -q

echo "--- coordinator-failover gate (np=4, 2 hosts over fake ssh): both
--- ranks on the coordinator's host SIGKILL after committing step 4;
--- the launcher must demote the host, expire the lease, elect the
--- survivor (epoch 0->1), warm-restart from peer spill and converge —
--- the merged metrics must count the election (docs/control_plane.md)"
COORD_DIR="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  HOROVOD_SSH_CMD="ci/fake_ssh.sh" \
  HOROVOD_METRICS_FILE="$COORD_DIR/metrics.json" \
  HOROVOD_TERMINATE_GRACE_SECONDS=3 \
  python -m horovod_tpu.runner -np 4 -H 127.0.1.1:2,localhost:2 \
  --elastic-restarts 1 --min-np 2 \
  python tests/distributed/coord_failover_np4.py \
  2> "$COORD_DIR/err.log" | tee "$COORD_DIR/out.log"
cat "$COORD_DIR/err.log" >&2
grep -q "coordinator lease expired (host 127.0.1.1 gone); elected host localhost as coordinator epoch=1" \
  "$COORD_DIR/err.log"
grep -q "COORD_OK attempt=1 rank=0 size=2 epoch=1 source=spill committed=4" \
  "$COORD_DIR/out.log"
python - "$COORD_DIR/metrics.json" <<'PYEOF'
import json, sys
from horovod_tpu.telemetry import aggregate
doc = json.load(open(sys.argv[1]))
assert aggregate.counter_total(
    doc["merged"], "hvd_coord_elections_total") >= 1, doc["merged"].keys()
print("coordinator failover metrics OK")
PYEOF
rm -rf "$COORD_DIR"

echo "--- tree-coordination gate (np=4, 2 hosts over fake ssh,
--- HOROVOD_COORD_TREE=1): members wire to their host leader, leaders
--- to the master; the collective matrix must be bit-identical and
--- every rank must report tree mode active (docs/control_plane.md)"
JAX_PLATFORMS=cpu python -m pytest \
  tests/test_chaos.py::test_chaos_tree_coordination_two_host_matrix -x -q

echo "--- heartbeat gate (2 ranks): rank 1's heartbeats chaos-dropped;
--- the health plane must SIGKILL it at the heartbeat deadline and
--- elastic-restart on the surviving host — without the watchdog this
--- lane cannot finish (workers sleep 600s)"
HB_DIR="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  HOROVOD_SSH_CMD="ci/fake_ssh.sh" \
  HOROVOD_TERMINATE_GRACE_SECONDS=3 \
  HOROVOD_FAULT_SPEC="rank=1,site=heartbeat,after=3,kind=heartbeat_drop,attempt=0" \
  timeout 150 \
  python -m horovod_tpu.runner -np 2 -H localhost:1,127.0.1.1:1 \
  --elastic-restarts 1 --min-np 1 --heartbeat-interval 0.2 \
  python ci/heartbeat_gate_workload.py \
  2> "$HB_DIR/err.log" | tee "$HB_DIR/out.log"
grep -q "HB_OK attempt=1 rank=0 size=1" "$HB_DIR/out.log"
grep -q "health plane: rank 1 sent no heartbeat" "$HB_DIR/err.log"
rm -rf "$HB_DIR"

echo "--- fleet gate (2 jobs, 3 slots over fake ssh): priority-1 trainB
--- takes the whole pool, priority-2 quickA starves past the deadline,
--- the controller preempts trainB (rc 75, coordinated save, NO
--- blacklist), admits quickA, re-admits trainB shrunken to np=2 and it
--- resumes from the preemption checkpoint (docs/fleet.md).
--- FLEET_GATE_* rides inline via env(1): the ssh rank path only
--- forwards HOROVOD_*/PYTHONPATH/PATH/XLA_*/JAX_* variables."
FLEET_DIR="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  HOROVOD_SSH_CMD="ci/fake_ssh.sh" \
  HOROVOD_TERMINATE_GRACE_SECONDS=15 \
  timeout 150 \
  python -m horovod_tpu.runner fleet \
  -H localhost:1,127.0.1.1:1,127.0.1.2:1 \
  --starvation-deadline 2 --tick-interval 0.25 \
  --metrics-file "$FLEET_DIR/fleet.json" \
  --job "trainB 1 2:3 -- env FLEET_GATE_CKPT=$FLEET_DIR/ckpt \
FLEET_GATE_STEPS=40 FLEET_GATE_STEP_SECONDS=0.25 \
python tests/distributed/fleet_np2.py" \
  --job "quickA 2 1 after=6 -- echo QUICK_OK" \
  2> "$FLEET_DIR/err.log" | tee "$FLEET_DIR/out.log"
grep -q "admit job trainB np=3" "$FLEET_DIR/err.log"
grep -q "preempting job trainB" "$FLEET_DIR/err.log"
grep -q "job trainB preempted (rc 75)" "$FLEET_DIR/err.log"
grep -q "admit job quickA np=1" "$FLEET_DIR/err.log"
grep -q "admit job trainB np=2" "$FLEET_DIR/err.log"
grep -q "QUICK_OK" "$FLEET_DIR/out.log"
grep -q "FLEET_RESUME job=trainB" "$FLEET_DIR/out.log"
grep -q "FLEET_OK job=trainB" "$FLEET_DIR/out.log"
! grep -q "blacklisting host" "$FLEET_DIR/err.log"
python - "$FLEET_DIR/fleet.json" <<'PYEOF'
import json, sys
doc = json.load(open(sys.argv[1]))
assert doc["schema"] == "horovod_tpu.fleet.summary.v1", doc["schema"]
assert doc["jobs"]["trainB"]["state"] == "done", doc["jobs"]
assert doc["jobs"]["trainB"]["preemptions"] == 1, doc["jobs"]
assert doc["jobs"]["quickA"]["state"] == "done", doc["jobs"]
print("fleet summary OK")
PYEOF
rm -rf "$FLEET_DIR"

echo "--- serving gate (np=2): two tenants stream concurrently over two
--- RPC replica workers with token-level continuous batching (merged
--- batch occupancy > 1), then a hot weight update rides the broadcast
--- plane mid-stream — every in-flight stream flips generations exactly
--- at its pause point with ZERO dropped requests (docs/serving.md)"
SERVE_DIR="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  HOROVOD_SERVING_GATE_DIR="$SERVE_DIR/gate" \
  HOROVOD_METRICS_FILE="$SERVE_DIR/metrics.json" \
  timeout 120 \
  python -m horovod_tpu.runner -np 2 \
  python tests/distributed/serving_np2.py | tee "$SERVE_DIR/out.log"
grep -q "SERVING_OK rank=0 completed=14 dropped=0 tenants=alice,bob" \
  "$SERVE_DIR/out.log"
grep -q "SERVING_REPLICA_OK rank=1 staged_gen=1" "$SERVE_DIR/out.log"
python - "$SERVE_DIR/metrics.json" <<'PYEOF'
import json, sys
doc = json.load(open(sys.argv[1]))
assert doc["schema"] == "horovod_tpu.metrics.summary.v1", doc["schema"]
m = doc["merged"]

def total(name, **labels):
    out = 0.0
    for e in m[name]["values"]:
        if all(e["labels"].get(k) == v for k, v in labels.items()):
            out += e["value"]
    return out

# Both tenants completed every request; nothing was dropped.
assert total("hvd_serving_completed_total", tenant="alice") == 7, m
assert total("hvd_serving_completed_total", tenant="bob") == 7, m
assert "hvd_serving_dropped_total" not in m, m["hvd_serving_dropped_total"]
# Continuous batching actually batched: mean occupancy > 1 slot/step.
occ, = m["hvd_serving_batch_occupancy"]["values"]
assert occ["count"] and occ["sum"] / occ["count"] > 1, occ
# One hot update staged per replica, and both ranks decoded.
assert total("hvd_serving_weight_updates_total") == 2, m
for rank, rdoc in doc["ranks"].items():
    steps = rdoc["metrics"]["hvd_serving_decode_steps_total"]["values"]
    assert steps and steps[0]["value"] > 0, (rank, steps)
print("serving np=2 metrics OK")
PYEOF
rm -rf "$SERVE_DIR"

echo "--- fleet-serving gate (serving + batch jobs, 3 local slots): a
--- request storm floods the type=serving job's queues, its published
--- stats cross --serving-scale-up-depth, the autoscaler preempts the
--- lower-priority training job, grows serving into the freed slots,
--- then shrinks it back after --serving-scale-down-idle calm seconds
--- and training resumes from its preemption checkpoint — the whole
--- episode asserted from controller hvd_fleet_serving_* metrics"
SFLEET_DIR="$(mktemp -d)"
JAX_PLATFORMS=cpu PYTHONPATH="$PWD" \
  HOROVOD_FAULT_SPEC="rank=0,site=serving,after=10,kind=request_storm:80,attempt=0" \
  timeout 150 \
  python -m horovod_tpu.runner fleet \
  -H localhost:3 \
  --starvation-deadline 60 --tick-interval 0.25 --grow-after 300 \
  --serving-scale-up-depth 8 --serving-scale-down-idle 3 \
  --metrics-file "$SFLEET_DIR/fleet.json" \
  --job "serveA 2 1:2 type=serving -- env \
HOROVOD_SERVING_GATE_DIR=$SFLEET_DIR/gate SERVING_GATE_SECONDS=18 \
python tests/distributed/serving_fleet_job.py" \
  --job "trainB 1 2:2 -- env FLEET_GATE_CKPT=$SFLEET_DIR/ckpt \
FLEET_GATE_STEPS=40 FLEET_GATE_STEP_SECONDS=0.25 \
python tests/distributed/fleet_np2.py" \
  2> "$SFLEET_DIR/err.log" | tee "$SFLEET_DIR/out.log"
grep -q "firing kind=request_storm at site=serving" "$SFLEET_DIR/out.log"
grep -q "serving job serveA under pressure" "$SFLEET_DIR/err.log"
grep -q "preempting job trainB .*serveA needs capacity" "$SFLEET_DIR/err.log"
grep -q "serving scale-up 1->2" "$SFLEET_DIR/err.log"
grep -q "admit job serveA np=2" "$SFLEET_DIR/err.log"
grep -q "serving scale-down 2->1" "$SFLEET_DIR/err.log"
test "$(grep -c "admit job serveA np=1" "$SFLEET_DIR/err.log")" -ge 2
grep -q "admit job trainB np=2 priority=1 attempt=1" "$SFLEET_DIR/err.log"
grep -q "SERVING_FLEET_STATS completed=[0-9]* dropped=0" "$SFLEET_DIR/out.log"
grep -q "SERVING_FLEET_OK rank=0" "$SFLEET_DIR/out.log"
grep -q "FLEET_RESUME job=trainB" "$SFLEET_DIR/out.log"
grep -q "FLEET_OK job=trainB" "$SFLEET_DIR/out.log"
python - "$SFLEET_DIR/fleet.json" <<'PYEOF'
import json, sys
doc = json.load(open(sys.argv[1]))
assert doc["schema"] == "horovod_tpu.fleet.summary.v1", doc["schema"]
serve, train = doc["jobs"]["serveA"], doc["jobs"]["trainB"]
assert serve["state"] == "done" and serve["type"] == "serving", serve
assert train["state"] == "done" and train["preemptions"] >= 1, train
scale = {(e["labels"]["job"], e["labels"]["direction"]): e["value"]
         for e in doc["controller"]["metrics"]
         ["hvd_fleet_serving_scale_events_total"]["values"]}
assert scale.get(("serveA", "grow"), 0) >= 1, scale
assert scale.get(("serveA", "shrink"), 0) >= 1, scale
# Final (post-shrink) attempt served trickle traffic cleanly.
reqs = doc["jobs"]["serveA"]["merged"]["hvd_serving_requests_total"]
assert sum(e["value"] for e in reqs["values"]) > 0, reqs
print("fleet-serving summary OK")
PYEOF
rm -rf "$SFLEET_DIR"

echo "--- transport backend A/B (six hvdrun -np 2 loopback runs of
--- tools/bench_eager.py: single socket (CRC-framed + unframed) vs shm
--- ring vs striped x1/x2/x4 — every worker asserts the forced backend
--- carried the bytes, headline ratios come from the thread-CPU link
--- counters so a single-core runner measures the transport, not the
--- scheduler; the checksum A/B bounds the wire-integrity overhead at
--- 64 MB)"
TRANSPORT_JSON="$PWD/ci/artifacts/eager/transport.json"
python tools/bench_eager.py --transport --out "$TRANSPORT_JSON"
python - "$TRANSPORT_JSON" <<'EOF'
import json, sys
doc = json.load(open(sys.argv[1]))
assert doc["backend_engagement_asserted"]
assert doc["shm_vs_socket_64mb"] > 1.0, doc["shm_vs_socket_64mb"]
assert doc["striped4_vs_striped1_64mb"] > 1.0, \
    doc["striped4_vs_striped1_64mb"]
assert doc["checksum_overhead_64mb"] < 0.05, \
    f"CRC32C framing cost {doc['checksum_overhead_64mb']:.1%} of link " \
    f"bandwidth at 64 MB (target < 5%)"
print("TRANSPORT_GATE_OK shm=%.2fx striped4=%.2fx crc_overhead=%.1f%%" %
      (doc["shm_vs_socket_64mb"], doc["striped4_vs_striped1_64mb"],
       doc["checksum_overhead_64mb"] * 100))
EOF

echo "--- sanitizer lane (TSAN build + np=2 distributed suite; races
--- attributed to libhorovod_tpu.so fail CI, jaxlib/XLA noise is
--- suppressed by native/cc/tsan.supp; raw logs + triage summary are
--- archived under ci/artifacts/sanitizer/)"
ci/run_sanitizer.sh tsan

echo "CI OK"
