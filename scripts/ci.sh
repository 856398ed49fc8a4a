#!/usr/bin/env bash
# Tier-1 CI: docs gate (README/ARCHITECTURE present, public-surface doctests,
# quickstart's sharded stanza), install test extras, run the streaming +
# fleet + sharded-fleet + transport + anomaly-monitor + observability +
# windowed vetting differential suites explicitly
# (with JUnit XML reports), then the full pytest suite, then a fast
# VetEngine smoke benchmark (batch + windowed + streaming sections: backend
# agreement, batched-vs-scalar speedup, cached-tick cost,
# incremental-tick-vs-regather speedup).
#
# Usage: scripts/ci.sh [extra pytest args...]
# JUnit XML lands in ${CI_REPORTS_DIR:-reports}/ for CI systems that ingest it.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
REPORTS_DIR="${CI_REPORTS_DIR:-reports}"
mkdir -p "$REPORTS_DIR"

# Docs gate: the repo ships its own map.  README.md and docs/ARCHITECTURE.md
# must exist, every docstring example on the public estimation surface must
# run (doctests on engine/ + fleet/ + the routed OnlineVet/VetController),
# and the quickstart's sharded-fleet stanza must work end to end.
echo "[ci] docs gate: README + ARCHITECTURE + doctests + quickstart stanza 6"
for doc in README.md docs/ARCHITECTURE.md; do
  if [ ! -f "$doc" ]; then
    echo "[ci] FAIL: $doc is missing (the docs gate requires it)"
    exit 1
  fi
done
docs_status=0
python -m pytest -q --doctest-modules \
  --junitxml="$REPORTS_DIR/doctest.xml" \
  src/repro/engine src/repro/fleet \
  src/repro/core/online.py src/repro/sched/straggler.py \
  || docs_status=$?
if [ "$docs_status" -ne 0 ]; then
  echo "[ci] FAIL: public-surface doctests exited $docs_status"
  exit "$docs_status"
fi
python examples/quickstart.py --stanza 6 || {
  echo "[ci] FAIL: quickstart stanza 6 (sharded fleet) did not run"
  exit 1
}

# Streaming vetting first and explicitly (-x): the streaming differential
# suite locks every incremental tick to the batch oracle, and the simulator
# determinism suite pins the ground truth every oracle is built from — if
# these break, the full-suite report below is noise.
echo "[ci] streaming vetting: differential + simulator-determinism suites"
streaming_status=0
python -m pytest -q -x \
  --junitxml="$REPORTS_DIR/streaming.xml" \
  tests/test_vet_stream.py \
  tests/test_simulator_determinism.py \
  || streaming_status=$?

# Fleet multiplexing next: the mux differential suite locks every coalesced
# dispatch to the per-stream oracle across the scenario bank, and the smoke
# suite is the fast (<= 64 workers, numpy) tier-1 path.
echo "[ci] fleet vetting: mux differential + scheduler + smoke suites"
fleet_status=0
python -m pytest -q -x \
  --junitxml="$REPORTS_DIR/fleet.xml" \
  tests/test_fleet.py \
  tests/test_fleet_smoke.py \
  || fleet_status=$?

# Sharded fleets: per-stream rows vs the single-mux oracle across the bank
# and all backends, merged job-level vets, deterministic placement, the
# scenario-bank edge cases, and the <= 64-worker / 2-shard numpy smoke.
echo "[ci] sharded fleet: shard differential + scenario + smoke suites"
shard_status=0
python -m pytest -q -x \
  --junitxml="$REPORTS_DIR/shard.xml" \
  tests/test_fleet_shard.py \
  tests/test_fleet_shard_smoke.py \
  tests/test_fleet_scenarios.py \
  || shard_status=$?

# Cross-process transport: the process-driver differential + kill-mid-tick
# recovery suites, under a hard timeout so a hung worker pool (a dead pipe
# that never times out, a respawn loop) fails the stage fast instead of
# wedging CI.  `timeout` sends TERM, then KILL 30s later if ignored.
echo "[ci] transport: process-driver differential + crash-recovery suites"
transport_status=0
timeout -k 30 600 python -m pytest -q -x \
  --junitxml="$REPORTS_DIR/transport.xml" \
  tests/test_fleet_transport.py \
  || transport_status=$?
if [ "$transport_status" -eq 124 ]; then
  echo "[ci] transport suite timed out (hung worker pool?)"
fi

# Anomaly monitoring: the live change-point monitor against the anomaly
# scenario bank (onset localization within +/-2 ticks on every backend,
# sharded/transport flag plumbing, checkpoint/resume), plus the change-point
# edge-case regressions (short-input guards, f64 index-sum precision) and
# the hypothesis property suite (skips offline).
echo "[ci] anomaly monitor: detection differential + change-point edge suites"
anomaly_status=0
python -m pytest -q -x \
  --junitxml="$REPORTS_DIR/anomaly.xml" \
  tests/test_fleet_anomaly.py \
  tests/test_changepoint_edges.py \
  tests/test_changepoint_properties.py \
  || anomaly_status=$?

# Observability: tracer/metrics/export/ledger semantics plus the
# instrumented fleet seam (traced-vs-untraced differential, cross-process
# span adoption, respawn re-enable), then a live trace-export-and-validate:
# quickstart stanza 7 dumps a Chrome trace and validate_chrome must pass it.
echo "[ci] observability: tracer + export + ledger suites, trace validate"
obs_status=0
python -m pytest -q -x \
  --junitxml="$REPORTS_DIR/obs.xml" \
  tests/test_obs.py \
  || obs_status=$?
if [ "$obs_status" -eq 0 ]; then
  python examples/quickstart.py --stanza 7 \
    --trace "$REPORTS_DIR/quickstart_trace.json" >/dev/null \
    || obs_status=$?
fi
if [ "$obs_status" -eq 0 ]; then
  python - "$REPORTS_DIR/quickstart_trace.json" <<'PY' || obs_status=$?
import json, sys
from repro.obs import validate_chrome
problems = validate_chrome(json.load(open(sys.argv[1])))
if problems:
    print("[ci] trace validation problems:", *problems, sep="\n  ")
    sys.exit(1)
print(f"[ci] quickstart trace validated ({sys.argv[1]})")
PY
fi

# Online autotuner: the simulator-recoverability lock (online VetTuner ==
# grid oracle exactly with noise off, within one knob step under seeded
# noise, all backends), the knob_hooks seam, and the elbow/SPSA/rollback
# property suite (skips offline).
echo "[ci] autotuner: recoverability differential + property suites"
tuner_status=0
python -m pytest -q -x \
  --junitxml="$REPORTS_DIR/tuner.xml" \
  tests/test_tuner.py \
  tests/test_tuner_properties.py \
  || tuner_status=$?

# Windowed vetting next (same reasoning for the batched sliding/ragged path).
echo "[ci] windowed vetting: differential + property + benchmark-smoke suites"
windowed_status=0
python -m pytest -q -x \
  --junitxml="$REPORTS_DIR/windowed.xml" \
  tests/test_vet_windows.py \
  tests/test_vet_windows_properties.py \
  tests/test_benchmarks_smoke.py \
  || windowed_status=$?

# Fused window-vet kernel: the one-launch ragged path against its ladder
# (gather rung bitwise on the cut, f64 scalar root), including the ring-wrap
# seam and the one-dispatch fused mux tick.
echo "[ci] fused window-vet: kernel differential + property suites"
windowvet_status=0
python -m pytest -q -x \
  --junitxml="$REPORTS_DIR/windowvet.xml" \
  tests/test_windowvet.py \
  tests/test_windowvet_properties.py \
  || windowvet_status=$?

# Full run (no -x) so the report covers every module, and the engine smoke
# below still executes when a test fails; exit status reflects the tests.
# The streaming/windowed suites already ran above, so they are not run twice.
echo "[ci] tier-1: pytest"
status=0
python -m pytest -q \
  --junitxml="$REPORTS_DIR/tier1.xml" \
  --ignore=tests/test_vet_stream.py \
  --ignore=tests/test_simulator_determinism.py \
  --ignore=tests/test_fleet.py \
  --ignore=tests/test_fleet_smoke.py \
  --ignore=tests/test_fleet_shard.py \
  --ignore=tests/test_fleet_shard_smoke.py \
  --ignore=tests/test_fleet_scenarios.py \
  --ignore=tests/test_fleet_transport.py \
  --ignore=tests/test_fleet_anomaly.py \
  --ignore=tests/test_changepoint_edges.py \
  --ignore=tests/test_changepoint_properties.py \
  --ignore=tests/test_obs.py \
  --ignore=tests/test_tuner.py \
  --ignore=tests/test_tuner_properties.py \
  --ignore=tests/test_vet_windows.py \
  --ignore=tests/test_vet_windows_properties.py \
  --ignore=tests/test_benchmarks_smoke.py \
  --ignore=tests/test_windowvet.py \
  --ignore=tests/test_windowvet_properties.py \
  "$@" || status=$?

echo "[ci] smoke: VetEngine backend benchmark (batch + windowed + streaming)"
smoke_status=0
python -m benchmarks.run --only vet_engine || smoke_status=$?

if [ "$streaming_status" -ne 0 ]; then
  echo "[ci] FAIL: streaming vetting suites exited $streaming_status"
  exit "$streaming_status"
fi
if [ "$fleet_status" -ne 0 ]; then
  echo "[ci] FAIL: fleet vetting suites exited $fleet_status"
  exit "$fleet_status"
fi
if [ "$shard_status" -ne 0 ]; then
  echo "[ci] FAIL: sharded fleet suites exited $shard_status"
  exit "$shard_status"
fi
if [ "$transport_status" -ne 0 ]; then
  echo "[ci] FAIL: transport suites exited $transport_status"
  exit "$transport_status"
fi
if [ "$anomaly_status" -ne 0 ]; then
  echo "[ci] FAIL: anomaly-monitor suites exited $anomaly_status"
  exit "$anomaly_status"
fi
if [ "$obs_status" -ne 0 ]; then
  echo "[ci] FAIL: observability suites / trace validation exited $obs_status"
  exit "$obs_status"
fi
if [ "$tuner_status" -ne 0 ]; then
  echo "[ci] FAIL: autotuner suites exited $tuner_status"
  exit "$tuner_status"
fi
if [ "$windowed_status" -ne 0 ]; then
  echo "[ci] FAIL: windowed vetting suites exited $windowed_status"
  exit "$windowed_status"
fi
if [ "$windowvet_status" -ne 0 ]; then
  echo "[ci] FAIL: fused window-vet suites exited $windowvet_status"
  exit "$windowvet_status"
fi
if [ "$status" -ne 0 ]; then
  echo "[ci] FAIL: pytest exited $status"
  exit "$status"
fi
if [ "$smoke_status" -ne 0 ]; then
  echo "[ci] FAIL: vet_engine smoke benchmark exited $smoke_status"
  exit "$smoke_status"
fi
echo "[ci] OK"
