#!/usr/bin/env bash
# Full-suite gate: run before any milestone/snapshot commit.
# Exits nonzero if ANY check fails — never snapshot red (VERDICT r3 #6).
#
# Order is cheap-first: static analysis (~4 s, per-engine counts and
# wall time printed in its summary line) before the test suite
# (~6 min), so a tracer leak, deadlock hazard, or contract drift
# fails in seconds.
#
#   tools/gate.sh                normal gate (baseline-tolerant)
#   tools/gate.sh --strict       piolint ignores piolint.baseline.json —
#                                periodic full-debt review of accepted
#                                findings; baselined PIO21x deadlock
#                                entries must carry a justification
#
# Any further args pass through to pytest.
set -euo pipefail
cd "$(git rev-parse --show-toplevel)"

PIOLINT_ARGS=()
if [ "${1:-}" = "--strict" ]; then
  PIOLINT_ARGS+=(--strict)
  shift
fi

# 1) piolint: JAX/lock/deadlock/contract static analysis
#    (PIO1xx/PIO2xx incl. PIO210-213 deadlock, PIO3xx, PIO4xx contract)
REPORT="${PIOLINT_REPORT:-/tmp/piolint_report.json}"
echo "gate [1/17] piolint (report: $REPORT)" >&2
if ! python -m predictionio_tpu.analysis --format text \
       --report "$REPORT" "${PIOLINT_ARGS[@]+"${PIOLINT_ARGS[@]}"}"; then
  echo "gate FAILED: piolint found non-baseline findings" >&2
  echo "  full JSON report: $REPORT" >&2
  echo "  suppress a finding inline with '# piolint: disable=PIOxxx'," >&2
  echo "  or accept it with a justified entry in piolint.baseline.json" >&2
  exit 1
fi

# 2) generic lint (ruff: pyflakes + isort per pyproject.toml) — the CI
# image doesn't ship ruff, so absence is a skip, not a failure
echo "gate [2/17] ruff" >&2
if command -v ruff >/dev/null 2>&1; then
  ruff check . || { echo "gate FAILED: ruff" >&2; exit 1; }
elif python -m ruff --version >/dev/null 2>&1; then
  python -m ruff check . || { echo "gate FAILED: ruff" >&2; exit 1; }
else
  echo "  ruff not installed; skipping generic lint" >&2
fi

# 3) gather-form smoke: the row and grouped gathers return the same
# rows (tools/probe_gather.py --smoke — shape/row validation; whether
# the solve kernel compiles at rank 64 is answered on the chip by
# chip_smoke.py's pallas train) — cheap-first so a gather break fails
# in seconds, not after the full suite
echo "gate [3/17] gather probe smoke" >&2
if ! JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
     python tools/probe_gather.py --smoke > /tmp/probe_gather_smoke.json; then
  echo "gate FAILED: gather-form smoke (see /tmp/probe_gather_smoke.json)" >&2
  exit 1
fi

# 4) pio-scout smoke: the two-stage ANN retrieval contract on a tiny
# catalog — recall@10 == 1.0 at covering candidate_factor (the rerank
# really is exact math restricted to the shortlist), stage metrics
# booked, and one fold-in delta patching the quantized index IN PLACE
# (no rebuild) with the appended + patched rows served immediately
echo "gate [4/17] ann smoke" >&2
ANN_OUT="${ANN_SMOKE_OUT:-/tmp/ann_smoke.json}"
if ! JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
     python tools/ann_smoke.py --out "$ANN_OUT"; then
  echo "gate FAILED: ann smoke (see $ANN_OUT)" >&2
  exit 1
fi

# 5) pio-xray smoke: boots a trained engine server with the ALS phase
# tracer armed, forces a serving-path recompile, and asserts the
# compiler-observability contract (pio_jit_compiles_total increments,
# /debug/xray's recompile ring parses and carries the signature delta,
# exemplar trace ids resolve to flight-recorder span trees)
echo "gate [5/17] xray smoke" >&2
XRAY_OUT="${XRAY_SMOKE_OUT:-/tmp/xray_smoke.json}"
if ! JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" PIO_TPU_TRACE_ALS=1 \
     python tools/xray_smoke.py --out "$XRAY_OUT"; then
  echo "gate FAILED: xray smoke (see $XRAY_OUT)" >&2
  exit 1
fi

# 6) pio-pulse smoke: boots a real engine + event server, fires
# concurrent closed-loop load through tools/loadgen.py, and asserts the
# request-lifecycle decomposition contract (every segment present in
# /metrics with equal counts, segment sums reconcile with the e2e
# latency histogram, saturation metrics move, /debug/profile produces a
# non-empty jax.profiler artifact, flight records carry segmentsMs)
echo "gate [6/17] pulse smoke" >&2
PULSE_OUT="${PULSE_SMOKE_OUT:-/tmp/pulse_smoke.json}"
if ! JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
     python tools/pulse_smoke.py --out "$PULSE_OUT"; then
  echo "gate FAILED: pulse smoke (see $PULSE_OUT)" >&2
  exit 1
fi

# 7) pio-live smoke: event server + engine server over sqlite, events
# for an unseen user, one fold-in cycle, non-fallback predictions with
# ZERO /reload calls and a stable fold-in kernel signature — the
# event->fresh-prediction contract end to end
echo "gate [7/17] foldin smoke" >&2
FOLDIN_OUT="${FOLDIN_SMOKE_OUT:-/tmp/foldin_smoke.json}"
if ! JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
     python tools/foldin_smoke.py --out "$FOLDIN_OUT"; then
  echo "gate FAILED: foldin smoke (see $FOLDIN_OUT)" >&2
  exit 1
fi

# 8) pio-surge smoke: router + 2 REAL replica subprocesses on the
# event-loop edge — round-robin serving, one fold-in delta pushed
# rolling across the fleet (both replicas answer fresh predictions
# with ZERO reloads), and a SIGKILLed replica masked from clients
# with zero failed requests
echo "gate [8/17] surge smoke" >&2
SURGE_OUT="${SURGE_SMOKE_OUT:-/tmp/surge_smoke.json}"
if ! JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
     python tools/surge_smoke.py --out "$SURGE_OUT"; then
  echo "gate FAILED: surge smoke (see $SURGE_OUT)" >&2
  exit 1
fi

# 9) pio-hive smoke: ONE server hosting 2 apps x 2 variants over
# sqlite — sticky weighted A/B routing, a tenant-scoped fault plan
# opening tenant A's breaker while tenant B serves 0 errors, quota
# isolation, budget-driven eviction with zero failed in-flight
# requests + lazy reload, and per-variant feedback attribution grepped
# back out of the event store into /metrics + a pio-tower manifest
echo "gate [9/17] hive smoke" >&2
HIVE_OUT="${HIVE_SMOKE_OUT:-/tmp/hive_smoke.json}"
if ! JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
     python tools/hive_smoke.py --out "$HIVE_OUT"; then
  echo "gate FAILED: hive smoke (see $HIVE_OUT)" >&2
  exit 1
fi

# 10) pio-pilot smoke: ONE server hosting 2 apps x 2 variants with the
# SPRT auto-weight controller closed-loop — a seeded conversion gap
# concludes its own A/B (bounded ramp steps landing as REAL
# POST /tenants/weights calls, loser floored at minWeight, every
# decision in a pio-tower manifest), and a fault-plan-broken variant
# with the BEST conversion rate is guardrail-vetoed back down
echo "gate [10/17] pilot smoke" >&2
PILOT_OUT="${PILOT_SMOKE_OUT:-/tmp/pilot_smoke.json}"
if ! JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
     python tools/pilot_smoke.py --out "$PILOT_OUT"; then
  echo "gate FAILED: pilot smoke (see $PILOT_OUT)" >&2
  exit 1
fi

# 11) pio-tower smoke: a tiny real train through run_train — complete
# run manifest, per-sweep phase sums reconciling with the train.run
# wall time within 2%, a typed watchdog abort on an injected NaN
# sweep (train.nan fault point), the cluster registry merge on a
# chief's /metrics, and the runlog CLI over the produced manifests
echo "gate [11/17] train obs smoke" >&2
TOWER_OUT="${TRAIN_OBS_SMOKE_OUT:-/tmp/train_obs_smoke.json}"
if ! JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
     python tools/train_obs_smoke.py --out "$TOWER_OUT"; then
  echo "gate FAILED: train obs smoke (see $TOWER_OUT)" >&2
  exit 1
fi

# 12) pio-forge smoke: a from-scratch ONE-FILE engine written to a temp
# dir and named via PIO_TPU_ENGINE_PATH must register, show up in
# `pio-tpu engines list`, train via `train --engine`, serve real HTTP
# queries, and move the engine-labeled query counter — the one-file-
# engine contract end to end (piolint's PIO301 separately guards that
# engine files never import server internals)
echo "gate [12/17] forge smoke" >&2
FORGE_OUT="${FORGE_SMOKE_OUT:-/tmp/forge_smoke.json}"
if ! JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
     python tools/forge_smoke.py --out "$FORGE_OUT"; then
  echo "gate FAILED: forge smoke (see $FORGE_OUT)" >&2
  exit 1
fi

# 13) pio-lens smoke: router + 2 REAL replica subprocesses — the
# router's merged /metrics equals the sum of the replicas' (strict
# exposition grammar), a SIGSTOPped replica's tail is attributed to it
# by the router flight recorder while the merged counters stay
# monotone through the stall, and tools/tracecat.py stitches one trace
# id across the router's and a replica's span journals into ONE tree
echo "gate [13/17] fleet smoke" >&2
FLEET_OUT="${FLEET_SMOKE_OUT:-/tmp/fleet_smoke.json}"
if ! JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
     python tools/fleet_smoke.py --out "$FLEET_OUT"; then
  echo "gate FAILED: fleet smoke (see $FLEET_OUT)" >&2
  exit 1
fi

# 14) pio-levee smoke: ingest router + 2 REAL shard-owner worker
# subprocesses with group-commit WALs — a SIGKILLed owner mid-load
# costs zero errors on healthy shards, its entities answer structured
# 503 + Retry-After (positionally inside batches), the federated
# /stats.json stays monotone through the death, and after a restart on
# the same WAL dir every acknowledged event is readable: zero acked
# loss
echo "gate [14/17] ingest smoke" >&2
INGEST_OUT="${INGEST_SMOKE_OUT:-/tmp/ingest_smoke.json}"
if ! JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
     python tools/ingest_smoke.py --out "$INGEST_OUT"; then
  echo "gate FAILED: ingest smoke (see $INGEST_OUT)" >&2
  exit 1
fi

# 15) pio-scope smoke: boots a REAL trained engine server (microbatch
# on, eventloop edge), floods it, and asserts the always-on profiler
# contract: /debug/pprof attributes samples to registered thread roles
# (eventloop + microbatch dispatcher at minimum), the contention lens
# books nonzero pio_lock_wait_seconds{lock="microbatch"} under the
# flood, the folded text renders to the self-contained flamegraph
# page, the worst-N flight records join dominantStacks from the ring,
# and an interleaved profiler on/off A/B keeps the on-arm p50 within
# the 5% budget (0.5 ms noise floor) with the self-measured overhead
# ratio under 5%
echo "gate [15/17] scope smoke" >&2
SCOPE_OUT="${SCOPE_SMOKE_OUT:-/tmp/scope_smoke.json}"
if ! JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}" \
     python tools/scope_smoke.py --out "$SCOPE_OUT"; then
  echo "gate FAILED: scope smoke (see $SCOPE_OUT)" >&2
  exit 1
fi

# 16) bench trajectory gate: the newest fenced BENCH_HISTORY.jsonl
# record must sit within the noise-aware threshold of its rolling
# median baseline; --allow-empty keeps the gate green until the
# trajectory is >= min-samples deep (it still fails on a judged
# regression)
echo "gate [16/17] bench trajectory (tools/bench_gate.py)" >&2
if ! python tools/bench_gate.py --check --allow-empty; then
  echo "gate FAILED: bench trajectory regressed beyond noise" >&2
  echo "  inspect: python tools/bench_gate.py --check" >&2
  exit 1
fi

# 17) the full test suite — includes the end-to-end smokes that boot
# real servers: tools/chaos_smoke.py (via tests/test_chaos_smoke.py),
# tools/obs_smoke.py (/metrics exposition + trace propagation),
# tools/xray_smoke.py, tools/foldin_smoke.py and
# tools/train_obs_smoke.py again under pytest env isolation
echo "gate [17/17] pytest" >&2
exec python -m pytest tests/ -q "$@"
