#!/usr/bin/env bash
# CI gate: lint + tier-1 tests + catalog freshness + a time-budgeted smoke
# pass of every registered scenario.  Exits nonzero on regression-shaped
# failures: lint errors, test failures, a stale scenario catalog, scenario
# SLO violations (p99 shielded from stragglers, bounded admitted p99 +
# nonzero shed rate past saturation, zero lost chunksets, ...), the 40 Mbps
# 4K bar, or blowing a smoke time budget (exit 124 is reported as exactly
# that, so the log says WHICH budget blew, not just "tests failed").
#
#   scripts/ci.sh                      # registry budgets per scenario
#   SCENARIO_BUDGET_SCALE=2 scripts/ci.sh   # slow runner: double budgets
#
# Scenario budgets live ON the registry entries (budget_s in
# src/repro/scenarios/*.py); the loop below reads them via
# `python -m repro.scenarios budgets` and SCENARIO_BUDGET_SCALE scales
# them uniformly.  Benchmark metrics are written to
# ${BENCH_JSON:-BENCH_backbone.json} (machine-readable; the GitHub Actions
# workflow uploads it as an artifact so the bench trajectory is tracked
# across PRs instead of scraped from stdout).
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH}
export BENCH_JSON="${BENCH_JSON:-BENCH_backbone.json}"

# run a smoke under `timeout`, distinguishing "budget exceeded" (timeout
# kills with 124) from an assertion/regression failure inside the smoke
run_budgeted() {
    local budget="$1" what="$2"; shift 2
    local status=0
    timeout "$budget" "$@" || status=$?
    if [ "$status" -eq 124 ]; then
        echo "FAIL: $what smoke budget exceeded (${budget}s)" >&2
        exit 124
    elif [ "$status" -ne 0 ]; then
        echo "FAIL: $what failed (exit $status)" >&2
        exit "$status"
    fi
}

echo "== lint: ruff =="
# config lives in pyproject.toml; the container image may not ship ruff
# (no network installs allowed there), so skip with a loud note — the
# GitHub Actions workflow installs it and enforces the gate on every PR
if command -v ruff >/dev/null 2>&1; then
    ruff check src tests benchmarks examples scripts
else
    echo "ruff not installed; lint gate skipped (enforced in GitHub Actions)"
fi

echo "== lint: simlint =="
# determinism linter over the sim path (net/ storage/ core/ scenarios/):
# exit 0 clean, 1 on new findings or stale baseline entries, 2 on internal
# error — so CI distinguishes "gate found problems" from "gate is broken".
# Stdlib-only (ast), so unlike ruff it always runs here.  Rule catalog and
# the pragma/baseline workflow: docs/simlint.md
python -m repro.analysis --check

echo "== tier-1: pytest =="
python -m pytest -q

echo "== scenario catalog freshness =="
# docs/CATALOG.md is generated from the registry + the COMMITTED bench
# sidecar; this gate runs BEFORE the smokes below rewrite $BENCH_JSON so
# freshness is always judged against what is committed
python scripts/gen_scenario_catalog.py --check

# NOTE: no `rm -f "$BENCH_JSON"` here — emit_json merges sections
# read-modify-write, so a pre-existing sidecar (earlier partial run, a
# caller accumulating several suites into one file) keeps its other
# sections instead of being clobbered; corrupt files are tolerated and
# rewritten atomically by repro.scenarios.report.
echo "== scenario smokes (registry budgets x SCENARIO_BUDGET_SCALE=${SCENARIO_BUDGET_SCALE:-1.0}) =="
# every registered scenario runs headless at smoke size: the runner
# resolves its knobs, replays its workload, asserts its declared SLOs
# (violations name the scenario), and merges its section into $BENCH_JSON
python -m repro.scenarios budgets | while read -r name budget; do
    echo "-- scenario: $name (budget: ${budget}s) --"
    BACKBONE_SMOKE=1 run_budgeted "$budget" "scenario $name" \
        python -m repro.scenarios run "$name"
done

echo "== simsan smoke: background scenario under the sanitizer (budget: ${SIMSAN_BUDGET_S:-240}s) =="
# re-run one full scenario with the event-loop sanitizer armed
# (SHELBY_SIMSAN=1): pop-order audits, slot-leak detection at drain,
# off-loop mutation guards, per-epoch payment conservation.  The sanitizer
# only observes — the scenario's results (and its $BENCH_JSON section) are
# byte-identical to the plain run above — so a nonzero exit here means a
# real simulation-safety violation, not flake.
SHELBY_SIMSAN=1 BACKBONE_SMOKE=1 run_budgeted "${SIMSAN_BUDGET_S:-240}" "simsan background" \
    python -m repro.scenarios run background

echo "== read-throughput smoke (budget: ${SMOKE_BUDGET_S:-600}s) =="
BACKBONE_SMOKE=1 run_budgeted "${SMOKE_BUDGET_S:-600}" "read throughput" \
    python -m benchmarks.run read_throughput

echo "== streaming smoke: video through BlobReader (budget: ${VIDEO_BUDGET_S:-120}s) =="
# exercises the session API end to end: open/stream receipts, pay-on-delivery,
# settlement conservation, and the 40 Mbps 4K bar under failures
VIDEO_SMOKE=1 run_budgeted "${VIDEO_BUDGET_S:-120}" "video streaming" \
    python examples/video_streaming.py

echo "== bench trajectory: $BENCH_JSON =="
python - <<'EOF'
import json, os
path = os.environ["BENCH_JSON"]
with open(path) as f:
    doc = json.load(f)
for section in ("serve_grid", "concurrent_ramp", "background", "churn", "das",
                "tune_admission"):
    assert section in doc, f"{path} missing section {section!r}"
print(f"{path}: {', '.join(sorted(doc))} OK")
EOF

echo "CI OK"
