#!/usr/bin/env bash
# Workload flight-recorder gate: proves the per-step recorder (ISSUE 8)
# stays within its <=2% step-time budget and that the whole diagnose
# surface — StepStats aggregation, straggler detection, goodput
# buckets, serve SLO histograms, `ray_tpu diagnose` — keeps working.
#
# Two layers:
#   1. tests/test_workload.py — aggregator math under dup/replay chaos,
#      deterministic straggler naming, MFU agreement with the
#      6 * params * tokens formula, goodput sum-exactness,
#      latency-histogram percentiles, the diagnose rule set, and the
#      live end-to-end run (train ->
#      workload series -> goodput -> /api/workload -> CLI);
#   2. the workload_recorder_overhead release entry under --smoke,
#      which enforces the smoke_criteria floors from
#      release/release_tests.yaml (paired off/on boot step rate, serve
#      burst, diagnose findings) and appends release_history.jsonl.
#
# The full-size measurement (3 boot pairs x 400 steps, <=5% gate,
# 2% budget) is the release suite proper:
#   python release/run_all.py --only workload_recorder_overhead
# Usage: ci/run_diagnose_smoke.sh [extra pytest args...]
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu

echo "== workload recorder + straggler + goodput + diagnose (pytest) =="
python -m pytest tests/test_workload.py -q -m 'not slow' \
    -p no:cacheprovider "$@"

echo "== recorder overhead + diagnose (release floors, --smoke) =="
python release/run_all.py --smoke --only workload_recorder_overhead

echo "diagnose smoke: PASS"
