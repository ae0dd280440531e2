#!/usr/bin/env bash
# CI entry point, split into named, individually timed, fail-fast stages.
#
#   scripts/ci.sh            # full tier: lint + tests + all smokes
#   scripts/ci.sh --fast     # lint + tier-1 tests only
#   scripts/ci.sh --perf     # full tier + the slow perf benchmark
#                            # (legacy baselines included)
#
# Stages (each reports its own wall time; the first failure stops the run
# and prints which stage died):
#
#   lint           ruff check + ruff format --check, both hard gates
#                  (pyproject.toml config; SKIPPED with a notice when
#                  ruff is absent — the GitHub workflow always installs
#                  it)
#   tests          tier-1 pytest (the ROADMAP verify command)
#   docs-check     executable-docs gate: every fenced python block in
#                  API.md and every examples/*.py runs (CI-budget args
#                  per file; scripts/check_docs.py) — subsumes the old
#                  quickstart smoke
#   perf-smoke     planner-latency budget gate  -> BENCH_perf.json
#   schemes-smoke  scheme sanity + plan budget  -> BENCH_schemes.json
#   nonlinear-smoke CodedFedL kernel head beats the equal-wall-clock
#                  uncoded run and the best linear model
#                                               -> BENCH_nonlinear.json
#   privacy-smoke  DP calibration + frontier    -> BENCH_privacy.json
#   sweep-smoke    batched sweep engine >= 3x   -> BENCH_sweep.json
#   serve-smoke    serving engine >= 2x sess/s  -> BENCH_serve.json
#   kernels-smoke  tuned tiles >= 1.2x default  -> BENCH_kernels.json
#                  (block="auto" vs hard-coded tiles at fleet scale;
#                  floor tunable via KERNELS_SMOKE_MIN_SPEEDUP)
#   fleet-smoke    100k-client sharded plan under wall budget, tiered
#                  encode tile-cache hit, budgeted-round sublinearity
#                                               -> BENCH_plan_scale.json
#   perf-trend     compares every BENCH_*.json metric against the
#                  previous run's artifacts in $PERF_BASELINE_DIR
#                  (downloaded by ci.yml; SKIPPED with a notice when
#                  absent — e.g. first run or local dev box).  Bands:
#                  PERF_TREND_TOL / PERF_TREND_GATE_TOL / PERF_TREND_SKIP.
#   perf-full      (--perf only) full session micro-benchmark
#
# The BENCH_*.json artifacts are machine-readable (timings + gate
# values); .github/workflows/ci.yml uploads them AND feeds the previous
# run's copies back in, so the perf trajectory is a hard gate across
# PRs, not just a tracked artifact.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

TIER="full"
case "${1:-}" in
    --fast) TIER="fast" ;;
    --perf) TIER="perf" ;;
    "") ;;
    *) echo "usage: scripts/ci.sh [--fast|--perf]" >&2; exit 2 ;;
esac

declare -a STAGE_SUMMARY=()

summary() {
    echo
    echo "== stage summary =="
    local line
    for line in "${STAGE_SUMMARY[@]}"; do
        echo "  $line"
    done
}

run_stage() {
    local name="$1"; shift
    echo
    echo "== stage: $name =="
    local t0=$SECONDS
    if "$@"; then
        STAGE_SUMMARY+=("$name: OK ($((SECONDS - t0))s)")
    else
        local code=$?
        STAGE_SUMMARY+=("$name: FAILED exit $code ($((SECONDS - t0))s)")
        echo
        echo "-- stage FAILED: $name (exit $code)" >&2
        summary
        exit "$code"
    fi
}

lint() {
    if ! command -v ruff >/dev/null 2>&1; then
        echo "SKIP: ruff not installed (pip install -r" \
             "requirements-dev.txt); the GitHub workflow runs this gate"
        return 0
    fi
    ruff check .
    # hard gate since the tree-wide format migration: run `ruff format .`
    # before committing when this trips
    ruff format --check .
}

perf_trend() {
    local dir="${PERF_BASELINE_DIR:-}"
    if [[ -z "$dir" || ! -d "$dir" ]] || \
            [[ -z "$(find "$dir" -name 'BENCH_*.json' -print -quit)" ]]
    then
        echo "SKIP: no baseline artifacts (PERF_BASELINE_DIR='${dir}');" \
             "first run or local dev box"
        return 0
    fi
    python -m benchmarks.perf_trend --baseline-dir "$dir" --new-dir .
}

run_stage lint lint
run_stage tests python -m pytest -x -q

if [[ "$TIER" != "fast" ]]; then
    run_stage docs-check python scripts/check_docs.py
    run_stage perf-smoke python -m benchmarks.perf_session --smoke
    run_stage schemes-smoke python -m benchmarks.fig_schemes --smoke
    run_stage nonlinear-smoke python -m benchmarks.fig_nonlinear --smoke
    run_stage privacy-smoke python -m benchmarks.fig_privacy --smoke
    run_stage sweep-smoke python -m benchmarks.perf_sweep --smoke
    run_stage serve-smoke python -m benchmarks.perf_serve --smoke
    run_stage kernels-smoke python -m benchmarks.kernels --smoke
    run_stage fleet-smoke python -m benchmarks.perf_fleet --smoke
    run_stage perf-trend perf_trend
fi

if [[ "$TIER" == "perf" ]]; then
    run_stage perf-full python -m benchmarks.perf_session --epochs 200
fi

summary
echo
echo "CI OK ($TIER tier)"
