#!/usr/bin/env bash
# Local mirror of .github/workflows/ci.yml.
#
#   scripts/ci.sh lint         # ruff + mypy (mypy soft-skips if absent)
#   scripts/ci.sh verify       # repo lints + plan-fuzzing harness
#   scripts/ci.sh test         # fast tier-1 suite + benches + regression gate
#   scripts/ci.sh multidevice  # slow 8-host-device subprocess suites
#   scripts/ci.sh fault-drill  # worker/pod-loss + straggler drills + elastic bench
#   scripts/ci.sh all          # everything, in CI job order
#
# Set SKIP_INSTALL=1 to reuse the current environment as-is.
set -euo pipefail
cd "$(dirname "$0")/.."

job="${1:-all}"

install() {
    if [ "${SKIP_INSTALL:-0}" = "1" ]; then
        return
    fi
    python -m pip install -e ".[dev]"
}

run_lint() {
    if ! python -m ruff --version >/dev/null 2>&1; then
        echo "ruff is not installed; run: python -m pip install ruff" >&2
        exit 1
    fi
    python -m ruff check .
    if python -m mypy --version >/dev/null 2>&1; then
        python -m mypy
    else
        echo "mypy not installed; skipping type check" \
             "(CI runs it: python -m pip install mypy)" >&2
    fi
    # docs link check: backtick-quoted module paths / CLI flags in
    # docs/*.md + README must resolve against the tree
    python scripts/check_docs.py
}

run_verify() {
    # pure host-side (numpy only): repo-specific lints, then the
    # randomized plan-fuzzing harness over the static verifier
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
        python -m repro.analysis.lints
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
        python -m repro.verify --fuzz --plans 200 --seed 0
    # survivor-set replan fuzzing: kill each worker AND each whole
    # pod, verify every survivor schedule, regrow and assert the plan
    # cache re-hits (CI adds a rolling-seed pass via GITHUB_RUN_NUMBER)
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
        python -m repro.verify --fuzz-elastic --plans 50 --seed 0
}

run_test() {
    install
    # no -x: one failure must not mask the rest (CI parity)
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
        python -m pytest -q -m "not slow"
    mkdir -p bench_out
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
        python -m benchmarks.bench_executor --quick \
        --out bench_out/BENCH_executor.json
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
        python -m benchmarks.bench_planner --quick \
        --out bench_out/BENCH_planner.json
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
        python -m benchmarks.bench_serve --quick \
        --out bench_out/BENCH_serve.json
    python scripts/check_bench.py --baseline . --fresh bench_out \
        --only BENCH_executor.json,BENCH_planner.json,BENCH_serve.json
}

run_multidevice() {
    install
    # the fault drill has its own job (run_fault_drill) for CI parity
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
        python -m pytest -q -m slow tests/test_multidevice.py \
        --deselect tests/test_multidevice.py::test_fault_drill_multidevice
}

run_fault_drill() {
    install
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
        python -m pytest -q -m slow \
        tests/test_multidevice.py::test_fault_drill_multidevice
    mkdir -p bench_out
    PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
        python -m benchmarks.bench_elastic \
        --out bench_out/BENCH_elastic.json
    python scripts/check_bench.py --baseline . --fresh bench_out \
        --only BENCH_elastic.json
}

case "$job" in
    lint)         run_lint ;;
    verify)       run_verify ;;
    test)         run_test ;;
    multidevice)  run_multidevice ;;
    fault-drill)  run_fault_drill ;;
    all)          run_lint; run_verify; run_test; run_multidevice;
                  run_fault_drill ;;
    *)
        echo "usage: scripts/ci.sh" \
             "[lint|verify|test|multidevice|fault-drill|all]" >&2
        exit 2 ;;
esac
