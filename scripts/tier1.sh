#!/usr/bin/env bash
# Tier-1 verification (see ROADMAP.md): the default-marker test suite.
# Extra args are passed straight to pytest, e.g.  scripts/tier1.sh -m slow
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
# lint first (cheap, config in ruff.toml); CI runs the same check as its
# own job, so keep local and CI gates identical
if command -v ruff >/dev/null 2>&1; then
  ruff check .
else
  echo "[tier1] ruff not installed; skipping lint (CI still runs it)" >&2
fi
# TIER1_MULTIDEV=<D> runs the distributed-sort suites on D simulated
# host-platform devices instead of the full single-device suite — the CI
# multi-device job sets TIER1_MULTIDEV=8 so every push exercises the
# sample-sort / odd-even paths at real D>1, not just the degenerate D=1.
if [[ -n "${TIER1_MULTIDEV:-}" ]]; then
  export XLA_FLAGS="--xla_force_host_platform_device_count=${TIER1_MULTIDEV} ${XLA_FLAGS:-}"
  exec python -m pytest -x -q --durations=10 \
    tests/test_distributed_sort.py tests/test_samplesort.py \
    tests/test_distributed_topk.py tests/test_relational_distributed.py \
    "$@"
fi
# TIER1_SPILL=1 runs the out-of-core spill tier by itself: the spill unit
# suite, the data-pipeline dedup consumers, the spill fuzz lenses, and the
# suite-wide slow-marked cases (the spill job is CI's home for `-m slow`
# coverage, so the deselected-by-default tests still run on every push).
if [[ -n "${TIER1_SPILL:-}" ]]; then
  python -m pytest -x -q --durations=10 \
    tests/test_spill.py tests/test_data.py \
    "tests/test_fuzz_conformance.py::test_fuzz_spill_sort_matches_jnp" \
    "tests/test_fuzz_conformance.py::test_fuzz_spill_argsort_is_stable" \
    "$@"
  exec python -m pytest -x -q --durations=10 -m slow "$@"
fi
# TIER1_BENCH=1 appends the perf-trajectory leg after the suite: emit a
# fresh bench document on the quick probe grid, then enforce the
# auto-within-factor-of-best invariant (scripts/bench_gate.py) and, when
# the committed baseline exists, the no-drift-vs-baseline bound.  Pass
# TIER1_BENCH_ARGS for extra gate flags (e.g. "--warn-only" on noisy CI).
if [[ -n "${TIER1_BENCH:-}" ]]; then
  python -m pytest -x -q --durations=10 "$@"
  echo "[tier1] bench leg: emitting benchmarks/BENCH_sort.ci.json"
  python -m benchmarks.emit_bench --quick --out benchmarks/BENCH_sort.ci.json
  baseline_args=()
  if [[ -f benchmarks/BENCH_sort.json ]]; then
    baseline_args=(--baseline benchmarks/BENCH_sort.json)
  fi
  # shellcheck disable=SC2086
  python scripts/bench_gate.py benchmarks/BENCH_sort.ci.json \
    "${baseline_args[@]}" ${TIER1_BENCH_ARGS:-}
  exit 0
fi
# TIER1_TUNE=1 appends the autotuner leg: run a tiny-grid calibrate() that
# probes this machine, persists the winning tuning profile, and validates
# the emitted JSON (schema + device fingerprint) via --check.  The profile
# lands in a throwaway dir so the run never pollutes the user's cache.
if [[ -n "${TIER1_TUNE:-}" ]]; then
  python -m pytest -x -q --durations=10 "$@"
  tunedir="$(mktemp -d)"
  trap 'rm -rf "$tunedir"' EXIT
  echo "[tier1] tune leg: calibrating into $tunedir/profile.json"
  python scripts/autotune.py --tile-n 512 --batch 8 --reps 1 \
    --out "$tunedir/profile.json" --check
  exit 0
fi
# --durations=10 surfaces the suite's hot spots (it runs ~9 min on CPU CI)
exec python -m pytest -x -q --durations=10 "$@"
