#!/usr/bin/env bash
# CI matrix (see ROADMAP.md and .github/workflows/ci.yml). Lanes, each
# runnable by name:
#
#   ./scripts/ci.sh              # full:    the whole tier-1 suite
#   ./scripts/ci.sh full
#   ./scripts/ci.sh fast         # fast:    tier-1 minus slow (multi-process)
#   ./scripts/ci.sh kernels      # kernels: Pallas suites, interpret mode
#                                #          forced via REPRO_PALLAS_INTERPRET=1
#                                #          (incl. the valid_m row-count paths
#                                #          the compact reduction drives)
#   ./scripts/ci.sh x64          # x64:     numerical core under
#                                #          JAX_ENABLE_X64=1 (screening bound
#                                #          math, solver, paths)
#   ./scripts/ci.sh stream       # stream:  out-of-core subsystem
#                                #          (tests/test_sparse_stream.py) with
#                                #          a small forced chunk size
#                                #          (REPRO_STREAM_CHUNK_M=48): bitwise
#                                #          chunked bound sweep, solver seam,
#                                #          BCOO, memory-shape property,
#                                #          chunk-skip twin; plus the
#                                #          disk-resident smoke
#                                #          (scripts/stream_smoke.py: libsvm ->
#                                #          mmap store -> gated path with
#                                #          chunks_skipped > 0)
#   ./scripts/ci.sh serve        # serve:   path-server suite (continuous
#                                #          batching, bucket padding, warm
#                                #          program cache) + the --serve
#                                #          launcher smoke
#   ./scripts/ci.sh rules        # rules:   the screening-rule zoo — rule
#                                #          programs on every engine
#                                #          (tests/test_rule_programs.py:
#                                #          host-vs-scan equivalence matrix,
#                                #          EDPP-tightens-VI, dvi history
#                                #          carry, composite round-trip,
#                                #          dispatch rejections) + the host
#                                #          rule-protocol suite
#   ./scripts/ci.sh bench        # bench:   engine + storage equivalence smoke
#                                #          (bench_screening --smoke): catches
#                                #          host/scan/compact/pallas/chunked,
#                                #          batched-compact, server-vs-
#                                #          sequential and sharded-scan-bitwise
#                                #          regressions in seconds
#   ./scripts/ci.sh obs          # obs:     observability layer
#                                #          (tests/test_obs.py: span recorder
#                                #          round-trip, disabled-mode no-op,
#                                #          metrics registry mirroring the
#                                #          legacy stats dicts bitwise,
#                                #          PathTrace schema across engines;
#                                #          tests/test_path_spans.py: entry
#                                #          spans on the profiler, scopes in
#                                #          the program, binding rounds)
#                                #          + a train_svm --trace smoke that
#                                #          validates the exported Chrome
#                                #          trace JSON
#   ./scripts/ci.sh chaos        # chaos:   fault-injection suite
#                                #          (tests/test_faults.py via
#                                #          src/repro/testing/faults.py):
#                                #          poisoned solves keep a superset
#                                #          and recover, corrupt/truncated
#                                #          stores surface typed errors,
#                                #          flaky reads are absorbed, server
#                                #          kill+resume equals uninterrupted,
#                                #          quarantine isolates tenants;
#                                #          interpret mode forced so guard
#                                #          paths run on any backend
#   ./scripts/ci.sh all          # kernels + x64 + stream + serve + rules
#                                # + bench + chaos + obs,
#                                # then full
#
# Extra pytest args pass through after the lane name (a leading '-' arg is
# treated as pytest args for the full lane, back-compat):
#   ./scripts/ci.sh fast -k screening
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

lane="${1:-full}"
case "$lane" in
  full|fast|kernels|x64|stream|serve|rules|bench|chaos|obs|all) shift || true ;;
  -*) lane="full" ;;  # bare pytest args => full lane (legacy invocation)
  *) echo "unknown lane '$lane' (full|fast|kernels|x64|stream|serve|rules|bench|chaos|obs|all)" >&2; exit 2 ;;
esac

# suites whose numerics are dtype-parametric: the safe-screening bound
# geometry, the solver, and both path engines must hold in fp64 too
X64_SUITES="tests/test_screening.py tests/test_dual.py tests/test_solver.py \
tests/test_path.py tests/test_path_scan.py"

run_lane() {
  local name="$1"; shift
  echo "=== ci lane: $name ==="
  case "$name" in
    full)
      python -m pytest -x -q "$@"
      ;;
    fast)
      python -m pytest -x -q -m 'not slow' "$@"
      ;;
    kernels)
      REPRO_PALLAS_INTERPRET=1 python -m pytest -x -q \
        tests/test_kernels.py "$@"
      ;;
    x64)
      JAX_ENABLE_X64=1 python -m pytest -x -q $X64_SUITES "$@"
      ;;
    stream)
      # deliberately small + ragged: many chunks per instance, last chunk
      # partial — the shapes the out-of-core paths must be invariant to
      REPRO_STREAM_CHUNK_M=48 python -m pytest -x -q \
        tests/test_sparse_stream.py "$@"
      # disk-resident + chunk-skip smoke: libsvm -> mmap store in a tmpdir,
      # gated path must actually skip transfers (chunks_skipped > 0)
      python scripts/stream_smoke.py
      ;;
    serve)
      python -m pytest -x -q tests/test_path_server.py "$@"
      python -m repro.launch.train_svm --serve --serve-jobs 4 \
        --serve-slots 2 --m 120 --n 60 --reduce compact
      ;;
    rules)
      python -m pytest -x -q tests/test_rule_programs.py tests/test_rules.py "$@"
      ;;
    bench)
      python -m benchmarks.bench_screening --smoke
      ;;
    chaos)
      REPRO_PALLAS_INTERPRET=1 python -m pytest -x -q \
        tests/test_faults.py "$@"
      ;;
    obs)
      python -m pytest -x -q tests/test_obs.py tests/test_path_spans.py "$@"
      # trace-capture smoke: the launcher must export loadable Chrome
      # trace-event JSON holding the scan engine's dispatch span
      python -m repro.launch.train_svm --m 120 --n 60 --n-lambdas 4 \
        --engine scan --trace artifacts/ci_trace.json
      python - <<'EOF'
import json
doc = json.load(open("artifacts/ci_trace.json"))
evs = doc["traceEvents"]
assert any(e.get("ph") == "X" and e["name"] == "svm_path.dispatch"
           for e in evs), \
    sorted({e["name"] for e in evs})
print(f"obs smoke: {len(evs)} trace events OK")
EOF
      ;;
  esac
}

if [ "$lane" = "all" ]; then
  # kernels (interpret-forced), x64, stream, bench smoke, then full — full
  # already includes every non-slow test, so fast here would duplicate work
  run_lane kernels "$@"
  run_lane x64 "$@"
  run_lane stream "$@"
  run_lane serve "$@"
  run_lane rules "$@"
  run_lane bench
  run_lane chaos "$@"
  run_lane obs "$@"
  run_lane full "$@"
else
  run_lane "$lane" "$@"
fi
