#!/usr/bin/env bash
# Run the test suite on a simulated 8-device CPU mesh.
#
# Every gate runs with JAX_PLATFORMS=cpu — tests are CPU-only by design; the
# chip is reached through the chip tool (chip_smoke.py first, then
# benchmark/run.py).
set -euo pipefail
cd "$(dirname "$0")/.."

# per-gate wall-time ledger: every gate prints its cost so drift toward
# the 1200 s tier-1 budget is attributable to a GATE per-PR, not just to
# a test (--durations covers those); past 1000 s the ledger warns loudly
# so the budget is defended before it is blown
gate_t0=$SECONDS
gate_time() {
  local now=$SECONDS
  echo "gate-time: $1 $((now - gate_t0))s (total ${now}s of 1200s budget)"
  if (( now >= 1000 )); then
    echo "gate-time: WARNING total ${now}s has crossed 1000s of the" \
         "1200s tier-1 budget — trim a gate before the next PR" >&2
  fi
  gate_t0=$now
}

# native library freshness: rebuild libhivemall_native.so when the C++
# source is newer, the .so cannot load on THIS host (the PR 11
# GLIBCXX-mismatch silent-fallback pathology), or it predates the current
# plan ABI — skipped cleanly when no compiler exists (native.available()
# then reports the mismatch loudly and the native gates skip with the
# reason in-artifact). A present-but-broken toolchain fails here, before
# any gate runs against a stale library.
bash scripts/build_native.sh --if-stale
gate_time "native-build"

# tier-1 gate 1: graftcheck static analysis on changed files (+ their
# callers) — any new non-baselined recompile/host-sync/dtype/axis/donation/
# side-effect/SPMD-safety/precision-flow finding fails before pytest spends
# minutes (docs/static_analysis.md)
bash scripts/lint.sh
# next to the baseline check: overwrite the changed-files artifact with a
# merged FULL-tree report (accepted debt included) so CI uploads ONE
# analysis.sarif covering the whole package, and print the per-rule
# findings/baselined/suppressions ledger so debt drift is attributable
# per-PR instead of discovered at the next baseline refresh
# (docs/static_analysis.md "Baseline workflow")
python - <<'PY'
import collections
import json
import os

from hivemall_tpu.analysis import analyze_paths
from hivemall_tpu.analysis.baseline import load_baseline
from hivemall_tpu.analysis.findings import parse_suppressions
from hivemall_tpu.analysis.rules import RULE_DOCS
from hivemall_tpu.analysis.sarif import render_sarif

findings = analyze_paths(["hivemall_tpu"])
live = collections.Counter(f.rule for f in findings)
based = collections.Counter(b.rule for b in load_baseline())
supp = collections.Counter()
for root, _dirs, names in os.walk("hivemall_tpu"):
    for name in names:
        if not name.endswith(".py"):
            continue
        with open(os.path.join(root, name), encoding="utf-8") as fh:
            per_line, whole_file = parse_suppressions(fh.read())
        for rules in per_line.values():
            supp.update(rules)
        supp.update(whole_file)
print("graftcheck ledger (live findings / baselined / suppressions):")
# every registered rule prints, zeros included — an all-zero row is the
# ledger's proof the rule ran and the tree is clean, not that it was absent
for rule in sorted(set(RULE_DOCS) | set(live) | set(based) | set(supp)):
    print("  %-5s %3d live  %3d baselined  %3d suppressed"
          % (rule, live[rule], based[rule], supp[rule]))
with open("analysis.sarif", "w", encoding="utf-8") as fh:
    json.dump(render_sarif(findings), fh, indent=2, sort_keys=True)
print("graftcheck: merged full-tree SARIF archived at analysis.sarif")
PY
gate_time "graftcheck-lint"

# tier-1 gate 2: no machine-applicable fix may be left unapplied in the
# changed files — if `--fix` would produce a diff there, fail with the
# would-be diff so the fix lands in the same change (full-tree fix
# cleanliness is locked by the baseline test: a fixable finding is always
# a non-baselined finding)
bash scripts/lint.sh --fix-check
gate_time "graftcheck-fix-check"

# tier-1 gate 3: serving smoke — warmup then a bucket-sweeping load must
# show ZERO steady-state recompiles, and an in-flight hot swap must fail
# zero requests (docs/serving.md; prints one BENCH-style JSON line)
env JAX_PLATFORMS=cpu \
  python scripts/bench_serving.py --smoke
gate_time "serving-smoke"

# tier-1 gate 4: quantized-serving smoke — one tiny model frozen f32/bf16/
# int8, served through all three engines: the int8/bf16 holdout logloss
# must sit within the parity tolerance of f32 AND every precision must
# show zero steady-state recompiles (docs/serving.md "Quantized
# artifacts"; prints one BENCH-style JSON line)
env JAX_PLATFORMS=cpu \
  python scripts/bench_serving.py --quantize --smoke
gate_time "quantize-smoke"

# tier-1 gate 5: chaos smoke — a seeded device loss mid-run must end in an
# elastic resume on a DIFFERENT simulated device count that converges to
# the uninterrupted run's holdout logloss within tolerance with zero lost
# checkpointed work (docs/elastic_training.md; one BENCH-style JSON line)
env JAX_PLATFORMS=cpu \
  python scripts/bench_chaos.py --smoke
gate_time "chaos-smoke"

# tier-1 gate 6: sharded-serving smoke — one model served single-device
# and NamedSharding-striped over every admissible (batch, model) mesh
# shape: sharded scores must match single-device at equal model, every
# placement must show zero steady-state recompiles, and an artifact
# exceeding the simulated single-device byte budget must refuse
# single-device but serve sharded (docs/serving.md "Sharded serving";
# prints one BENCH-style JSON line)
env JAX_PLATFORMS=cpu \
  python scripts/bench_serving.py --sharded --smoke
gate_time "sharded-smoke"

# tier-1 gate 7: overload smoke — a stepped offered-load sweep over
# POST /predict (priority mix + deadline budgets through real sockets)
# must show goodput at 2x saturation >= 0.8x peak goodput (degradation
# flattens, never collapses), zero steady-state recompiles, and
# admission counters consistent with the client-observed outcomes
# (accepted == 200s + sheds + expiries, quota rejects == quota 503s)
# (docs/serving.md "Overload behavior"; prints one BENCH-style JSON line).
# One retry: the goodput gate measures a live host — a CPU-steal burst
# during the 2x step can fail a healthy server once; twice in a row is a
# real regression (the admission SEMANTICS are pinned deterministically
# in tests/test_serving_overload.py, no retry there)
env JAX_PLATFORMS=cpu \
  python scripts/bench_serving.py --overload --smoke || \
env JAX_PLATFORMS=cpu \
  python scripts/bench_serving.py --overload --smoke
gate_time "overload-smoke"

# (gate 8, a CPU timing threshold on the -batch backends, left with
# bench.py; the later gates keep the numbers the docs know them by)

# tier-1 gate 9: continuous-training pipeline smoke — the stream ->
# freeze -> eval gate -> hot-swap loop must land >= 3 gated publishes
# (>= 2 atomic hot-swaps) under concurrent traffic with ZERO failed
# in-flight requests, REFUSE the publish trained on the injected
# label-flip regression, and keep end-to-end freshness p99 (event
# observed -> model serving it) under the pinned bound
# (docs/continuous_training.md; prints one BENCH-style JSON line)
env JAX_PLATFORMS=cpu \
  python scripts/bench_pipeline.py --smoke
gate_time "pipeline-smoke"

# tier-1 gate 10: hot-row cache smoke — a pinned-Zipf closed-loop workload
# against cache-on vs cache-off registry arms must show effective rows/sec
# >= 1.3x cache-off at the smoke skew with the measured hit ratio above
# the pinned floor, cached scores BIT-identical to computed ones at every
# precision (f32/bf16/int8), zero failed requests across the mid-bench
# hot-swap (and zero scores labeled with a version that did not compute
# them), and zero steady-state recompiles (docs/serving.md "Score caching
# & coalescing"; prints one BENCH-style JSON line)
env JAX_PLATFORMS=cpu \
  python scripts/bench_serving.py --skew --smoke
gate_time "skew-smoke"

# tier-1 gate 11: top-K retrieval smoke — the blocked streamed top-K
# merge over an MF catalog must be BIT-identical (ids and f32 scores) to
# the stable-argsort baseline, the LSH-pruned path must hold the pinned
# recall@K floor with at least one query actually pruned, sharded
# catalogs must reproduce single-device scores at equal model, and the
# whole sweep — exact and probed, every bucket — must run with zero
# steady-state recompiles (docs/serving.md "Top-K retrieval"; prints one
# BENCH-style JSON line)
env JAX_PLATFORMS=cpu \
  python scripts/bench_serving.py --topk --smoke
gate_time "topk-smoke"

# tier-1 gate 12: native sanitizer pass — the parity/refusal suites run
# against the ASan+UBSan-instrumented .so (halt_on_error: any heap
# overflow, use-after-free, or UB aborts the run). This is the dynamic
# complement to graftcheck's G022-G026 static FFI rules, and the harness
# the threaded native apply will reuse with --sanitize=thread. Skips with
# a NAMED reason — never silently — when the toolchain lacks the
# compiler or sanitizer runtime libraries.
sanitize_skip=""
if ! command -v g++ >/dev/null 2>&1; then
  sanitize_skip="no g++ on PATH"
else
  libasan="$(g++ -print-file-name=libasan.so)"
  libubsan="$(g++ -print-file-name=libubsan.so)"
  # -print-file-name echoes the bare name back when the library is absent
  if [[ "$libasan" != */* || "$libubsan" != */* ]]; then
    sanitize_skip="toolchain lacks libasan/libubsan runtimes"
  fi
fi
if [[ -n "$sanitize_skip" ]]; then
  echo "native-sanitizer gate: SKIPPED ($sanitize_skip)"
else
  bash scripts/build_native.sh --if-stale --sanitize=address,undefined
  env LD_PRELOAD="$libasan $libubsan" \
    ASAN_OPTIONS=halt_on_error=1:detect_leaks=0 \
    UBSAN_OPTIONS=halt_on_error=1:print_stacktrace=1 \
    HIVEMALL_TPU_NATIVE_SANITIZE=asan \
    JAX_PLATFORMS=cpu \
    python -m pytest tests/test_native.py tests/test_native_batch.py -q
  echo "native-sanitizer gate: PASSED (ASan+UBSan, halt_on_error)"
fi
gate_time "native-sanitizer"

# tier-1 gate 13: SLO smoke — the overload ladder re-driven with the
# time-series sampler + SLO engine live on the process singletons: the
# latency burn-rate alert must FIRE (page) during the 2x step and CLEAR
# after recovery, never fire at light load, the sampler must cost < 5%
# of wall time, the mid-overload GET /debug/bundle must carry every
# flight-recorder section, and the ladder must run with zero
# steady-state recompiles (docs/observability.md "SLOs & burn rates";
# prints one BENCH-style JSON line). One retry for the same reason as
# gate 7: the ladder measures a live host — the alert SEMANTICS are
# pinned deterministically in tests/test_slo.py, no retry there
env JAX_PLATFORMS=cpu \
  python scripts/bench_serving.py --slo --smoke || \
env JAX_PLATFORMS=cpu \
  python scripts/bench_serving.py --slo --smoke
gate_time "slo-smoke"

# --durations=15 keeps per-test cost visible so drift toward the 1200 s
# tier-1 budget is attributable per-PR (ROADMAP hygiene); no exec — the
# ledger's final line below still needs this shell
env JAX_PLATFORMS=cpu \
  python -m pytest tests/ -q --durations=15 "$@"
gate_time "pytest-tier1"
