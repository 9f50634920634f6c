#!/usr/bin/env bash
# CI entry point: tier-1 tests + docs link check + suite-level smoke bench
# + model-variation smoke bench.
#
#   scripts/ci.sh            # full tests + docs check + smoke benches
#   scripts/ci.sh --no-bench # tests + docs check only
#   scripts/ci.sh --smoke    # fast profile: -m "not slow" marker split,
#                            # tighter per-test timeout, capped hypothesis
#   scripts/ci.sh --chaos    # also run the fault-injection matrix
#                            # (scripts/chaos.py) + the journal-overhead
#                            # gate (benchmarks.bench_faults, <2%);
#                            # combine with --no-bench for a focused
#                            # survivability run
#
# Uses the PYTHONPATH=src layout (works without installation; `pip
# install -e .` works too, see pyproject.toml).
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
mkdir -p runs

RUN_BENCH=1
SMOKE=0
CHAOS=0
for arg in "$@"; do
    case "$arg" in
        --no-bench) RUN_BENCH=0 ;;
        --smoke)    SMOKE=1 ;;
        --chaos)    CHAOS=1 ;;
        *) echo "unknown flag: $arg (known: --no-bench --smoke --chaos)"; exit 2 ;;
    esac
done

# Per-test SIGALRM timeout (tests/conftest.py) so a hung test fails fast
# instead of stalling the pipeline, and a capped hypothesis "ci" profile
# so the property suites stay inside the CI time budget.
export HYPOTHESIS_PROFILE="${HYPOTHESIS_PROFILE:-ci}"
if [[ "$SMOKE" == 1 ]]; then
    export PYTEST_PER_TEST_TIMEOUT="${PYTEST_PER_TEST_TIMEOUT:-120}"
    export HYPOTHESIS_MAX_EXAMPLES="${HYPOTHESIS_MAX_EXAMPLES:-10}"
    PYTEST_MARKERS=(-m "not slow")
else
    export PYTEST_PER_TEST_TIMEOUT="${PYTEST_PER_TEST_TIMEOUT:-600}"
    PYTEST_MARKERS=()
fi

# The property suites (tests/test_transforms.py, test_variation.py, ...)
# need hypothesis (the pyproject `test` extra); install it when the
# environment doesn't ship it so those suites actually run in CI.  On
# air-gapped runners the install fails gracefully and the suites skip —
# the skip count below makes that visible instead of silent.
if ! python -c "import hypothesis" >/dev/null 2>&1; then
    echo "== installing hypothesis (test extra) =="
    python -m pip install -q hypothesis \
        || echo "warning: could not install hypothesis (offline?); property suites will be SKIPPED"
fi

echo "== tier-1 tests (smoke=$SMOKE, per-test timeout ${PYTEST_PER_TEST_TIMEOUT}s) =="
python -m pytest -x -q -rs "${PYTEST_MARKERS[@]}" 2>&1 | tee runs/pytest.log
n_skipped=$(grep -Eo '[0-9]+ skipped' runs/pytest.log | tail -1 | grep -Eo '[0-9]+' || echo 0)
echo "skipped tests: ${n_skipped} (see runs/pytest.log for reasons)"

echo "== docs link check =="
python scripts/check_links.py

echo "== jit-discipline static analyzer (src tree + registered kernels) =="
# Fails (set -e) on any finding not in the checked-in baseline; the
# baseline is empty, so the tree must be *actually* clean.
python -m repro.analysis.lint --format json | tee runs/lint.json
python - <<'EOF'
import json
with open("runs/lint.json") as f:
    r = json.load(f)
c = r["counts"]
assert c["new"] == 0, f"{c['new']} new lint finding(s)"
print(f"lint: {c['new']} new, {c['baselined']} baselined, "
      f"{c['total']} total finding(s)")
EOF

echo "== static analyzer: every seeded-violation fixture must fail =="
# One fixture per rule (tests/lint_fixtures/); a rule that stops firing
# on its own seed is a dead rule, so each must exit non-zero.
for fx in tests/lint_fixtures/fx_ast_*.py; do
    if python -m repro.analysis.lint --no-jaxpr --baseline "" "$fx" >/dev/null 2>&1; then
        echo "FAIL: $fx passed the AST lint (seeded violation did not fire)"
        exit 1
    fi
done
for fx in tests/lint_fixtures/fx_jaxpr_*.py; do
    if python -m repro.analysis.lint --no-ast --baseline "" --kernels-from "$fx" >/dev/null 2>&1; then
        echo "FAIL: $fx passed the jaxpr lint (seeded violation did not fire)"
        exit 1
    fi
done
echo "all seeded fixtures correctly rejected"

echo "== static analyzer: guard flip checks on src/repro/core/batch.py =="
# The annotations must actually be guarding: strip one host-boundary
# annotation / one trace-counter increment from a *copy* of batch.py
# and the lint run must flip from green to failing.
lint_tmp=$(mktemp -d)
trap 'rm -rf "$lint_tmp"' EXIT
python - "$lint_tmp" <<'EOF'
import os, sys
src = open("src/repro/core/batch.py").read()
d = sys.argv[1]
ann = "  # repro: host-boundary\n"
assert ann in src, "no trailing host-boundary annotation to strip"
with open(os.path.join(d, "strip_annotation.py"), "w") as f:
    f.write(src.replace(ann, "\n", 1))
cnt = 'TRACE_COUNTS["select_batch"] += 1'
assert cnt in src, "no select_batch trace-counter increment to strip"
with open(os.path.join(d, "strip_counter.py"), "w") as f:
    f.write(src.replace(cnt, "", 1))
EOF
for f in strip_annotation strip_counter; do
    if python -m repro.analysis.lint --no-jaxpr --baseline "" "$lint_tmp/$f.py" >/dev/null 2>&1; then
        echo "FAIL: $f copy of batch.py still passes — the lint is not guarding"
        exit 1
    fi
done
rm -rf "$lint_tmp"
trap - EXIT
echo "both stripped copies correctly rejected"

# Style gate (pyproject [tool.ruff]); best-effort like the hypothesis
# install above: air-gapped runners without ruff warn and skip rather
# than fail — the jit-discipline lint above is the load-bearing gate.
if command -v ruff >/dev/null 2>&1; then
    echo "== ruff check =="
    ruff check src tests benchmarks scripts
else
    echo "warning: ruff not installed; style gate SKIPPED (pip install ruff to enable)"
fi

if [[ "$RUN_BENCH" == 1 ]]; then
    echo "== suite-level explorer bench (smoke, cache cold + warm) =="
    python -m benchmarks.bench_explorer --smoke --out runs/BENCH_explorer_smoke.json
    python - <<'EOF'
import json
with open("runs/BENCH_explorer_smoke.json") as f:
    r = json.load(f)
total = r["total"]
assert total["all_agree"], "python/jax backends disagree on best implementation"
cold, warm = total["characterize_cold_s"], total["characterize_warm_s"]
assert warm < cold, f"warm cache not faster than cold ({warm}s vs {cold}s)"
assert warm < 2.0, f"warm-cache characterization should be near-zero, got {warm}s"
print(f"suite sweep speedup: {total['speedup']}x "
      f"(python {total['python_us']:.0f}us -> jax {total['jax_us']:.0f}us); "
      f"characterize cold {cold:.2f}s -> warm {warm:.3f}s; "
      f"e2e cold {total['e2e']['cold_s']}s / warm {total['e2e']['warm_s']}s")
EOF

    echo "== characterization bench (smoke, device vs python front half) =="
    python -m benchmarks.bench_characterization --smoke \
        --out runs/BENCH_explorer_smoke.json
    python - <<'EOF'
import json
with open("runs/BENCH_explorer_smoke.json") as f:
    r = json.load(f)
assert "characterization" in r, \
    "bench must record a 'characterization' section"
cha = r["characterization"]
assert cha["backend_available"], \
    "device characterization backend unavailable (jax import failed)"
assert cha["parity"]["agree"], \
    "device and python characterization disagree (AigStats or transform " \
    "fingerprints differ on some (circuit, recipe))"
assert cha["parity"]["stats_checked"] > 0, "parity check did not run"
for t, pt in cha["per_transform"].items():
    assert pt["fingerprints_agree"], \
        f"transform {t}: device output fingerprint differs from python"
# The cold-start contract: once the persistent caches exist (XLA compile
# cache + CharacterizationCache), a fresh characterization run beats
# recomputing through the python-int path outright.
assert cha["device_warm_s"] < cha["python_cold_s"], \
    f"cache-warm device characterization ({cha['device_warm_s']}s) must " \
    f"beat the cold python path ({cha['python_cold_s']}s)"
assert cha["device_warm_s"] < cha["device_cold_s"], \
    "warm characterization cache not faster than cold"
print(f"characterization: python cold {cha['python_cold_s']}s, device "
      f"cold {cha['device_cold_s']}s / warm {cha['device_warm_s']}s; "
      f"parity on {cha['parity']['stats_checked']} (circuit, recipe) "
      f"stats + all transform fingerprints")
EOF

    echo "== system bench (smoke, rCiM vs conventional roofline per token) =="
    python -m benchmarks.bench_system --smoke \
        --out runs/BENCH_explorer_smoke.json
    python - <<'EOF'
import json, math
with open("runs/BENCH_explorer_smoke.json") as f:
    s = json.load(f)["system"]
assert len(s["configs"]) >= 4, \
    f"system bench must cover >= 4 configs, got {len(s['configs'])}"
for arch, ok in s["conservation"].items():
    assert ok, f"{arch}: lowered op stream not conserved (sum over " \
               f"levels != per-layer op totals)"
for arch, rec in s["configs"].items():
    assert rec["conserved"], f"{arch}: conservation flag false"
    for side in ("rcim", "baseline"):
        e = rec[side]["energy_per_token_j"]
        t = rec[side]["latency_per_token_s"]
        assert math.isfinite(e) and e > 0, f"{arch}/{side}: bad energy {e}"
        assert math.isfinite(t) and t > 0, f"{arch}/{side}: bad latency {t}"
sw = s["bw_sweep"]
assert sw["compiles"] == 1, \
    f"an N-point BW sweep must cost exactly one jit trace, got {sw['compiles']}"
assert sw["recompiles_on_value_change"] == 0, \
    "changing only bandwidth values retriggered tracing"
assert sw["memory_s_monotone"], "memory term not monotone in HBM BW"
print(f"system: {len(s['configs'])} configs compared "
      f"(conservation checked on {s['conservation_checked']}), "
      f"bw sweep {sw['n_points']} points, compiles={sw['compiles']}, "
      f"retraces={sw['recompiles_on_value_change']}")
EOF

    echo "== exploration service bench (smoke, warm persistent engine) =="
    python -m benchmarks.bench_service --smoke \
        --out runs/BENCH_explorer_smoke.json
    python - <<'EOF'
import json
with open("runs/BENCH_explorer_smoke.json") as f:
    s = json.load(f)["service"]
assert s["winners_agree"] == s["n_requests_total"], \
    f"only {s['winners_agree']}/{s['n_requests_total']} service winners " \
    f"match a fresh offline explore_request"
assert s["warm_p50_ms"] < s["cold_p50_ms"] / 10, \
    f"warm p50 ({s['warm_p50_ms']}ms) must be << cold p50 " \
    f"({s['cold_p50_ms']}ms)"
assert s["rerank_retrace"] == 0, \
    f"constraint-only re-ranks recompiled {s['rerank_retrace']} kernels"
assert s["fused_traces"] == s["distinct_buckets"], \
    f"{s['fused_traces']} fused jit traces for {s['distinct_buckets']} " \
    f"bucket shapes (must be exactly one per shape)"
print(f"service: cold p50 {s['cold_p50_ms']}ms -> warm p50 "
      f"{s['warm_p50_ms']}ms (p99 {s['warm_p99_ms']}ms), "
      f"{s['burst_rps']} rps, {s['fused_traces']} trace(s) for "
      f"{s['distinct_buckets']} bucket(s), "
      f"{s['winners_agree']}/{s['n_requests_total']} winners agree")
EOF
fi

if [[ "$CHAOS" == 1 ]]; then
    echo "== chaos matrix (fault injection over every registry point) =="
    # Exit status is the number of scenarios that failed to recover
    # with parity; the driver also fails on a registry point with no
    # scenario, so growing runtime/faults.py without covering the new
    # point here breaks CI.
    python scripts/chaos.py

    echo "== fault-tolerance bench (journal machinery gate) =="
    python -m benchmarks.bench_faults --n-iter 5 \
        --out runs/BENCH_explorer_smoke.json
    python - <<'EOF'
import json
with open("runs/BENCH_explorer_smoke.json") as f:
    r = json.load(f)["faults"]
# The ISSUE acceptance gate: shard journaling must add <2% to the warm
# full-suite sweep.  Gated on the serialized machinery upper bound
# (zero async-overlap credit), which is reproducible under ambient
# load where an end-to-end A/B of two ~80ms sweeps is not.
pct = r["machinery_overhead_pct"]
assert pct < 2.0, \
    f"journal machinery adds {pct:.2f}% to the warm sweep (gate: <2%)"
assert r["shards_resumed"] == r["crash_after_shards"], \
    "recovery run did not resume every journaled shard"
print(f"journal machinery: {r['publish_machinery_us']:.0f}us/publish x "
      f"{r['n_shards']} shards = {pct:.2f}% of the "
      f"{r['sweep_plain_ms']:.1f}ms warm sweep (gate <2%); "
      f"e2e A/B {r['journal_overhead_pct']:.2f}% (noisy, informational); "
      f"crash at shard {r['crash_after_shards']} recovered in "
      f"{r['recovery_ms']:.1f}ms")
EOF
fi
echo "CI OK"
