#!/usr/bin/env bash
# Tier-1 verify: the linter, then the test suite on the CPU (Pallas in
# interpret mode). The installed stack has the extras (zstandard,
# hypothesis); their imports stay gated in-tree so a base install still
# collects. This is the command CI runs.
#
# Tests marked @pytest.mark.slow (long-grid calibration sweeps, full
# benchmark-scale evals) are deselected by default via pyproject's
# addopts; run them explicitly with:  pytest -m slow
#
# A wall-time budget guards against tier-1 runtime regressions (the
# calibration sweeps once pushed the suite past 5 minutes): override
# with TIER1_BUDGET_S for slower boxes. The default allows for the
# seed's heavy model/serving compiles, which dominate the wall time.
set -euo pipefail
cd "$(dirname "$0")/.."
TIER1_BUDGET_S="${TIER1_BUDGET_S:-600}"
t0=$(date +%s)
# Invariant linter first — pure stdlib AST analysis, sub-second, and
# strict (the committed baseline is empty and stays that way): tracer
# readbacks, nondeterministic artifact writers, registry-contract
# drift, silent dispatch fallbacks, donation bugs and CIM6xx range
# proofs fail the build before any jax compile spends wall time. The
# run regenerates the range certificate into a tempdir and diffs it
# against the committed results/analysis/range-certificate.json —
# certificate drift (a geometry or proof changing without the
# committed document) fails the same as a finding. See docs/analysis.md.
cert_tmp="$(mktemp -d)"
trap 'rm -rf "${cert_tmp}"' EXIT
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.analysis src/repro --strict \
    --certificate "${cert_tmp}/range-certificate.json"
if ! cmp -s "${cert_tmp}/range-certificate.json" \
        results/analysis/range-certificate.json; then
    echo "FAIL: range certificate drifted from the committed" \
        "results/analysis/range-certificate.json — regenerate with" \
        "'PYTHONPATH=src python -m repro.analysis src/repro --strict'" \
        "and commit the result" >&2
    diff "${cert_tmp}/range-certificate.json" \
        results/analysis/range-certificate.json | head -40 >&2 || true
    exit 1
fi
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q "$@"
elapsed=$(( $(date +%s) - t0 ))
echo "tier-1 wall time: ${elapsed}s (budget ${TIER1_BUDGET_S}s)"
if [ "${elapsed}" -gt "${TIER1_BUDGET_S}" ]; then
    echo "FAIL: tier-1 exceeded the ${TIER1_BUDGET_S}s wall-time budget" >&2
    exit 1
fi
# Smoke the plan/execute, macro-variant and kernel-dispatch benchmark
# paths end to end (CI-scale shapes): catches engine/backend/variant
# regressions the unit tests abstract over. The `kernels` bench also
# enforces the no-silent-fallback guard — it RAISES (failing this
# script) if an explicit Pallas request for any variant with a
# registered Pallas kernel ever resolves to the jnp scan — and
# measures the tracked headline (calibrated-analog vs int8-exact
# decode at the LM decode cell) into a throwaway JSON, gated below
# against the committed BENCH_kernels.json baseline: a fresh ratio
# more than 20% above the committed one fails the build. The ratio
# (not raw microseconds) is compared so a slower CI box cancels out
# of both sides.
bench_tmp="$(mktemp -d)"
trap 'rm -rf "${bench_tmp}" "${cert_tmp}"' EXIT
REPRO_BENCH_OUT="${bench_tmp}/BENCH_kernels.json" \
    PYTHONPATH=src:.${PYTHONPATH:+:$PYTHONPATH} \
    python benchmarks/run.py --only plan,variants,kernels --smoke
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - "$bench_tmp" <<'PYEOF'
import json, pathlib, sys
fresh = json.loads(
    (pathlib.Path(sys.argv[1]) / "BENCH_kernels.json").read_text()
)["headline"]
base = json.loads(pathlib.Path("BENCH_kernels.json").read_text())["headline"]
limit = base["ratio"] * 1.2
print(
    f"headline analog/exact ratio: fresh={fresh['ratio']:.3f} "
    f"committed={base['ratio']:.3f} limit={limit:.3f}"
)
if fresh["cell"] != base["cell"]:
    sys.exit(f"FAIL: headline cell changed {base['cell']} -> {fresh['cell']}")
if fresh["ratio"] > limit:
    sys.exit(
        f"FAIL: headline ratio regressed >20% vs committed "
        f"BENCH_kernels.json ({fresh['ratio']:.3f} > {limit:.3f}); "
        "if the regression is intended, re-measure with "
        "`python benchmarks/run.py --only kernels` and commit the "
        "refreshed baseline"
    )
PYEOF
# When BENCH_ARTIFACT_DIR is set (CI does this), keep the fresh bench
# JSON past the tempdir cleanup so the workflow can upload it as an
# artifact — the per-PR perf trajectory next to the committed baseline.
if [ -n "${BENCH_ARTIFACT_DIR:-}" ]; then
    mkdir -p "${BENCH_ARTIFACT_DIR}"
    cp "${bench_tmp}/BENCH_kernels.json" \
        "${BENCH_ARTIFACT_DIR}/BENCH_kernels.json"
fi
# Pareto/refinement smoke: tiny grid + stub eval exercises the
# cutoff/vdd sweep axes, the energy cost model, greedy refinement and
# the byte-deterministic report writer; the full resnet refinement
# lives under `pytest -m slow`, keeping tier-1 inside TIER1_BUDGET_S.
# (Since PR 6 this routes through the repro.sweep harness + the
# committed configs/sweeps/pareto_smoke.json config.)
PYTHONPATH=src:.${PYTHONPATH:+:$PYTHONPATH} \
    python benchmarks/pareto.py --smoke
# Sweep-harness smoke: the tiny committed config end to end — dry-run
# feasibility validation, a 2-point resumable run into a throwaway
# dir, and the analysis pass rendering the versioned pareto report.
sweep_tmp="$(mktemp -d)"
trap 'rm -rf "${sweep_tmp}" "${bench_tmp}" "${cert_tmp}"' EXIT
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.sweep configs/sweeps/ci_smoke.json --dry-run \
    --out "${sweep_tmp}"
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.sweep configs/sweeps/ci_smoke.json \
    --out "${sweep_tmp}"
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.sweep configs/sweeps/ci_smoke.json --analyze \
    --out "${sweep_tmp}"
