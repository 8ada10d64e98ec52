"""Benchmark driver: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (benchmarks/common.emit).
``--full`` runs the paper-fidelity sample counts (10K Monte-Carlo,
512-image evals, full sweep grids); default is the quick profile;
``--smoke`` shrinks further to CI scale (scripts/check.sh runs
``--only plan --smoke`` so the plan/execute path stays exercised in
tier-1 without the benchmark cost).
"""

import argparse
import inspect
import sys
import traceback

from benchmarks import (
    fig5_linearity,
    fig7_sweeps,
    fig9_dac_adc,
    fig10_energy,
    kernel_bench,
    pareto,
    roofline,
    table1_accuracy,
    table2_summary,
    variants_bench,
)
from repro.launch.compile_cache import enable_compile_cache

ALL = {
    "fig5": fig5_linearity.main,
    "fig7": fig7_sweeps.main,
    "fig9": fig9_dac_adc.main,
    "fig10": fig10_energy.main,
    "table1": table1_accuracy.main,
    "table2": table2_summary.main,
    "kernel": kernel_bench.main,
    "kernels": kernel_bench.kernels_main,
    "pareto": pareto.main,
    "plan": kernel_bench.planned_main,
    "roofline": roofline.main,
    "variants": variants_bench.main,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-fidelity sample counts (slow)")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-scale shapes/reps (implies quick)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(ALL))
    args = ap.parse_args()
    if args.full and args.smoke:
        ap.error("--full and --smoke are mutually exclusive")
    names = args.only.split(",") if args.only else list(ALL)
    unknown = [n for n in names if n not in ALL]
    if unknown:
        print(
            f"error: unknown benchmark(s) {unknown}; "
            f"registered: {','.join(sorted(ALL))}",
            file=sys.stderr, flush=True,
        )
        sys.exit(2)
    enable_compile_cache()
    quick = not args.full
    failed = []
    for name in names:
        print(f"# --- {name} ---", flush=True)
        try:
            fn = ALL[name]
            kwargs = {"quick": quick}
            if "smoke" in inspect.signature(fn).parameters:
                kwargs["smoke"] = args.smoke
            fn(**kwargs)
        except Exception:  # noqa: BLE001 - keep the harness running
            failed.append(name)
            traceback.print_exc()
    if failed:
        print(f"# FAILED: {failed}", flush=True)
        sys.exit(1)
    print("# all benchmarks complete", flush=True)


if __name__ == "__main__":
    main()
