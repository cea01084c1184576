"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (benchmarks.common.emit).
Default is --quick (CPU-friendly subset per figure); --full covers every
(model x dataset) cell the paper reports.
"""

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="all (model x dataset) cells (slow on CPU)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. fig8,table3")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: tiny-size serving benchmark only, so "
                         "BENCH_JSON regressions are caught on every PR")
    args = ap.parse_args()
    quick = not args.full

    from repro.common import enable_compilation_cache

    enable_compilation_cache()
    from benchmarks import (
        fig7_device_dse,
        fig7c_arch_dse,
        fig8_orchestration,
        fig9_breakdown,
        fig10_12_comparison,
        kernel_micro,
        serving_throughput,
        table2_datasets,
        table3_accuracy,
    )

    suites = {
        "table2": table2_datasets.run,
        "table3": table3_accuracy.run,
        "fig7": fig7_device_dse.run,
        "fig7c": fig7c_arch_dse.run,
        "fig8": fig8_orchestration.run,
        "fig9": fig9_breakdown.run,
        "fig10_12": fig10_12_comparison.run,
        "kernels": kernel_micro.run,
        "serving": serving_throughput.run,
    }
    if args.smoke:
        if args.only or args.full:
            ap.error("--smoke is a fixed tiny suite; drop --only/--full")
        suites = {
            "serving": lambda quick: serving_throughput.run(
                quick=True, requests=12, working_set=4, slots=4,
                ticks=16, arrivals=4),
            # Tiny fused-vs-unfused kernel comparison so BENCH_JSON perf
            # regressions in the Pallas path are caught on every PR too.
            "kernels": lambda quick: kernel_micro.run(quick=True,
                                                      smoke=True),
        }
    selected = (args.only.split(",") if args.only else list(suites))

    print("name,us_per_call,derived")
    t0 = time.time()
    for name in selected:
        try:
            suites[name](quick=quick)
        except Exception as e:  # keep the harness running; report the failure
            print(f"{name}/ERROR,0.0,{type(e).__name__}:{e}", file=sys.stderr)
            raise
    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
