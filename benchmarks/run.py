"""Benchmark driver: one module per paper table/figure + roofline + tuner.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --only table2 roofline

Env: RUYA_BENCH_REPS (default 50; the paper used 200 repetitions).
"""

from __future__ import annotations

import argparse
import sys
import time
import traceback

def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset: table1 table2 table3 fig1 fig4 fig5 "
                         "roofline kernels tuner")
    ap.add_argument("--skip-tuner", action="store_true",
                    help="skip the compile-heavy tuner benchmark")
    args = ap.parse_args()

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        fig1_memory_cliff,
        fig4_convergence,
        fig5_cumulative_cost,
        kernel_bench,
        roofline,
        table1_memory_categorization,
        table2_iterations,
        table3_profiling_time,
    )

    suites = {
        "table1": table1_memory_categorization.run,
        "table2": table2_iterations.run,
        "table3": table3_profiling_time.run,
        "fig1": fig1_memory_cliff.run,
        "fig4": fig4_convergence.run,
        "fig5": fig5_cumulative_cost.run,
        "roofline": roofline.run,
        "kernels": kernel_bench.run,
    }
    if not args.skip_tuner:
        from benchmarks import tuner_vs_baseline

        suites["tuner"] = tuner_vs_baseline.run

    selected = args.only or list(suites)
    failures = []
    for name in selected:
        if name not in suites:
            print(f"unknown suite {name!r}; have {list(suites)}")
            sys.exit(2)
        t0 = time.time()
        print(f"\n{'='*72}\nBENCH {name}\n{'='*72}")
        try:
            suites[name]()
            print(f"[{name}] done in {time.time()-t0:.0f}s")
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"\nFAILED suites: {failures}")
        sys.exit(1)
    print("\nAll benchmark suites completed.")


if __name__ == "__main__":
    main()
