"""Benchmark driver: one module per paper table/figure + roofline + tuner.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --only table2 roofline
    PYTHONPATH=src python -m benchmarks.run --only fleet --smoke

`--only fleet` (re)writes the machine-readable perf baseline
`BENCH_fleet.json` at the repo root — including the streaming
`TuningSession` scenario (workload D: 64 recurring jobs in 8 waves,
warm-start amortization; standalone via `python -m benchmarks.fleet_bench
--session`) and the job-axis sharding sweep (workload E; `--shards N ...`
is passed through to the fleet bench, default 2 — when the fleet suite is
selected, and only then, this driver forces
--xla_force_host_platform_device_count=max(--shards, 2) before JAX
initializes so the shard lanes have devices to run on).  `--smoke` runs suites that
support it in a seconds-scale wiring mode (currently: fleet) — the same
mode `pytest -m bench_smoke` exercises.

Env: RUYA_BENCH_REPS (default 50; the paper used 200 repetitions).
"""

from __future__ import annotations

import argparse
import inspect
import sys
import time
import traceback

def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None,
                    help="subset: table1 table2 table3 fig1 fig4 fig5 "
                         "roofline kernels fleet tuner")
    ap.add_argument("--skip-tuner", action="store_true",
                    help="skip the compile-heavy tuner benchmark")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale wiring mode for suites that support it")
    ap.add_argument("--shards", type=int, nargs="*", default=None,
                    help="shard counts for the fleet bench's job-axis "
                         "sharding sweep (passed through to --only fleet)")
    args = ap.parse_args()

    if args.only is None or "fleet" in args.only:
        # The fleet suite's sharded lanes need a multi-device CPU topology,
        # forced before the jax-importing benchmark modules below can
        # initialize the backend.  Only the fleet suite pays for it: extra
        # forced devices dilute the intra-op thread pool, and the other
        # suites' absolute numbers must stay comparable to their baselines.
        from repro.hostdevices import force_host_device_count

        force_host_device_count(max([2] + list(args.shards or [])))

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    from benchmarks import (
        fig1_memory_cliff,
        fig4_convergence,
        fig5_cumulative_cost,
        fleet_bench,
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
        "fleet": fleet_bench.run,
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
            fn = suites[name]
            kwargs = {}
            params = inspect.signature(fn).parameters
            if args.smoke and "smoke" in params:
                kwargs["smoke"] = True
            if args.shards is not None and "shards" in params:
                kwargs["shards"] = tuple(args.shards)
            fn(**kwargs)
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
