#!/usr/bin/env python3
"""Readings the limits of `check.py` are set from, in one process per cell.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... --seconds 3 \
        [--control high,bfloat16] [--rates 20,40,...] [--record-trace DIR]

For each seed: the cell's set-up and a short window at its own load, then
the check's numbers for the service and, for each ``--control``
precision, for the control: the reference itself, in float32 on the chip
with JAX's default matrix-product precision lowered to that setting, put
in the service's place at the same states (its own pick).

``--rates`` instead sweeps an open-loop cell's arrival rate, one window
per rate, and prints sojourn percentiles and how far completions lagged
arrivals.  ``--record-trace DIR`` keeps the raw profiler trace of each
window under DIR.  Each reading is one JSON line on standard output.
Needs a TPU, like `run.py`.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import numpy as np  # noqa: E402

import check  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402

# The control's matrix-product precision: one step below the float32 at
# `highest` that the deployments state.
CONTROL = "high"


def control_answer(enc64, surrogate, precision):
    """The reference as the system under test: float32 on the chip with
    matrix products at ``precision``; its own grid choice and pick."""
    import jax
    import jax.numpy as jnp
    from jax.scipy.special import erf

    enc = jnp.asarray(enc64, jnp.float32)

    @jax.jit
    def step(trials, costs, cand):
        fits = reference.grid_fits(jnp, enc, trials, costs, surrogate)
        h = jnp.argmax(fits[0])
        ei = reference.expected_improvement(jnp, erf, enc, costs, fits, h,
                                            cand, surrogate)
        return jnp.argmax(ei)

    def answer(s, k, trials, costs, cand):
        with jax.default_matmul_precision(precision):
            pick = step(jnp.asarray(trials, jnp.int32),
                        jnp.asarray(costs, jnp.float32), jnp.asarray(cand))
        return int(pick)

    return answer


def one(cell, seed, seconds, controls, trace_dir, counter):
    t0 = time.perf_counter()
    ctx = harness.setup(cell, seed, cell["workload"]["chips"])
    t_setup = time.perf_counter() - t0
    win = harness.window(ctx, seconds, trace_dir, counter)
    ctx.svc.shutdown(drain=False)
    searches = harness.searches_of(ctx, win)
    checker = check.Checker(ctx.cfg, ctx.data)
    states = checker.sample(searches, seed)
    out = {"seed": seed, "setup_s": t_setup, "searches": len(searches),
           "states": len(states), "e2e": win["e2e"],
           "compiles": dict(counter.seen)}
    t1 = time.perf_counter()
    out["service"] = checker.rules(searches)
    out["service"].update(checker.readings(states, check.service_answer))
    out["check_s"] = time.perf_counter() - t1
    for prec in controls:
        t1 = time.perf_counter()
        ans = control_answer(checker.enc, ctx.cfg["surrogate"], prec)
        out["control_" + prec] = checker.readings(states, ans)
        out["control_" + prec + "_s"] = time.perf_counter() - t1
    return out


def sweep(cell, seed, seconds, rates, counter):
    for rate in rates:
        c = copy.deepcopy(cell)
        c["traffic"]["rate_per_s"] = rate
        ctx = harness.setup(c, seed, c["workload"]["chips"])
        win = harness.window(ctx, seconds, None, counter)
        ctx.svc.shutdown(drain=False)
        done = ctx.session.completed_at
        last_due = max(s["due"] for s in win["searches"])
        lag = max(done.get(s["handle"].uid, time.perf_counter())
                  for s in win["searches"]) - last_due
        yield {"rate_per_s": rate, "searches": len(win["searches"]),
               "finished": sum(s["handle"].uid in done
                               for s in win["searches"]),
               "lag_after_last_arrival_s": lag, **win["e2e"],
               "queue_wait_p95_s": float(np.percentile(
                   win["notes"]["queue_wait_s"], 95)),
               "generator_late_p95_s": win["notes"]["generator_late_p95_s"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--rates", default="")
    ap.add_argument("--record-trace", default="")
    args = ap.parse_args(argv)

    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: JAX found no TPU", file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(harness.OUT_DIR, "jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell = harness.load_cell(args.workload)
    counter = harness._CompileCounter()
    seeds = [int(s) for s in args.seeds.split(",")]
    if args.rates:
        rates = [float(r) for r in args.rates.split(",")]
        for row in sweep(cell, seeds[0], args.seconds, rates, counter):
            print(json.dumps(row), flush=True)
        return 0
    controls = [c for c in args.control.split(",") if c]
    for seed in seeds:
        trace_dir = None
        if args.record_trace:
            trace_dir = os.path.join(args.record_trace,
                                     f"{args.workload}-{seed}")
            shutil.rmtree(trace_dir, ignore_errors=True)
        print(json.dumps(one(cell, seed, args.seconds, controls, trace_dir,
                             counter)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
