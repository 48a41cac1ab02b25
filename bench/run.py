#!/usr/bin/env python3
"""Run one benchmark cell once on the TPU chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets up the cell (its data from ``--seed``, the tuning service, warm-up of
every shape its traffic hits), measures for ``--seconds``, checks what the
window produced against the plain reference, and prints one JSON object
as the last line of standard output: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``: each number compared with its limit, also printed as the last
lines of standard error.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips
than the cell asks for.  JAX's persistent compilation cache lives in
``bench_out/jax_cache`` of the checkout unless ``JAX_COMPILATION_CACHE_DIR``
names another.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = harness.load_cell(args.workload)
    chips = cell["workload"]["chips"]

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"bench/run.py: {args.workload} needs {chips} TPU chip(s); JAX "
              f"found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(harness.OUT_DIR, "jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    result, lines = harness.run(args.workload, args.seed, args.seconds,
                                bool(args.trace))
    sys.stdout.flush()
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
