"""Streaming fleet tuning: jobs arrive over time, warm starts are logged.

A `TuningSession` is a long-lived tuning service.  Jobs are submitted in
waves (here: the paper's recurring Spark/Hadoop workloads re-arriving, the
Blink scenario); each submission is probe-classified against the session's
`ProfileCache`, its §III-D split is computed vectorized, and the search
joins a lockstep chunk at the next `step()`.  Once a memory-signature
class has completed trials, later arrivals in the same class are
WARM-STARTED: their packed observation/feature buffers are seeded from the
class history, the random initialization is skipped, and the EI stop
criterion usually fires after a handful of fresh trials.

    PYTHONPATH=src python examples/streaming_fleet.py
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.core.bayesopt import BOSettings
from repro.fleet import ProfileCache, TuningSession, cluster_fleet

KEYS = ["terasort/hadoop/bigdata", "kmeans/spark/huge",
        "join/spark/bigdata", "pagerank/hadoop/bigdata"]
WAVES = 3


def main() -> None:
    session = TuningSession(
        settings=BOSettings(max_iters=16),
        cache=ProfileCache(),  # session-owned profile reuse (Flora-style)
        warm_start=True,
        to_exhaustion=False,  # stop at the EI convergence threshold
    )
    reported = 0
    for wave in range(WAVES):
        print(f"\n== wave {wave}: {len(KEYS)} jobs arrive ==")
        for i, job in enumerate(cluster_fleet(KEYS)):
            session.submit(job, seed=100 * wave + i)
        # Advance the whole fleet one batched BO iteration at a time; a real
        # service would interleave these steps with further submissions.
        while session.step():
            pass
        for out in session.results()[reported:]:
            tag = f"warm×{len(out.seeded)}" if out.seeded else "cold"
            print(f"  {out.name:26s} [{out.memory_model.category.value:7s}]"
                  f" {tag:8s} fresh trials {len(out.records):2d} "
                  f"best {out.best_cost:.3f}")
        reported = len(session.results())

    outs = session.results()
    warm = [o for o in outs if o.seeded]
    cold = [o for o in outs if not o.seeded]
    mean = lambda xs: sum(xs) / max(len(xs), 1)
    print(f"\nprofile cache: {session.cache.hits} hits / "
          f"{session.cache.misses} misses; "
          f"warm-started {session.warm_hits} jobs "
          f"({session.warm_trials} seeded trials)")
    print(f"fresh trials to convergence: "
          f"cold {mean([len(o.records) for o in cold]):.1f} "
          f"vs warm {mean([len(o.records) for o in warm]):.1f}")


if __name__ == "__main__":
    main()
