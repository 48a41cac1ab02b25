"""Open loop: many independent users sharing one service.

Searches arrive at ``rate_per_s`` for ``seconds``, submitted when due
whatever the service is doing.  Every seed gets the same work in another
order, and no search repeats: the N = ``rate × seconds`` searches are
fixed (search i runs job i mod J of the deployment's J jobs, with its
initialization seed drawn from ``[pool_seed, 1]``), the run's seed orders
them, and the N inter-arrival gaps are the exponential distribution's
quantiles at (i + ½)/N, shuffled by the seed.  The warm-up's searches
draw their seeds from ``[pool_seed, 0]``.

After the last arrival the run waits up to ``drain_wait_s`` for the
searches still in flight; one that does not finish by then has failed.
Over every search due in the window:

    sojourn    = publication − due time
    queue wait = admission into a chunk − due time
"""

from __future__ import annotations

import time

import numpy as np

import adapter


def searches(ctx, key, n: int) -> list:
    """[(job index, initialization seed)] of n searches."""
    rng = np.random.default_rng([ctx.traffic["pool_seed"], *key])
    return [(i % len(ctx.jobs), int(s))
            for i, s in enumerate(rng.integers(0, 2**31, size=n))]


def schedule(rate: float, seconds: float, seed: int):
    """(due offsets in seconds, search index) per arrival."""
    rng = np.random.default_rng([seed, 5])
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    due = np.cumsum(rng.permutation(gaps))
    which = rng.permutation(n)
    keep = due <= seconds
    return due[keep], which[keep]


def warm(ctx) -> None:
    """Arrivals meet the service at any point, so admissions form chunks
    of every extent from 1 (run at 2) to 8: compile each once."""
    pool = searches(ctx, (0,), 8)
    for rows in range(2, 9):
        ctx.svc.pause()
        for j, s in pool[:rows]:
            adapter.submit(ctx.svc, ctx.jobs[j], s)
        ctx.svc.drain()


def run(ctx, seconds: float) -> dict:
    tr = ctx.traffic
    due, which = schedule(tr["rate_per_s"], seconds, ctx.seed)
    pool = searches(ctx, (1,), max(1, int(round(tr["rate_per_s"] * seconds))))
    runs = []
    late = []
    t0 = time.perf_counter()
    for at, i in zip(due, which):
        lag = t0 + at - time.perf_counter()
        if lag > 0:
            time.sleep(lag)
        late.append(time.perf_counter() - (t0 + at))
        j, s = pool[i]
        h = adapter.submit(ctx.svc, ctx.jobs[j], s)
        runs.append({"handle": h, "job": j, "due": t0 + at})
    deadline = t0 + seconds + tr["drain_wait_s"]
    while (not all(s["handle"].done for s in runs)
           and time.perf_counter() < deadline):
        time.sleep(0.005)
    done = ctx.session.completed_at
    admitted = ctx.session.admitted_at
    end = time.perf_counter()
    sojourn = [done.get(s["handle"].uid, end) - s["due"] for s in runs]
    wait = [admitted.get(s["handle"].uid, end) - s["due"] for s in runs]
    return {
        "searches": runs, "t0": t0,
        "t_end": max(done.get(s["handle"].uid, end) for s in runs),
        "e2e": {"sojourn_p50_s": float(np.percentile(sojourn, 50)),
                "sojourn_p95_s": float(np.percentile(sojourn, 95))},
        "notes": {"queue_wait_s": wait,
                  "generator_late_p95_s": float(np.percentile(late, 95)),
                  "generator_late_max_s": float(np.max(late))},
    }
