"""Closed-loop waves: a team re-tuning its fleet of recurring jobs.

Each wave submits the deployment's ``concurrent_searches`` searches at
once (one per job, cycling over the deployment's jobs) to the paused
service, then drains it; the next wave follows when the last search of
this one is published.

No search repeats within a run, and every seed gets the same work.  How
long a wave takes depends on its searches (a Ruya search stops once its
max EI falls below 10 % of its best, and a chunk steps until its last
member stops), so the waves come in blocks of ``block_waves``: block b's
initialization seeds are drawn from ``[pool_seed, 1, b]``, the blocks run
in order, and the run's seed orders the waves within each block.  The
window is a whole number of blocks: it opens at the first submit and
closes at the last publication of the block running when ``seconds`` have
passed.  The warm-up wave's seeds come from ``[pool_seed, 0]``, so it
repeats no search of the window.

    searches_per_s = searches published in the window / its length
"""

from __future__ import annotations

import time

import numpy as np

import adapter


def _waves(ctx, key, count) -> list:
    """``count`` waves of [(job index, initialization seed)]."""
    w = ctx.cfg["service"]["concurrent_searches"]
    rng = np.random.default_rng([ctx.traffic["pool_seed"], *key])
    return [[(i % len(ctx.jobs), int(s)) for i, s in enumerate(row)]
            for row in rng.integers(0, 2**31, size=(count, w))]


def block(ctx, b: int) -> list:
    """Block b's waves."""
    return _waves(ctx, (1, b), ctx.traffic["block_waves"])


def _wave(ctx, wave) -> list:
    ctx.svc.pause()
    searches = []
    for j, s in wave:
        due = time.perf_counter()
        h = adapter.submit(ctx.svc, ctx.jobs[j], s)
        searches.append({"handle": h, "job": j, "due": due})
    ctx.svc.drain()
    return searches


def warm(ctx) -> None:
    """One wave compiles the only chunk extents the waves hit."""
    _wave(ctx, _waves(ctx, (0,), 1)[0])


def run(ctx, seconds: float) -> dict:
    rng = np.random.default_rng([ctx.seed, 3])
    searches = []
    b = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        waves = block(ctx, b)
        for i in rng.permutation(len(waves)):
            searches += _wave(ctx, waves[i])
        b += 1
    done = ctx.session.completed_at
    t_end = max(done[s["handle"].uid] for s in searches)
    n = sum(s["handle"].uid in done for s in searches)
    return {"searches": searches, "t0": t0, "t_end": t_end,
            "e2e": {"searches_per_s": n / (t_end - t0)},
            "notes": {"blocks": b}}
