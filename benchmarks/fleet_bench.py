"""Fleet-scale search benchmark: packed batched engine vs sequential loop.

Four measurements; A–C are trace-checked against the sequential engine:

  A. **Paper replay** — the 16 evaluation jobs × 4 seeds, full two-phase
     Ruya search over the 69-config space, to exhaustion (the Table II
     protocol as a fleet).
  B. **Priority-only service fleet** — 64 runs of the recurring flat-memory
     jobs (terasort, join, Hadoop pagerank) tuned *within their
     memory-derived priority group only* (10 configs each).  This is the
     paper's own observation (the optimum lands in the priority group for
     every categorized job) run the way Blink-style systems run tuning:
     small spaces, cheap trials, as a routine re-tuning service.
  C. **Search-space scaling sweep** — synthetic spaces of n ∈ {69, 256,
     512, 1024, 8192, 32768} configurations plus a step-only n = 131072
     catalog-scale point, a 64-job fleet with the paper-regime trial
     budget (B = 24): per-BO-step time of the feature-buffer engine vs
     the fused streaming-kernel lane (``layout="fused"``,
     `repro.kernels.ei_argmax` — its tiled (max EI, argmax) reduction
     never materializes the (B,n) cross block, and XLA's compiled
     transient footprint is reported for both layouts to show it) vs the
     retained d²-gather step (n ≤ 8192 — its (n,n) tensor is the memory
     wall the feature buffer removed) vs the dense full-extent step
     (n ≤ 1024, O(18n³)), plus end-to-end batched vs sequential and
     per-point memory reporting (analytic geometry bytes, largest live
     device buffer, peak RSS).  The fused lane is trace-checked against
     the feature lane at EVERY extent — it has no n ceiling, which is its
     point.  This is the engine's target regime — B ≪ n, n up to
     10⁴–10⁵ — where the gather engine was memory-bound and the dense
     engine flops-bound.
  D. **Streaming session** (`--session` to run it alone) — 64 recurring
     paper jobs arriving in 8 waves against one `TuningSession` with
     warm-starting on: wave 0 is cold, later waves hit the probe cache and
     are seeded from their memory-signature class's completed trials.
     Asserts warm-started searches reach the EI convergence threshold in
     strictly fewer fresh trials than cold starts, and reports cache hit
     rates and the seeded-trial counts.
  E. **Job-axis sharding** (`--shards N [N ...]`) — the service fleet (B)
     re-run with the lockstep chunks sharded across JAX devices
     (`repro.fleet.sharding`): per shard count, best-of wall clock vs the
     single-device reference and a bit-identity assertion on every trace.
     On CPU the devices come from --xla_force_host_platform_device_count
     (forced at the top of this module and by `benchmarks/run.py` when
     nothing set it).  Target on the 2-core container: ≥ 1.5× at 2 shards.
  F. **Adversarial fleet** — the paper fleet re-run through a disturbance
     schedule (`repro.cluster.faults`): Poisson transient profiling
     failures (hash-drawn at rate 0.25, retried with deterministic
     backoff), 10% straggler trials (reported, never fed back), 10% of the
     fleet cancelled mid-flight, one permanently broken job (full runs
     only), and one shard-loss event (a live `reshard` from 2 devices to
     1 mid-drain).  Reports completion rate (converged / non-cancelled,
     asserted ≥ 95% under the schedule), wasted trials (the cancelled
     jobs' partial work), retry overhead (extra profiling attempts and
     charged backoff seconds), and straggler counts.
  G. **Open-loop service fleet** — Poisson arrivals against the async
     `TuningService` (`repro.fleet.service`) vs the global-lockstep
     `TuningSession`, same pre-drawn arrival times on both sides, three
     heterogeneous admission groups (24/96/384-config spaces → distinct
     chunk shapes), and deterministic per-(group, iteration) straggler
     stalls injected through the service's ``pace`` seam on one side
     and an equivalent inline sleep in the single-threaded barrier loop
     on the other.  Under lockstep every straggling group's stall
     serializes through the barrier; under the service it stalls only
     that group's dispatch thread.  Reports sustained jobs/sec
     (completions over the first-arrival → last-completion window) and
     p50/p99 job sojourn (completion − scheduled arrival); outcomes are
     asserted bit-identical per job across the two drivers, and the
     async side must sustain ≥ 1.3× the lockstep jobs/sec at the full
     protocol (≥ 1.1× in smoke).
  H. **Cost-aware pricing** — the `repro.cluster.pricing` catalogs:
     per-catalog USD-argmin movement over the Table I jobs (≥ 3 must move
     on at least one book), the spot-volatility fleets searched under
     both `objective="runtime"` and `objective="cost"` (reported USD
     savings of the cost picks, ≥ 1 job where the objectives diverge,
     Pareto-front invariants asserted on every cost outcome), and the
     family-constrained Graviton scenarios at table level.

The sweep also asserts **buffer donation**: the lockstep update consumes
(donates) its input state, so each fleet iteration updates the observation
mask and the packed trial/target/(B,d)-feature buffers in place — the old
state's device buffers are deleted after one update, i.e. no per-iteration
device copies remain.

`benchmarks/run.py --only fleet` (and running this module directly, at the
default 64 jobs) writes the machine-readable perf baseline to
`BENCH_fleet.json` at the repo root: per-step ms, end-to-end seconds,
speedups, and memory numbers, so the perf trajectory is tracked PR over PR.
Smoke or reduced-job runs never touch the committed baseline (their numbers
are not comparable); `--smoke` (or `run(smoke=True)`) is the seconds-scale
wiring check used by `pytest -m bench_smoke` — it includes an n = 32768
sweep point so the 10⁴–10⁵ regime stays wired.

    PYTHONPATH=src python -m benchmarks.fleet_bench [--jobs 64] [--no-check]
                                                    [--smoke]
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import threading
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

# The sharded lanes need a multi-device CPU topology, which must be forced
# before the JAX backend initializes.  Under pytest, conftest.py has done
# it; `benchmarks.run` does it when (and only when) the fleet suite is
# selected; the block below covers `python -m benchmarks.fleet_bench`
# directly — gated on __main__ so that merely IMPORTING this module (e.g.
# `benchmarks.run --only table2` imports every suite) never changes
# another benchmark's device topology.  Forcing MORE devices than needed
# is not free — the single-device baseline loses wall clock to the extra
# device plumbing — so exactly max(--shards, 2) are forced, and only when
# the caller forced nothing.
def shard_device_count(argv: Sequence[str]) -> int:
    """max(requested --shards, 2), pre-parsed from raw argv — this must
    run before argparse (and therefore before the jax-importing module
    body) can."""
    want = [2]
    argv = list(argv)
    for i, a in enumerate(argv):
        if a == "--shards":  # space-separated: --shards 2 4
            tail = argv[i + 1:]
        elif a.startswith("--shards="):  # argparse's --shards=4 spelling
            tail = [a.split("=", 1)[1]]
        else:
            continue
        for v in tail:
            if v.startswith("-"):
                break
            try:
                want.append(int(v))
            except ValueError:  # argparse will reject it properly later
                break
    return max(want)


if __name__ == "__main__":
    from repro.hostdevices import force_host_device_count

    force_host_device_count(shard_device_count(sys.argv[1:]))

import jax
import jax.numpy as jnp

from benchmarks.common import JOB_ORDER, artifact_path
from repro.core.bayesopt import BOSettings, cherrypick_search
from repro.core.fast_bo import (
    FleetState,
    bo_step_core_dense,
    encode_features,
    precompute_d2,
)
from repro.core.profiler import profile_job
from repro.core.search_space import Configuration, SearchSpace, split_search_space
from repro.fleet import batched_search, cluster_fleet, tune_fleet
from repro.fleet.batched_engine import _CHUNK, _fleet_update

BENCH_JSON = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "BENCH_fleet.json")
)

# Per-step timing caps for the retained layouts.  The dense step is O(18n³)
# flops; the gather step is cheap per step but holds a resident (n,n)
# float32 tensor per job in the chunk — at n = 32768 that would be 4 GiB
# per job, which is precisely the wall the feature buffer removes.
_DENSE_MAX_N = 1024
_GATHER_MAX_N = 8192


def build_fleet(n_jobs: int):
    keys = [JOB_ORDER[i % len(JOB_ORDER)] for i in range(n_jobs)]
    jobs = cluster_fleet(keys)
    # Profile once per distinct job up front: the bench times the *search*
    # engines, and both must see identical splits.
    profiles = {}
    for job in jobs:
        if job.name not in profiles:
            profiles[job.name] = profile_job(job.profile_run, job.full_input_size)
        job.profile_result = profiles[job.name]
    return jobs


def _rngs(n: int) -> List[np.random.Generator]:
    return [np.random.default_rng(1000 + i) for i in range(n)]


def _peak_rss_mb() -> float:
    """Process high-water RSS in MB — MONOTONE over the process lifetime,
    so it is reported once per run, not per sweep point (a per-point value
    would inherit earlier points' gather/dense allocations).  ru_maxrss is
    kilobytes on Linux, bytes on macOS."""
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
    if sys.platform == "darwin":
        return rss / 1024.0**2
    return rss / 1024.0


def _live_device_mb() -> Tuple[float, float]:
    """(total, largest) live device-buffer MB — the on-device footprint."""
    sizes = [a.nbytes for a in jax.live_arrays()]
    if not sizes:
        return 0.0, 0.0
    return sum(sizes) / 1e6, max(sizes) / 1e6


def synthetic_space(n: int, d: int = 6, seed: int = 7) -> Tuple[SearchSpace, np.ndarray]:
    """An n-config space with random features and a smooth cost surface."""
    rng = np.random.default_rng(seed + n)
    feats = rng.normal(size=(n, d))
    space = SearchSpace(
        [
            Configuration(
                name=f"s{i}",
                features=tuple(float(v) for v in feats[i]),
                total_memory=float(i),
            )
            for i in range(n)
        ]
    )
    w = rng.normal(size=d)
    z = feats @ w
    z = (z - z.mean()) / max(float(z.std()), 1e-9)
    table = 1.0 + (z - 0.7) ** 2 + 0.05 * rng.random(n)
    return space, table


def check_buffer_donation() -> dict:
    """Assert the lockstep update donates its state: after one jitted call
    the *input* state's device buffers are deleted (XLA aliased them to the
    outputs), so fleet iterations update in place — no per-iteration device
    copies of the observation mask or the packed trial/target/feature
    buffers (the (B,d) feature buffer rides the same donation contract)."""
    n, j, b = 16, 2, 6
    space, table = synthetic_space(n)
    enc = encode_features(space.encoded())
    d = enc.shape[1]
    geom = jnp.asarray(np.stack([enc] * j))
    state = FleetState(
        obs=jnp.zeros((j, n), bool),
        tried=jnp.full((j, b), -1, jnp.int32),
        py=jnp.zeros((j, b), jnp.float32),
        feats=jnp.zeros((j, b, d), jnp.float32),
        t=jnp.zeros(j, jnp.int32),
        stop=jnp.full(j, -1, jnp.int32),
        pb=jnp.full(j, -1, jnp.int32),
        done=jnp.zeros(j, bool),
        last_ei=jnp.zeros(j, jnp.float32),
        last_best=jnp.full(j, jnp.inf, jnp.float32),
    )
    args = (
        geom, jnp.asarray(np.stack([table] * j), jnp.float32),
        jnp.ones((j, n), bool), jnp.zeros((j, n), bool),
        jnp.zeros((j, 1), jnp.int32), jnp.zeros(j, jnp.int32),
        jnp.full(j, b, jnp.int32), jnp.asarray(0, jnp.int32),
        jnp.asarray(0.0, jnp.float32), jnp.asarray(True),
    )
    old = (state.obs, state.tried, state.py, state.feats)
    new = _fleet_update(state, *args, xi=0.0, layout="feature")
    jax.block_until_ready(new.t)
    deleted = [bool(buf.is_deleted()) for buf in old]
    assert all(deleted), (
        f"state buffers survived the donated lockstep call: {deleted} — "
        "per-iteration device copies are back"
    )
    return {
        "state_donated": True,
        "buffers_checked": ["obs", "tried", "py", "feats"],
    }


def _packed_state_args(space, table, budget: int, layout: str):
    """A warm lockstep (state, args) pair for `_fleet_update` — buffer
    nearly full, budget live — shared by the step timer and the
    compiled-transient-footprint probe so both measure the same program."""
    n = len(space)
    j = _CHUNK
    k = max(budget - 1, 1)  # warm state: buffer nearly full, budget live
    enc = encode_features(space.encoded())
    geom_one = (
        enc if layout in ("feature", "fused")
        else np.asarray(precompute_d2(enc))
    )
    # broadcast_to is a host-side view — the chunk replication only
    # materializes once, on device (at n=8192 the gather layout's stacked
    # (8,n,n) geometry is ~2 GiB there; that resident tensor is exactly
    # the cost being measured, so don't also pay it in host RAM).
    geom = jnp.asarray(np.broadcast_to(geom_one, (j,) + geom_one.shape))
    obs = np.zeros((j, n), bool)
    obs[:, :k] = True
    tried = np.full((j, budget), -1, np.int32)
    tried[:, :k] = np.arange(k)
    py = np.zeros((j, budget), np.float32)
    py[:, :k] = np.asarray(table[:k], np.float32)
    feats = np.zeros((j, budget, enc.shape[1]), np.float32)
    feats[:, :k] = enc[:k]
    state = FleetState(
        obs=jnp.asarray(obs),
        tried=jnp.asarray(tried),
        py=jnp.asarray(py),
        feats=jnp.asarray(feats),
        t=jnp.full(j, k, jnp.int32),
        stop=jnp.full(j, -1, jnp.int32),
        pb=jnp.full(j, -1, jnp.int32),
        done=jnp.zeros(j, bool),
        last_ei=jnp.zeros(j, jnp.float32),
        last_best=jnp.full(j, jnp.inf, jnp.float32),
    )
    args = (
        geom, jnp.asarray(np.stack([table] * j), jnp.float32),
        jnp.ones((j, n), bool), jnp.zeros((j, n), bool),
        jnp.zeros((j, 1), jnp.int32), jnp.zeros(j, jnp.int32),
        jnp.full(j, budget, jnp.int32), jnp.asarray(0, jnp.int32),
        jnp.asarray(0.0, jnp.float32), jnp.asarray(True),
    )
    return state, args


def _time_packed_step(space, table, budget: int, reps: int,
                      layout: str = "feature") -> Tuple[float, float, float]:
    """(seconds/iter, live-device MB, largest-buffer MB) of the packed
    lockstep update, one warm chunk, for any packed geometry layout
    ("feature", "gather", or the streaming-kernel "fused").  Memory is
    sampled while the engine state and geometry are resident — the
    steady-state on-device footprint."""
    state, args = _packed_state_args(space, table, budget, layout)
    state = _fleet_update(state, *args, xi=0.0, layout=layout)  # warm the jit
    jax.block_until_ready(state.t)
    live_mb, largest_mb = _live_device_mb()
    t0 = time.perf_counter()
    for _ in range(reps):
        state = _fleet_update(state, *args, xi=0.0, layout=layout)
    jax.block_until_ready(state.t)
    return (time.perf_counter() - t0) / reps, live_mb, largest_mb


def _step_transient_mb(space, table, budget: int, layout: str) -> float:
    """XLA's compiled transient footprint (temp buffers, MB) of one lockstep
    update — the compiler's own accounting of scratch the step allocates
    beyond its inputs/outputs.  This is where the fused layout's streaming
    reduction shows up: the feature layout's transients hold the (B,n)
    cross block (plus peers) per chunk row, the fused layout's only the
    (B,tile) working set."""
    state, args = _packed_state_args(space, table, budget, layout)
    stats = (
        _fleet_update.lower(state, *args, xi=0.0, layout=layout)
        .compile()
        .memory_analysis()
    )
    return float(stats.temp_size_in_bytes) / 1e6


_dense_chunk_step = jax.jit(jax.vmap(bo_step_core_dense))


def _time_dense_step(space, table, budget: int, reps: int) -> float:
    """Per-iteration seconds of the retained dense full-extent step (the
    pre-packed engine's O(18n³) layout), same chunk extent."""
    n = len(space)
    j = _CHUNK
    k = max(budget - 1, 1)
    encoded = encode_features(space.encoded())
    obs = np.zeros(n, bool)
    obs[:k] = True
    enc8 = jnp.asarray(np.stack([encoded] * j))
    obs8 = jnp.asarray(np.stack([obs] * j))
    y8 = jnp.asarray(np.stack([np.asarray(table, np.float32)] * j))
    cand8 = jnp.asarray(np.stack([~obs] * j))
    out = _dense_chunk_step(enc8, obs8, y8, cand8)  # warm the jit
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = _dense_chunk_step(enc8, obs8, y8, cand8)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps


def bench_scaling_point(
    n: int, n_jobs: int, budget: int, check: bool,
    packed_reps: int = 20, dense_reps: int = 2, step_only: bool = False,
) -> dict:
    """One sweep point: budgeted CherryPick over an n-config synthetic space.

    ``step_only`` skips the end-to-end sequential/batched timing (the
    catalog-scale extension points, n ≥ 10⁵, where a 64-job sequential
    Python loop would dominate the whole bench) — per-step timing, the
    transient-footprint probes, and the fused-vs-feature trace identity
    check still run.
    """
    space, table = synthetic_space(n)
    d = space.encoded().shape[1]
    settings = BOSettings(max_iters=budget)
    tables = [table] * n_jobs
    cost_fn = lambda i: float(table[i])

    t_seq = t_bat = None
    trials = None
    identical = None
    if not step_only:
        rng_seq = _rngs(n_jobs)
        rng_bat = _rngs(n_jobs)
        # Warm both engines' compiles outside the timed region (the batched
        # warm-up must cover the full-extent chunk shape, not a prefix).
        cherrypick_search(space, cost_fn, np.random.default_rng(0),
                          settings=settings, to_exhaustion=True)
        batched_search([space] * n_jobs, tables, _rngs(n_jobs),
                       settings=settings, to_exhaustion=True)

        t0 = time.perf_counter()
        seq = [
            cherrypick_search(space, cost_fn, r, settings=settings,
                              to_exhaustion=True)
            for r in rng_seq
        ]
        t_seq = time.perf_counter() - t0
        t0 = time.perf_counter()
        bat = batched_search([space] * n_jobs, tables, rng_bat,
                             settings=settings, to_exhaustion=True)
        t_bat = time.perf_counter() - t0

        identical = True
        if check:
            for jdx, ref in enumerate(seq):
                tr = bat.job_trace(jdx)
                identical &= tr.tried == ref.tried and tr.costs == ref.costs
            assert identical, f"engines diverged at n={n}"
        trials = sum(len(t.tried) for t in seq)

    gather_identical = None
    fused_identical = None
    if check:
        # Cross-layout identity at every point: each retained/alternative
        # layout must reproduce the feature-buffer traces bit-for-bit (few
        # jobs — the point is the check, not layout throughput).
        g_jobs = min(n_jobs, 2)
        bat_f = batched_search(
            [space] * g_jobs, tables[:g_jobs], _rngs(g_jobs),
            settings=settings, to_exhaustion=True,
        )
        if n <= _GATHER_MAX_N:
            bat_g = batched_search(
                [space] * g_jobs, tables[:g_jobs], _rngs(g_jobs),
                settings=settings, to_exhaustion=True, layout="gather",
            )
            gather_identical = all(
                bat_g.job_trace(jdx).tried == bat_f.job_trace(jdx).tried
                for jdx in range(g_jobs)
            )
            assert gather_identical, f"gather layout diverged at n={n}"
        # The fused streaming-kernel lane has no n ceiling — that is its
        # entire point — so it is checked at every sweep extent.
        bat_u = batched_search(
            [space] * g_jobs, tables[:g_jobs], _rngs(g_jobs),
            settings=settings, to_exhaustion=True, layout="fused",
        )
        fused_identical = all(
            bat_u.job_trace(jdx).tried == bat_f.job_trace(jdx).tried
            and bat_u.job_trace(jdx).costs == bat_f.job_trace(jdx).costs
            for jdx in range(g_jobs)
        )
        assert fused_identical, f"fused layout diverged at n={n}"

    feature_s, live_mb, largest_mb = _time_packed_step(
        space, table, budget, packed_reps, layout="feature")
    fused_s = _time_packed_step(
        space, table, budget, packed_reps, layout="fused")[0]
    gather_s = (
        _time_packed_step(space, table, budget, packed_reps,
                          layout="gather")[0]
        if n <= _GATHER_MAX_N else None
    )
    dense_s = (
        _time_dense_step(space, table, budget, dense_reps)
        if n <= _DENSE_MAX_N else None
    )
    feature_transient_mb = _step_transient_mb(space, table, budget, "feature")
    fused_transient_mb = _step_transient_mb(space, table, budget, "fused")
    return {
        "n": n,
        "budget": budget,
        "n_jobs": n_jobs,
        "chunk": _CHUNK,
        "feature_step_ms": 1e3 * feature_s,
        "fused_step_ms": 1e3 * fused_s,
        "gather_step_ms": 1e3 * gather_s if gather_s is not None else None,
        "dense_step_ms": 1e3 * dense_s if dense_s is not None else None,
        "step_speedup_vs_dense": dense_s / feature_s if dense_s else None,
        "fused_step_speedup_vs_feature": feature_s / fused_s,
        # XLA's compiled transient accounting: the per-chunk scratch the
        # fused layout's streaming reduction eliminates ((B,n) → (B,tile)).
        "feature_step_transient_mb": feature_transient_mb,
        "fused_step_transient_mb": fused_transient_mb,
        "fused_transient_reduction": (
            feature_transient_mb / fused_transient_mb
            if fused_transient_mb > 0 else None
        ),
        # Geometry memory per job: the feature layout's resident (n,d)
        # encoding vs the (n,n) tensor the gather layout would need.
        "geom_feature_mb": n * d * 4 / 1e6,
        "geom_gather_mb": n * n * 4 / 1e6,
        "live_device_mb": live_mb,
        "largest_live_buffer_mb": largest_mb,
        "sequential_s": t_seq,
        "batched_s": t_bat,
        "speedup": t_seq / t_bat if not step_only else None,
        "total_trials": trials,
        "traces_identical": bool(identical) if identical is not None else None,
        "gather_traces_identical": gather_identical,
        "fused_traces_identical": fused_identical,
        "step_only": step_only,
    }


def bench_scaling(ns: Sequence[int], n_jobs: int, budget: int, check: bool,
                  packed_reps: int = 20, dense_reps: int = 2,
                  step_only_ns: Sequence[int] = ()) -> dict:
    rows = []
    for n in list(ns) + list(step_only_ns):
        r = bench_scaling_point(n, n_jobs, budget, check,
                                packed_reps=packed_reps, dense_reps=dense_reps,
                                step_only=n in step_only_ns)
        rows.append(r)
        gather = (f"{r['gather_step_ms']:8.2f}" if r["gather_step_ms"]
                  else "       –")
        dense = (f"{r['dense_step_ms']:9.2f}" if r["dense_step_ms"]
                 else "        –")
        e2e = (
            f"end-to-end {r['batched_s']:6.2f} s batched vs "
            f"{r['sequential_s']:7.2f} s sequential ({r['speedup']:.2f}x)"
            if not r["step_only"] else "end-to-end skipped (step-only point)"
        )
        print(f"  C. n={r['n']:6d}  B={r['budget']:3d}  "
              f"feature step {r['feature_step_ms']:8.2f} ms/chunk  "
              f"fused {r['fused_step_ms']:8.2f} ms "
              f"({r['fused_step_speedup_vs_feature']:.2f}x, transients "
              f"{r['feature_step_transient_mb']:.1f} -> "
              f"{r['fused_step_transient_mb']:.1f} MB, "
              f"{r['fused_transient_reduction']:.0f}x)  "
              f"gather {gather} ms  dense {dense} ms  "
              f"geom {r['geom_feature_mb']:8.2f} MB (vs "
              f"{r['geom_gather_mb']:9.1f} MB d²)  " + e2e)
    return {"budget": budget, "n_jobs": n_jobs, "sweep": rows}


def bench_session_streaming(
    n_jobs: int, waves: int, check: bool,
    settings: BOSettings = BOSettings(),
) -> dict:
    """Workload D: streaming `TuningSession` — jobs arriving in waves.

    ``n_jobs`` recurring paper jobs (the first ``n_jobs // waves`` catalog
    keys, cycling) arrive in ``waves`` submission waves against ONE
    session with warm-starting on and a session-owned `ProfileCache`.
    Wave 0 is all cold; later waves re-submit the same workload keys, hit
    the probe cache, and are warm-started from their signature class's
    completed trials.  The scenario measures the amortization claim:
    fresh trials until the EI convergence threshold fires, warm vs cold
    (asserted strictly fewer when ``check``), plus cache hit rates and
    end-to-end wall time.
    """
    from benchmarks.common import get_sim
    from repro.fleet import ProfileCache, TuningSession

    per = max(n_jobs // waves, 1)
    wave_keys = [JOB_ORDER[i % len(JOB_ORDER)] for i in range(per)]
    # Build every wave's job objects up front (through the shared simulator
    # memo): the timed region below measures the SESSION — probe/profile,
    # vectorized split, lockstep search, warm seeding — not harness setup.
    wave_jobs = [
        cluster_fleet(wave_keys, sims={k: get_sim(k) for k in wave_keys})
        for _ in range(waves)
    ]
    session = TuningSession(
        settings=settings, cache=ProfileCache(), warm_start=True,
        to_exhaustion=False,
    )
    t0 = time.perf_counter()
    submitted = 0
    for jobs in wave_jobs:
        for i, job in enumerate(jobs):
            session.submit(job, seed=1000 + submitted + i)
        submitted += len(jobs)
        # Drain the wave: one batched BO iteration per step for every live
        # search (a real service would interleave submissions here).
        while session.step():
            pass
    elapsed = time.perf_counter() - t0

    outs = session.results()
    warm = [o for o in outs if o.seeded]
    cold = [o for o in outs if not o.seeded]
    mean = lambda xs: float(np.mean(xs)) if xs else None
    cold_iters = mean([len(o.records) for o in cold])
    warm_iters = mean([len(o.records) for o in warm])
    row = {
        "n_jobs": submitted,
        "waves": waves,
        "jobs_per_wave": len(wave_keys),
        "cold_jobs": len(cold),
        "warm_jobs": len(warm),
        "warm_seeded_trials": session.warm_trials,
        "cold_mean_fresh_trials": cold_iters,
        "warm_mean_fresh_trials": warm_iters,
        # None = fully amortized (warm searches needed zero fresh trials).
        "fresh_trial_reduction": (
            cold_iters / warm_iters
            if (warm_iters is not None and warm_iters > 0) else None
        ),
        "cold_mean_best": mean([o.best_cost for o in cold]),
        "warm_mean_best": mean([o.best_cost for o in warm]),
        "profile_cache_hits": session.cache.hits,
        "profile_cache_misses": session.cache.misses,
        "session_s": elapsed,
    }
    if check:
        assert warm and cold, "streaming scenario needs cold AND warm jobs"
        assert warm_iters < cold_iters, (
            f"warm-started searches should converge in fewer fresh trials: "
            f"warm {warm_iters} vs cold {cold_iters}"
        )
    return row


def bench_adversarial(
    n_jobs: int, check: bool, settings: BOSettings,
    *, permanent_jobs: int = 1, steps_before_churn: int = 3,
) -> dict:
    """Workload F: the paper fleet under an adversarial schedule.

    Every job's profiling runs draw Poisson-style transient failures
    (`FaultPlan(transient_rate=0.25, max_injected=3)` — bounded below the
    retry budget, so retried resolution always terminates) and 10% of
    trials are stragglers (latency reported via `TrialRecord.attempts`,
    never fed into costs).  After ``steps_before_churn`` lockstep steps,
    every 10th handle is cancelled and the session loses a device
    (`reshard` 2 → 1).  ``permanent_jobs`` jobs are additionally broken
    outright (every run raises `PermanentRunError`) — they surface as
    first-class "failed" outcomes at submit; the smoke variant passes 0.

    Completion rate is converged / (submitted − cancelled): cancellation
    is the caller's choice, but every job the scheduler was *asked* to
    finish counts — permanently failed ones included.
    """
    from repro.cluster.faults import FaultPlan
    from repro.fleet import TuningSession

    keys = [JOB_ORDER[i % len(JOB_ORDER)] for i in range(n_jobs)]
    plans = {
        k: FaultPlan(seed=i, transient_rate=0.25, max_injected=3,
                     straggler_rate=0.10)
        for i, k in enumerate(dict.fromkeys(keys))
    }
    jobs = cluster_fleet(keys, faults=plans)
    for job in jobs[len(jobs) - permanent_jobs:] if permanent_jobs else []:
        job.profile_run = FaultPlan(permanent=True).wrap_run(
            job.profile_run, job.name,
        )

    shard = 2 if jax.device_count() >= 2 else None
    session = TuningSession(settings=settings, warm_start=False, shard=shard)
    t0 = time.perf_counter()
    handles = [
        session.submit(job, seed=2000 + i) for i, job in enumerate(jobs)
    ]
    for _ in range(steps_before_churn):
        session.step()
    victims = [h for i, h in enumerate(handles) if i % 10 == 9]
    cancelled = sum(h.cancel() for h in victims)
    survivors_moved = session.reshard(shard=None)  # the shard-loss event
    outs = session.drain()
    elapsed = time.perf_counter() - t0

    by = lambda s: [o for o in outs if o.status == s]
    n_converged, n_failed = len(by("converged")), len(by("failed"))
    completion = n_converged / max(n_jobs - cancelled, 1)
    row = {
        "n_jobs": n_jobs,
        "shard": shard,
        "transient_rate": 0.25,
        "straggler_rate": 0.10,
        "cancelled": cancelled,
        "failed": n_failed,
        "converged": n_converged,
        "completion_rate": completion,
        "wasted_trials": sum(len(o.records) for o in by("cancelled")),
        "retry_attempts": sum(o.profile_attempts - 1 for o in outs),
        "retry_backoff_s": sum(o.retry_backoff_s for o in outs),
        "straggler_trials": sum(
            1 for o in outs for r in o.records if r.attempts > 1
        ),
        "reshard_survivors": survivors_moved,
        "adversarial_s": elapsed,
    }
    if check:
        assert len(outs) == n_jobs, "results() must be exactly-once"
        assert completion >= 0.95, (
            f"completion {completion:.3f} under the adversarial schedule"
        )
        assert row["retry_attempts"] > 0, "no transient faults fired"
        assert row["straggler_trials"] > 0, "no stragglers reported"
        assert cancelled == 0 or row["wasted_trials"] > 0
    return row


def _report_adversarial(r: dict) -> None:
    print(f"  F. adversarial fleet ({r['n_jobs']} jobs, shard={r['shard']}, "
          f"transients at {r['transient_rate']}, "
          f"{r['cancelled']} cancelled, {r['failed']} broken)")
    print(f"    completion {100 * r['completion_rate']:.1f}%  "
          f"wasted trials {r['wasted_trials']}  "
          f"retries +{r['retry_attempts']} attempts "
          f"(+{r['retry_backoff_s']:.1f} s backoff)  "
          f"stragglers {r['straggler_trials']}  "
          f"reshard moved {r['reshard_survivors']} rows  "
          f"({r['adversarial_s']:.2f} s)")


# Workload G's heterogeneous admission groups: three space extents →
# three distinct chunk shapes, each with its own dispatch loop under the
# async service (the lockstep session barriers them together).
_G_SPACE_NS = (24, 96, 384)


def bench_open_loop(n_jobs: int, check: bool, *, smoke: bool = False) -> dict:
    """Workload G: Poisson-arrival open-loop fleet, async vs lockstep.

    ``n_jobs`` CherryPick jobs (budget 10) cycle over the three
    `_G_SPACE_NS` spaces and arrive at pre-drawn Poisson times — the SAME
    absolute schedule for both drivers, submitted open-loop (arrivals
    never wait for completions).  Straggler stalls are a deterministic
    per-(group key, group iteration) hash draw shared by both sides:

      * async — `TuningService` with a ``pace`` hook that sleeps the
        straggling group's OWN dispatch thread; the other groups keep
        stepping (stall isolation across worker threads — default device
        placement, since the forced host devices share the same cores);
      * lockstep — a single-threaded barrier loop over `TuningSession`
        internals that admits, then steps every live chunk, sleeping
        inline once per straggling group per barrier — the stall
        semantics of `TuningSession.step()`, where the slowest group
        sets the whole fleet's pace.

    Sojourn is completion minus *scheduled* arrival, so queueing delay
    is charged to the driver; sustained jobs/sec is completions over the
    first-arrival → last-completion window.  When ``check``, the two
    drivers' outcomes must be bit-identical per job (chunk membership
    and scheduling never touch traces) and the async side must clear the
    committed throughput floor (1.3×; 1.1× in smoke, where the fleet is
    too small to amortize thread spin-up).
    """
    from repro.cluster.faults import _hash_unit
    from repro.fleet import FleetJob, TuningService, TuningSession

    budget = 10
    straggler_rate = 0.4
    straggler_delay_s = 0.08
    mean_gap_s = 0.010 if smoke else 0.005
    spaces = [synthetic_space(n) for n in _G_SPACE_NS]
    arrivals = np.cumsum(
        np.random.default_rng(4242).exponential(mean_gap_s, size=n_jobs)
    )

    def make_jobs() -> List:  # fresh objects per driver — submit may annotate
        return [
            FleetJob(
                name=f"g{i}",
                space=spaces[i % len(spaces)][0],
                cost_table=spaces[i % len(spaces)][1],
            )
            for i in range(n_jobs)
        ]

    def session_kwargs() -> dict:
        return dict(
            settings=BOSettings(max_iters=budget), mode="cherrypick",
            to_exhaustion=True, warm_start=False,
        )

    def straggles(key: tuple, iteration: int) -> bool:
        return (
            _hash_unit("workloadG", str(key), str(iteration))
            < straggler_rate
        )

    def completion_clock(session) -> dict:
        done = {}

        def listener(outcome):  # fires under the session lock — keep tiny
            done[outcome.name] = time.perf_counter()

        session._outcome_listeners.append(listener)
        return done

    def submit_at_arrivals(submit, jobs, t0: float) -> None:
        for i, (job, at) in enumerate(zip(jobs, arrivals)):
            lag = (t0 + at) - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
            submit(job, seed=3000 + i)

    def stats(done: dict, t0: float) -> dict:
        sojourns = [done[f"g{i}"] - (t0 + arrivals[i]) for i in range(n_jobs)]
        span = max(done.values()) - (t0 + arrivals[0])
        return {
            "jobs_per_sec": n_jobs / span,
            "makespan_s": span,
            "sojourn_p50_s": float(np.percentile(sojourns, 50)),
            "sojourn_p99_s": float(np.percentile(sojourns, 99)),
        }

    # Warm every lockstep program the drivers can hit: admission timing
    # decides chunk ROW extents (2..8 after single-job padding), and a
    # mid-run compile would otherwise be charged as scheduling time.
    for space, table in spaces:
        warm = TuningSession(**session_kwargs())
        for rows in range(2, _CHUNK + 1):
            for i in range(rows):
                warm.submit(
                    FleetJob(name=f"w{rows}-{i}", space=space,
                             cost_table=table),
                    seed=i,
                )
            warm.drain()

    def run_lockstep():
        session = TuningSession(**session_kwargs())
        done = completion_clock(session)
        jobs = make_jobs()
        t0 = time.perf_counter()
        feeder = threading.Thread(
            target=submit_at_arrivals, args=(session.submit, jobs, t0),
            name="g-lockstep-feeder", daemon=True,
        )
        feeder.start()
        iters: dict = {}
        while True:
            with session._lock:
                session._admit()
                chunks = list(session._chunks)
            if not chunks:
                if not feeder.is_alive():
                    with session._lock:
                        if not session._pending and not session._chunks:
                            break
                time.sleep(0.001)
                continue
            paced = set()
            for ch in chunks:
                key = ch.group_key
                if key not in paced:
                    # One straggler draw per group per barrier — identical
                    # injection law to the async pace hook, but the sleep
                    # happens on the ONLY stepping thread: every other
                    # group waits out the stall (the lockstep pathology).
                    paced.add(key)
                    iters[key] = iters.get(key, 0) + 1
                    if straggles(key, iters[key]):
                        time.sleep(straggler_delay_s)
                session._step_chunk(ch)
        feeder.join()
        outs = session.drain()
        return stats(done, t0), outs

    def run_async():
        session = TuningSession(**session_kwargs())
        done = completion_clock(session)

        def pace(key: tuple, iteration: int) -> None:
            if straggles(key, iteration):
                time.sleep(straggler_delay_s)  # stalls this group only

        # devices=None: forced host "devices" share the same CPU cores, and
        # XLA caches executables PER DEVICE — round-robin placement would
        # recompile every (space, rows) program per device and charge it
        # as scheduling time.  Stall isolation is a thread property here.
        svc = TuningService(session, pace=pace, devices=None)
        jobs = make_jobs()
        t0 = time.perf_counter()
        submit_at_arrivals(svc.submit, jobs, t0)
        outs = svc.drain()
        m = svc.metrics()
        svc.shutdown(drain=False)
        return stats(done, t0), outs, m

    lock_stats, lock_outs = run_lockstep()
    async_stats, async_outs, metrics = run_async()

    if check:
        by_lock = {o.name: o.as_dict() for o in lock_outs}
        by_async = {o.name: o.as_dict() for o in async_outs}
        assert by_lock == by_async, (
            "open-loop async outcomes diverged from the lockstep session"
        )

    speedup = async_stats["jobs_per_sec"] / lock_stats["jobs_per_sec"]
    floor = 1.1 if smoke else 1.3
    if check:
        assert speedup >= floor, (
            f"async service sustained only {speedup:.2f}x the lockstep "
            f"jobs/sec under straggler injection (floor {floor}x)"
        )
    return {
        "n_jobs": n_jobs,
        "space_ns": list(_G_SPACE_NS),
        "budget": budget,
        "mean_interarrival_s": mean_gap_s,
        "straggler_rate": straggler_rate,
        "straggler_delay_s": straggler_delay_s,
        "lockstep": lock_stats,
        "async": async_stats,
        "speedup_jobs_per_sec": speedup,
        "speedup_floor": floor,
        "traces_identical": bool(check) if check else None,
        "service_groups": len(metrics["groups"]),
        "service_jobs_per_sec": metrics["jobs_per_sec"],
    }


def _report_open_loop(r: dict) -> None:
    print(f"  G. open-loop service fleet ({r['n_jobs']} Poisson arrivals, "
          f"mean gap {1e3 * r['mean_interarrival_s']:.0f} ms, "
          f"{r['service_groups']} admission groups, stragglers at "
          f"{r['straggler_rate']} x {1e3 * r['straggler_delay_s']:.0f} ms)")
    for tag in ("lockstep", "async"):
        s = r[tag]
        print(f"    {tag:8s}: {s['jobs_per_sec']:6.2f} jobs/s  "
              f"sojourn p50 {1e3 * s['sojourn_p50_s']:7.1f} ms  "
              f"p99 {1e3 * s['sojourn_p99_s']:7.1f} ms  "
              f"(makespan {s['makespan_s']:.2f} s)")
    print(f"    sustained throughput: {r['speedup_jobs_per_sec']:.2f}x "
          f"async vs lockstep (floor {r['speedup_floor']}x, traces "
          f"{'identical' if r['traces_identical'] else 'UNCHECKED'})")


def bench_pricing(check: bool, settings: BOSettings, *, smoke: bool = False,
                  seed: int = 0) -> dict:
    """Workload H: cost-aware tuning over pricing catalogs.

    Three measurements over `repro.cluster.pricing`:

      * **Repricing movement** — for every Table I job × every catalog in
        `default_catalogs(seed)`, does the USD-optimal configuration move
        off the legacy (x86 on-demand) optimum?  Asserted ≥ 3 jobs on at
        least one catalog: if no book can move the optimum, a cost
        objective is a no-op and the whole axis is dead weight.
      * **Objective contrast** — the `spot_volatility_scenarios` fleets
        (priced `cluster_fleet` jobs, per spot epoch) searched twice
        through `TuningSession`, once per objective.  Reports the USD the
        cost objective saves over the runtime objective's pick (summed;
        asserted ≥ 0 with ≥ 1 job where the two objectives choose
        different configurations) and asserts the Pareto-front invariants
        on every cost-run outcome (non-empty, mutually non-dominated,
        deterministic, contains the per-axis argmins).
      * **Family-constrained optima** — the `family_constrained_scenarios`
        Graviton searches evaluated at table level: the USD penalty of
        pinning each job to one instance family vs the whole grid.
    """
    from repro.cluster import (
        JOBS, default_catalogs, family_indices, job_cost_table,
    )
    from repro.cluster.workloads import (
        family_constrained_scenarios, spot_volatility_scenarios,
    )
    from repro.fleet import TuningSession

    t0 = time.perf_counter()

    # -- repricing movement (table-level; cheap enough to always run full)
    legacy_arg = {k: int(np.argmin(job_cost_table(j))) for k, j in JOBS.items()}
    argmin_moved = {}
    for cat in default_catalogs(seed).values():
        argmin_moved[cat.name] = sum(
            int(np.argmin(job_cost_table(j, catalog=cat))) != legacy_arg[k]
            for k, j in JOBS.items()
        )

    # -- objective contrast over the spot-volatility fleets
    scens = spot_volatility_scenarios(seed=seed)
    if smoke:
        first_epoch = scens[0].epoch
        scens = [s for s in scens if s.epoch == first_epoch]
    by_epoch: dict = {}
    for s in scens:
        by_epoch.setdefault(s.epoch, []).append(s)

    job_rows = []
    pareto_max = 0
    for epoch, group in sorted(by_epoch.items()):
        catalog = group[0].catalog
        keys = [s.job_key for s in group]
        jobs = cluster_fleet(keys, catalog=catalog, epoch=epoch)
        outs = {}
        for objective in ("runtime", "cost"):
            session = TuningSession(
                settings=settings, warm_start=False, objective=objective,
            )
            for i, job in enumerate(jobs):
                session.submit(job, seed=7000 + 100 * epoch + i)
            outs[objective] = session.drain()
        for s, o_rt, o_cost in zip(group, outs["runtime"], outs["cost"]):
            rt_pick = min(o_rt.observations, key=lambda r: r.cost)
            cost_pick = min(o_cost.observations, key=lambda r: r.cost)
            front = o_cost.pareto()
            pareto_max = max(pareto_max, len(front))
            if check:
                assert front, f"{s.name}: empty Pareto front"
                assert o_cost.pareto() == front, (
                    f"{s.name}: pareto() is not deterministic"
                )
                for i, a in enumerate(front):
                    for j, b in enumerate(front):
                        if i != j:
                            assert not (
                                b.runtime_h <= a.runtime_h and b.usd <= a.usd
                                and (b.runtime_h < a.runtime_h or b.usd < a.usd)
                            ), f"{s.name}: front member {i} is dominated"
                assert any(r.usd == o_cost.best_usd for r in front)
                assert any(
                    r.runtime_h == o_cost.best_runtime_h for r in front
                )
                # The cost search's own pick IS its cheapest observation.
                assert cost_pick.usd == o_cost.best_usd
            job_rows.append({
                "scenario": s.name,
                "epoch": epoch,
                "runtime_pick": int(rt_pick.index),
                "cost_pick": int(cost_pick.index),
                "usd_at_runtime_pick": float(rt_pick.usd),
                "usd_at_cost_pick": float(cost_pick.usd),
                "usd_saved": float(rt_pick.usd - cost_pick.usd),
                "pareto_size": len(front),
            })

    usd_rt = sum(r["usd_at_runtime_pick"] for r in job_rows)
    usd_cost = sum(r["usd_at_cost_pick"] for r in job_rows)
    contrast = sum(r["runtime_pick"] != r["cost_pick"] for r in job_rows)

    # -- family-constrained Graviton optima (table-level)
    fam_rows = []
    for s in family_constrained_scenarios():
        usd = job_cost_table(JOBS[s.job_key], catalog=s.catalog, epoch=s.epoch)
        idx = family_indices(s.families)
        fam_rows.append({
            "scenario": s.name,
            "families": list(s.families),
            "in_family_usd": float(usd[idx].min()),
            "global_usd": float(usd.min()),
            "family_penalty": float(usd[idx].min() / usd.min()),
        })

    row = {
        "seed": seed,
        "n_scenarios": len(scens) + len(fam_rows),
        "argmin_moved": argmin_moved,
        "jobs": job_rows,
        "usd_runtime_total": usd_rt,
        "usd_cost_total": usd_cost,
        "usd_saved_total": usd_rt - usd_cost,
        "contrast_jobs": int(contrast),
        "pareto_max_size": pareto_max,
        "family": fam_rows,
        "pricing_s": time.perf_counter() - t0,
    }
    if check:
        assert max(argmin_moved.values()) >= 3, (
            f"no catalog moves >= 3 Table I optima: {argmin_moved}"
        )
        assert row["usd_saved_total"] >= 0.0, (
            f"cost objective spent MORE than runtime's pick: {row}"
        )
        assert contrast >= 1, (
            "runtime and cost objectives picked identical configs on every "
            "catalog job — no contrast to measure"
        )
        for f in fam_rows:
            assert f["family_penalty"] >= 1.0 - 1e-12
    return row


def _report_pricing(r: dict) -> None:
    moved = ", ".join(f"{k}:{v}" for k, v in r["argmin_moved"].items())
    print(f"  H. cost-aware pricing ({len(r['jobs'])} priced searches x 2 "
          f"objectives, {len(r['family'])} family scenarios)")
    print(f"    Table I USD-argmin moved per catalog: {moved}")
    print(f"    cost objective saves {r['usd_saved_total']:.2f} USD over the "
          f"runtime picks ({r['usd_runtime_total']:.2f} -> "
          f"{r['usd_cost_total']:.2f}; {r['contrast_jobs']} jobs diverge, "
          f"Pareto fronts <= {r['pareto_max_size']} trials)  "
          f"({r['pricing_s']:.2f} s)")


def bench_paper_replay(jobs, check: bool, settings: BOSettings) -> dict:
    """Workload A: full two-phase Ruya search over the 69-config space."""
    n_jobs = len(jobs)
    warm = jobs[: min(2, n_jobs)]
    tune_fleet(warm, _rngs(len(warm)), settings=settings, to_exhaustion=True,
               engine="sequential")
    tune_fleet(jobs, _rngs(n_jobs), settings=settings, to_exhaustion=True)

    t0 = time.perf_counter()
    seq = tune_fleet(jobs, _rngs(n_jobs), settings=settings,
                     to_exhaustion=True, engine="sequential")
    t_seq = time.perf_counter() - t0
    t0 = time.perf_counter()
    bat = tune_fleet(jobs, _rngs(n_jobs), settings=settings,
                     to_exhaustion=True)
    t_bat = time.perf_counter() - t0

    if check:
        for r_s, r_b in zip(seq, bat):
            assert r_s.trace.tried == r_b.trace.tried, "engines diverged"
            assert r_s.trace.stop_iteration == r_b.trace.stop_iteration
            assert r_s.trace.phase_boundary == r_b.trace.phase_boundary
    trials = sum(len(r.trace.tried) for r in bat)
    return {"sequential_s": t_seq, "batched_s": t_bat,
            "speedup": t_seq / t_bat, "total_trials": trials}


def service_fleet_spaces(
    jobs, n_jobs: int
) -> Tuple[List[SearchSpace], List[np.ndarray]]:
    """The priority-only service workload's (spaces, tables): recurring
    flat-memory jobs searched within their memory-derived priority groups
    (~10 configs each) — shared by workload B and the `--shards` sweep."""
    from repro.core.memory_model import MemoryCategory

    flat = [
        job for job in jobs
        if job.profile_result.model.category is MemoryCategory.FLAT
    ]
    if not flat:
        # Small --jobs prefixes of JOB_ORDER may hold no flat job; pull the
        # recurring flat specs from the catalog directly.
        flat = build_fleet(len(JOB_ORDER))
        flat = [
            job for job in flat
            if job.profile_result.model.category is MemoryCategory.FLAT
        ]
    spaces: List[SearchSpace] = []
    tables: List[np.ndarray] = []
    for i in range(n_jobs):
        job = flat[i % len(flat)]
        prio, _ = split_search_space(
            job.space, job.profile_result.model, job.full_input_size,
            per_node_overhead=job.per_node_overhead,
        )
        spaces.append(SearchSpace([job.space.configs[k] for k in prio]))
        tables.append(np.asarray(job.cost_table)[np.asarray(prio, np.int64)])
    return spaces, tables


def bench_priority_service(jobs, check: bool, settings: BOSettings,
                           n_jobs: int) -> dict:
    """Workload B: recurring jobs re-tuned within their priority group only.

    The service scenario: the recurring flat-memory jobs (terasort, join,
    Hadoop pagerank — the ETL-style workloads a cluster re-tunes routinely)
    searched inside their ~10-config priority groups, ``n_jobs`` runs total.
    Unclear jobs have no priority group and linear jobs' groups vary per
    job; the flat fleet is the uniform, dispatch-bound service case.
    """
    spaces, tables = service_fleet_spaces(jobs, n_jobs)

    cost_fns = [lambda i, t=t: float(t[i]) for t in tables]
    # Warm both paths, covering every distinct space shape the sequential
    # engine will compile for.
    seen = set()
    for space, fn in zip(spaces, cost_fns):
        if space.encoded().shape not in seen:
            seen.add(space.encoded().shape)
            cherrypick_search(space, fn, np.random.default_rng(0),
                              settings=settings, to_exhaustion=True)
    batched_search(spaces, tables, _rngs(n_jobs), settings=settings,
                   to_exhaustion=True)

    t0 = time.perf_counter()
    seq = [
        cherrypick_search(space, fn, rng, settings=settings,
                          to_exhaustion=True)
        for space, fn, rng in zip(spaces, cost_fns, _rngs(n_jobs))
    ]
    t_seq = time.perf_counter() - t0
    t0 = time.perf_counter()
    bat = batched_search(spaces, tables, _rngs(n_jobs), settings=settings,
                         to_exhaustion=True)
    t_bat = time.perf_counter() - t0

    if check:
        for j, ref in enumerate(seq):
            tr = bat.job_trace(j)
            assert tr.tried == ref.tried, "engines diverged"
            assert tr.stop_iteration == ref.stop_iteration
    trials = sum(len(t.tried) for t in seq)
    return {"sequential_s": t_seq, "batched_s": t_bat,
            "speedup": t_seq / t_bat, "total_trials": trials,
            "n_jobs": n_jobs,
            "mean_space": float(np.mean([len(s) for s in spaces]))}


def bench_sharded(
    spaces: Sequence[SearchSpace], tables: Sequence[np.ndarray],
    check: bool, settings: BOSettings, shards: Sequence[int],
    reps: int = 3, workload: str = "priority_service",
) -> dict:
    """The ``--shards`` axis: the batched engine with the job axis sharded
    across devices vs the single-device lockstep reference, same fleet.

    Best-of-``reps`` wall clock on both sides (this host wobbles ±2×, and
    the quantity of interest — dispatch+execute throughput at a fixed
    array program — is the minimum, not the mean).  Sharded traces are
    asserted bit-identical to the unsharded run when ``check``; shard
    counts above the visible device count are recorded as skipped rather
    than silently run unsharded.
    """
    n_jobs = len(tables)

    def run_once(shard):
        t0 = time.perf_counter()
        bt = batched_search(
            spaces, tables, _rngs(n_jobs), settings=settings,
            to_exhaustion=True, shard=shard,
        )
        return time.perf_counter() - t0, bt

    run_once(None)  # compile warm-up
    t_un = float("inf")
    for _ in range(reps):
        t, ref = run_once(None)
        t_un = min(t_un, t)

    rows = []
    for s in shards:
        if s < 2 or s > jax.device_count():
            rows.append({
                "shards": s, "skipped":
                f"{jax.device_count()} device(s) visible; want ≥ {max(s, 2)}",
            })
            continue
        run_once(s)  # compile warm-up for the sharded programs
        t_s = float("inf")
        for _ in range(reps):
            t, bt = run_once(s)
            t_s = min(t_s, t)
        identical = None  # null = unchecked (--no-check), like the sweep
        if check:
            identical = all(
                bt.job_trace(j).tried == ref.job_trace(j).tried
                and bt.job_trace(j).costs == ref.job_trace(j).costs
                and bt.job_trace(j).stop_iteration
                == ref.job_trace(j).stop_iteration
                for j in range(n_jobs)
            )
            assert identical, f"sharded (S={s}) traces diverged from lockstep"
        rows.append({
            "shards": s,
            "batched_s": t_s,
            "speedup_vs_unsharded": t_un / t_s,
            "traces_identical": identical,
        })
    return {
        "workload": workload,
        "n_jobs": n_jobs,
        "devices_visible": jax.device_count(),
        "reps_best_of": reps,
        "unsharded_s": t_un,
        "shards": rows,
    }


def _report_sharded(r: dict) -> None:
    print(f"  --shards axis ({r['workload']}, {r['n_jobs']} jobs, "
          f"{r['devices_visible']} devices, best of {r['reps_best_of']}): "
          f"unsharded {r['unsharded_s']:.3f} s")
    for row in r["shards"]:
        if "skipped" in row:
            print(f"    S={row['shards']}: skipped ({row['skipped']})")
        else:
            print(f"    S={row['shards']}: {row['batched_s']:.3f} s  "
                  f"({row['speedup_vs_unsharded']:.2f}x vs unsharded, "
                  f"traces {'identical' if row['traces_identical'] else 'UNCHECKED'})")


def _report(tag: str, r: dict) -> None:
    print(f"  {tag}")
    print(f"    sequential engine : {r['sequential_s']:7.2f} s  "
          f"({1e3 * r['sequential_s'] / r['total_trials']:.2f} ms/trial)")
    print(f"    batched engine    : {r['batched_s']:7.2f} s  "
          f"({1e3 * r['batched_s'] / r['total_trials']:.2f} ms/trial)")
    print(f"    speedup           : {r['speedup']:7.2f}x")


def _report_session(r: dict) -> None:
    print(f"  D. streaming session ({r['n_jobs']} jobs in {r['waves']} waves,"
          f" {r['warm_jobs']} warm-started, "
          f"{r['profile_cache_hits']}/{r['profile_cache_hits'] + r['profile_cache_misses']}"
          f" probe-cache hits)")
    red = r["fresh_trial_reduction"]
    print(f"    fresh trials to convergence: cold "
          f"{r['cold_mean_fresh_trials']:.1f} vs warm "
          f"{r['warm_mean_fresh_trials']:.1f} "
          f"({f'{red:.1f}x fewer' if red is not None else 'fully amortized'})")
    print(f"    end-to-end: {r['session_s']:.2f} s "
          f"({r['warm_seeded_trials']} trials seeded from class history)")


def run(n_jobs: int = 64, check: bool = True,
        settings: BOSettings = BOSettings(), *, smoke: bool = False,
        scaling_ns: Sequence[int] = (69, 256, 512, 1024, 8192, 32768),
        scaling_step_only_ns: Sequence[int] = (131072,),
        budget: int = 24, json_path: Optional[str] = None,
        session_only: bool = False, shards: Sequence[int] = (2,)) -> dict:
    # The repo-root BENCH_fleet.json is the committed perf baseline; only
    # the full default protocol (64 jobs, full sweep) may rewrite it —
    # smoke or reduced-job runs would replace it with non-comparable
    # numbers.  Pass json_path explicitly to write elsewhere.
    if json_path is None and not smoke and n_jobs == 64:
        json_path = BENCH_JSON
    packed_reps, dense_reps = 20, 2
    if smoke:
        # Seconds-scale wiring check: tiny fleet, one small sweep point
        # plus the n=32768 feature-buffer point (seconds — nothing of
        # extent n² exists on that path), no cluster workloads (their
        # profiling + jit warm dominates).
        n_jobs = min(n_jobs, 8)
        scaling_ns = (64, 32768)
        scaling_step_only_ns = ()
        budget = 8
        packed_reps, dense_reps = 5, 1

    print(f"\n== Fleet bench: {n_jobs} jobs, traces "
          f"{'verified identical' if check else 'unchecked'}"
          f"{', SMOKE mode' if smoke else ''}"
          f"{', SESSION scenario only' if session_only else ''} ==")

    if session_only:
        d = bench_session_streaming(n_jobs, waves=8, check=check)
        _report_session(d)
        return {"n_jobs": n_jobs, "smoke": False,
                "session_streaming": d}

    donation = check_buffer_donation()
    print("  donation: lockstep state buffers consumed in place "
          f"({', '.join(donation['buffers_checked'])})")

    c = bench_scaling(scaling_ns, n_jobs, budget, check,
                      packed_reps=packed_reps, dense_reps=dense_reps,
                      step_only_ns=scaling_step_only_ns)

    out = {"n_jobs": n_jobs, "traces_identical": bool(check),
           "smoke": bool(smoke), "donation": donation, "scaling": c,
           "peak_rss_mb": _peak_rss_mb()}
    print(f"  peak RSS over the whole run: {out['peak_rss_mb']:.0f} MB")

    if smoke:
        # Sharded-lane wiring check: an 8-job synthetic service-like fleet
        # (10-config spaces, exhaustion) across the requested shard counts,
        # traces verified against the lockstep reference.
        sp_s, tb_s = synthetic_space(10)
        sh = bench_sharded([sp_s] * 8, [tb_s] * 8, check, BOSettings(),
                           shards, reps=2, workload="synthetic_service")
        _report_sharded(sh)
        out["sharding"] = sh
        # Streaming-session wiring check: 16 recurring jobs in 4 waves at a
        # reduced trial budget (small packed capacity → seconds of compile);
        # the warm-vs-cold convergence assertion still runs.
        d = bench_session_streaming(
            16, waves=4, check=check, settings=BOSettings(max_iters=16),
        )
        _report_session(d)
        out["session_streaming"] = d
        # Adversarial-fleet wiring check: 16 disturbed jobs, no broken one
        # (the permanent-failure path is tier-1 chaos-tested; at this fleet
        # size one broken job would drag completion below the ≥95% bar the
        # full protocol is held to).
        adv = bench_adversarial(
            16, check, BOSettings(max_iters=16), permanent_jobs=0,
        )
        _report_adversarial(adv)
        out["adversarial"] = adv
        # Open-loop wiring check: 12 Poisson arrivals over the three
        # admission groups — big enough for every group to live, small
        # enough to stay seconds-scale; the ≥1.1x smoke floor still holds
        # because straggler stalls dominate both drivers' wall clock.
        g = bench_open_loop(12, check, smoke=True)
        _report_open_loop(g)
        out["open_loop"] = g
        # Cost-aware pricing wiring check: one spot epoch (3 priced jobs x
        # 2 objectives) at the smoke trial budget; the table-level
        # repricing-movement and family scenarios always run in full
        # (they are argmin sweeps, not searches).
        h = bench_pricing(check, BOSettings(max_iters=16), smoke=True)
        _report_pricing(h)
        out["pricing"] = h

    if not smoke:
        jobs = build_fleet(n_jobs)
        b = bench_priority_service(jobs, check, settings, n_jobs)
        _report(f"B. priority-only service fleet ({b['n_jobs']} recurring jobs,"
                f" ~{b['mean_space']:.0f}-config spaces, {b['total_trials']} trials)", b)
        # The --shards axis on the same service fleet: the 64-job
        # dispatch-bound workload is exactly where job-axis sharding must
        # pay (target: ≥ 1.5× at 2 shards on the 2-core container).
        sp_b, tb_b = service_fleet_spaces(jobs, n_jobs)
        sh = bench_sharded(sp_b, tb_b, check, settings, shards)
        _report_sharded(sh)
        out["sharding"] = sh
        a = bench_paper_replay(jobs, check, settings)
        _report(f"A. paper replay, two-phase over 69 configs "
                f"({a['total_trials']} trials)", a)
        print("    (A runs to exhaustion, so its packed capacity equals the"
              " space extent\n     — the dense-regime floor; the scaling sweep"
              " C is the budgeted B << n\n     regime the packed engine"
              " targets.)")
        # Workload D: the full streaming scenario — 64 jobs in 8 waves of
        # the recurring paper workloads, natural EI stopping (the packed
        # capacity matches workload A's, so the lockstep compile is shared).
        d = bench_session_streaming(n_jobs, waves=8, check=check)
        _report_session(d)
        # Workload F: the same fleet size under the adversarial schedule,
        # including one permanently broken job.
        adv = bench_adversarial(n_jobs, check, settings)
        _report_adversarial(adv)
        # Workload G: the open-loop Poisson fleet, async service vs
        # lockstep session under straggler injection (≥1.3x floor).
        g = bench_open_loop(n_jobs, check)
        _report_open_loop(g)
        # Workload H: cost-aware tuning over the pricing catalogs — all
        # spot epochs, both objectives, Pareto invariants asserted.
        h = bench_pricing(check, settings)
        _report_pricing(h)
        out.update({"paper_replay": a, "priority_service": b,
                    "session_streaming": d, "adversarial": adv,
                    "open_loop": g, "pricing": h})
        with open(artifact_path("fleet", f"fleet_bench_{n_jobs}.json"), "w") as f:
            json.dump(out, f, indent=1)

    if json_path:
        with open(json_path, "w") as f:
            json.dump(out, f, indent=1)
        print(f"  wrote {json_path}")
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--jobs", type=int, default=64)
    ap.add_argument("--no-check", action="store_true",
                    help="skip the trace-equivalence assertion")
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale wiring check (tiny fleet, two sweep points)")
    ap.add_argument("--session", action="store_true",
                    help="run ONLY the streaming TuningSession scenario "
                         "(jobs arriving in 8 waves, warm-start amortization)")
    ap.add_argument("--shards", type=int, nargs="*", default=[2],
                    help="shard counts for the job-axis sharding sweep on "
                         "the service fleet (default: 2)")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    run(args.jobs, check=not args.no_check, smoke=args.smoke,
        session_only=args.session, shards=tuple(args.shards))
