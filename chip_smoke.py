#!/usr/bin/env python3
"""Smoke run of the tuning service's main path on TPU chips.

    python chip_smoke.py             # one chip: phases (a)-(c)
    python chip_smoke.py --chips 4   # four chips: the job-axis-sharded
                                     # catalog fleet and a mid-flight reshard

The main path is `TuningService` → `TuningSession.submit` (profiling, the
§III-D split) → the jitted lockstep chunk update (`fast_bo.fleet_step`: the
GP head plus the EI/argmax tail).  With no option every phase is pinned to
the first chip:

  (a) paper fleet — the 16 Table I jobs over the 69-config grid, submitted
      in Ruya mode with the default layout.  Every job must converge with a
      finite last max EI and a valid best config.
  (b) catalog — 64 CherryPick jobs over an n = 32768, d = 6 catalog at
      B = 24, drained with layout="feature" and with layout="fused"; the
      compiled fused chunk update must hold the Pallas EI/argmax kernel
      (``tpu_custom_call``).
  (c) agreement — search states sampled from (a) and (b) are stepped on the
      chip (both layouts) and by the unfused `bo_step_core` on the host's
      CPU backend, the reference.  The chip's pick must reach
      (1 − PICK_RTOL) × the reference's max EI and the max EIs must agree
      within EI_RTOL (both less an absolute floor of EI_ATOL × the best
      observed cost).  Where several hyperparameter grid points of the GP
      head tie in float64 marginal likelihood, agreeing with the reference
      at any one of them counts.  The §III-D masks the session used must equal the
      host rule `split_search_space`.  How many Table I jobs end on the
      same config as a CPU-backend run is printed, not checked.

``--chips 4`` runs only the catalog fleet at ``shard=4`` against the same
fleet unsharded on one of the four chips, and a run resharded to 2 devices
mid-flight against the undisturbed one.

Each drain runs twice: the first pays the compiles ("cold"), the second
reuses them ("warm"), so compile ≈ cold − warm.  The last line of standard
output is one JSON object naming the device; the script exits non-zero,
without that line, when JAX finds no TPU or any check fails.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmarks.common import JOB_ORDER  # noqa: E402
from repro.compile_cache import enable_compile_cache  # noqa: E402
from repro.core.bayesopt import BOSettings, trial_budget  # noqa: E402
from repro.core.fast_bo import (  # noqa: E402
    _JITTER,
    _LENGTHSCALES,
    _NOISES,
    FleetState,
    _packed_head,
    bo_step_core,
    bo_step_core_fused,
    encode_features,
    packed_sqdist_blocks,
)
from repro.kernels.ei_argmax import ei_from_sqdist  # noqa: E402
from repro.core.search_space import (  # noqa: E402
    Configuration,
    SearchSpace,
    split_search_space,
)
from repro.fleet import FleetJob, TuningService, TuningSession, cluster_fleet  # noqa: E402
from repro.fleet.batched_engine import _fleet_update  # noqa: E402

CATALOG_N, CATALOG_D, CATALOG_B, CATALOG_JOBS = 32768, 6, 24, 64
# Agreement of the chip with the CPU reference (phase (c)).  EI values are
# decided against ei_stop_rel × best = 0.1 × best, so an absolute floor of
# 1e-5 × best sits four orders of magnitude below any decision.
EI_RTOL = 1e-3
EI_ATOL = 1e-5
PICK_RTOL = 1e-3
# The GP head picks its (lengthscale, noise) by an argmax over 18 float32
# marginal likelihoods.  Grid points whose float64 LMLs lie within LML_TIE
# nats of the best are a tie that float32 rounding breaks, differently per
# backend (seen on v5e: 1.46e-5 nats between lengthscales 0.1 and 0.25 at
# three observations, a 1.3 % max-EI difference); the chip may agree with
# the reference at any of them.
LML_TIE = 1e-4
SAMPLES_PER_JOB = 3


class _LastEISession(TuningSession):
    """`TuningSession` that keeps each job's last max EI at retirement
    (the engine row's ``last_ei``, which outcomes do not carry)."""

    def __init__(self, **kwargs) -> None:
        super().__init__(**kwargs)
        self.last_ei: dict = {}

    def _retire(self, ch) -> None:
        rows = np.asarray(ch.state.last_ei).reshape(-1)
        for i, rec in enumerate(ch.members):
            if rec is not None:
                self.last_ei[rec.handle.uid] = float(rows[i])
        super()._retire(ch)


def _drain(jobs, *, devices=None, pace=None, **session_kwargs):
    """Submit ``jobs`` (seed = position) to a fresh, paused service, so the
    whole fleet is admitted at once, then drain it.  ``pace(session, key,
    iteration)`` runs on the group's worker before each of its iterations.
    Returns (outcomes in submission order, last max EI per job, seconds)."""
    session = _LastEISession(**session_kwargs)
    svc = TuningService(
        session, devices=devices,
        pace=None if pace is None else (lambda k, i: pace(session, k, i)),
    )
    t0 = time.perf_counter()
    try:
        svc.pause()
        handles = [svc.submit(job, seed=i) for i, job in enumerate(jobs)]
        svc.drain()
    finally:
        svc.shutdown(drain=False)
    dt = time.perf_counter() - t0
    outs = [h.outcome() for h in handles]
    return outs, [session.last_ei[h.uid] for h in handles], dt


def _check_outcomes(tag, jobs, outs, last_ei):
    """Every job converged with a finite last max EI; its trials are
    distinct configs of its space observed at their table costs."""
    for job, out, ei in zip(jobs, outs, last_ei):
        if out.status != "converged":
            raise AssertionError(f"{tag}: {out.name} ended {out.status!r}")
        if not np.isfinite(ei):
            raise AssertionError(f"{tag}: {out.name} last max EI {ei}")
        idx = [r.index for r in out.observations]
        if len(set(idx)) != len(idx) or not all(
                0 <= i < len(job.space) for i in idx):
            raise AssertionError(f"{tag}: {out.name} trials {idx}")
        if any(r.cost != float(job.cost_table[r.index]) for r in out.records):
            raise AssertionError(f"{tag}: {out.name} cost off its table")
        if not 0 <= out.best_index < len(job.space):
            raise AssertionError(f"{tag}: {out.name} best {out.best_index}")


def _twice(tag, make_jobs, **kwargs):
    """Cold then warm drain of the same fleet; both checked."""
    runs = []
    for label in ("cold", "warm"):
        jobs = make_jobs()
        outs, last_ei, dt = _drain(jobs, **kwargs)
        _check_outcomes(f"{tag} {label}", jobs, outs, last_ei)
        runs.append((jobs, outs, dt))
    (jobs, outs, cold), (_, outs2, warm) = runs
    same = sum(a.as_dict() == b.as_dict() for a, b in zip(outs, outs2))
    print(f"{tag}: cold {cold:.3f} s, warm drain {warm:.3f} s, compile ≈ "
          f"{cold - warm:.3f} s; {len(outs)} jobs converged; "
          f"cold/warm outcomes identical {same}/{len(outs)}")
    return jobs, outs


def synthetic_space(n: int, d: int = 6, seed: int = 7):
    """An n-config space with random features and a smooth cost surface:
    (space, (n,) cost table)."""
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


def catalog_jobs():
    """CATALOG_JOBS CherryPick searches over one synthetic catalog."""
    space, table = synthetic_space(CATALOG_N, CATALOG_D)
    return [FleetJob(name=f"c{i}", space=space, cost_table=table)
            for i in range(CATALOG_JOBS)]


def catalog_kwargs(layout):
    return dict(settings=BOSettings(max_iters=CATALOG_B), mode="cherrypick",
                to_exhaustion=True, warm_start=False, layout=layout)


def fused_update_text(dev, n, d, b, rows=8, n_init=3):
    """(lowered, compiled) text of the fused chunk update for ``dev``."""
    one = jax.sharding.SingleDeviceSharding(dev)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    state = FleetState(
        obs=s((rows, n), bool), tried=s((rows, b), jnp.int32),
        py=s((rows, b), jnp.float32), feats=s((rows, b, d), jnp.float32),
        t=s((rows,), jnp.int32), stop=s((rows,), jnp.int32),
        pb=s((rows,), jnp.int32), done=s((rows,), bool),
        last_ei=s((rows,), jnp.float32), last_best=s((rows,), jnp.float32),
    )
    args = (
        s((rows, n, d), jnp.float32), s((rows, n), jnp.float32),
        s((rows, n), bool), s((rows, n), bool), s((rows, n_init), jnp.int32),
        s((rows,), jnp.int32), s((rows,), jnp.int32), s((), jnp.int32),
        s((), jnp.float32), s((), bool),
    )
    lowered = _fleet_update.lower(state, *args, xi=0.0, layout="fused")
    return lowered.as_text(), lowered.compile().as_text()


# ----------------------------------------------------------- phase (c)

_step = jax.jit(bo_step_core, static_argnames=("xi",))
_step_fused = jax.jit(bo_step_core_fused, static_argnames=("xi",))


@functools.partial(jax.jit, static_argnames=("grid",))
def _step_at(enc, feats, tried, py, t, seen, cand, *, grid):
    """`bo_step_core` with the GP head's hyperparameter grid cut to
    ``grid`` = ((lengthscale,), (noise,))."""
    d2_bb, d2_bn = packed_sqdist_blocks(feats, enc, tried)
    pm, best, ls, chol, alpha, y_mean, y_std = _packed_head(d2_bb, py, t, *grid)
    ei = ei_from_sqdist(d2_bn, pm[:, None], alpha, chol, ls, y_mean, y_std,
                        best, cand & ~seen)
    return jnp.argmax(ei), jnp.max(ei), best


def tied_grid(feats, py, t):
    """The head's (lengthscale, noise) grid points whose float64 log
    marginal likelihood lies within LML_TIE nats of the best one."""
    x = feats[:t].astype(np.float64)
    y = py[:t].astype(np.float64)
    y = (y - y.mean()) / max(y.std(), 1e-8)
    r5 = np.sqrt(5.0 * ((x[:, None] - x[None]) ** 2).sum(-1))
    lml = {}
    for ls in _LENGTHSCALES:
        k = (1.0 + r5 / ls + (r5 / ls) ** 2 / 3.0) * np.exp(-r5 / ls)
        for noise in _NOISES:
            chol = np.linalg.cholesky(k + (noise + _JITTER) * np.eye(t))
            a = np.linalg.solve(chol, y)
            lml[(ls,), (noise,)] = -0.5 * a @ a - np.log(np.diag(chol)).sum()
    top = max(lml.values())
    return [g for g, v in lml.items() if v >= top - LML_TIE]


def sample_states(jobs, outs, budget_of, per_job, rng):
    """Packed search states the engine stepped through: the first k
    observations of each sampled job, for ``per_job`` values of k."""
    states = []
    for job, out in zip(jobs, outs):
        obs = out.observations
        lo = min(3, len(obs) - 1)
        if len(obs) - lo < 1:
            continue
        ks = rng.choice(np.arange(lo, len(obs)), min(per_job, len(obs) - lo),
                        replace=False)
        enc = encode_features(job.space.encoded())
        n, d = enc.shape
        b = budget_of(out)
        prio = np.zeros(n, bool)
        prio[list(out.priority)] = True
        rem = np.zeros(n, bool)
        rem[list(out.remaining)] = True
        for k in sorted(int(k) for k in ks):
            idx = np.asarray([r.index for r in obs[:k]], np.int64)
            tried = np.full(b, -1, np.int32)
            tried[:k] = idx
            py = np.zeros(b, np.float32)
            py[:k] = [r.cost for r in obs[:k]]
            feats = np.zeros((b, d), np.float32)
            feats[:k] = enc[idx]
            seen = np.zeros(n, bool)
            seen[idx] = True
            cand = prio & ~seen if (prio & ~seen).any() else rem & ~seen
            states.append((f"{out.name}@{k}", (enc, feats, tried, py,
                                               np.int32(k), seen, cand)))
    return states


def _disagreement(chip, ref, at_pick):
    """Why the chip's (pick, max EI, best) fails the reference's
    (pick, max EI, best) — None if it does not — and the relative max-EI
    error.  ``at_pick`` is the reference's EI at the chip's pick."""
    (_, max_ei, best), (_, ref_max, ref_best) = chip, ref
    floor = EI_ATOL * abs(ref_best)
    err = abs(max_ei - ref_max)
    rel = err / max(abs(ref_max), floor, 1e-30)
    if not np.isfinite(max_ei) or best != ref_best:
        return f"max EI {max_ei!r}, best {best!r} vs {ref_best!r}", rel
    if err > EI_RTOL * abs(ref_max) + floor:
        return (f"max EI {max_ei!r} vs reference {ref_max!r} (rtol "
                f"{EI_RTOL}, atol {floor:.3g})"), rel
    if at_pick < (1.0 - PICK_RTOL) * ref_max - floor:
        return (f"pick {chip[0]} has reference EI {at_pick!r} < "
                f"(1 - {PICK_RTOL}) x {ref_max!r}"), rel
    return None, rel


def check_agreement(tag, states, dev, cpu):
    """Step every state on ``dev`` (feature and fused lanes) and by the
    unfused reference on ``cpu``; raise, after reporting every
    disagreement, if any.  Where the float64 marginal likelihoods of
    several hyperparameter grid points tie within LML_TIE (`tied_grid`),
    the chip may agree with the reference at any of them."""
    worst = {"feature": 0.0, "fused": 0.0}
    same_pick = {"feature": 0, "fused": 0}
    ties = {"feature": 0, "fused": 0}
    failures = []
    for name, st in states:
        ref_in = jax.device_put(st, cpu)
        refs = [(None, lambda *a: _step(*a, xi=0.0))]
        grid = tied_grid(st[1], st[3], int(st[4]))
        if len(grid) > 1:
            refs += [(g, functools.partial(_step_at, grid=g)) for g in grid]
        chip_in = jax.device_put(st, dev)
        for lane, fn in (("feature", _step), ("fused", _step_fused)):
            pick, max_ei, best = (np.asarray(v) for v in fn(*chip_in, xi=0.0))
            chip = (int(pick), float(max_ei), float(best))
            one = np.zeros_like(st[-1])
            one[chip[0]] = True
            one = jax.device_put(one, cpu)
            verdicts = []
            for g, ref_fn in refs:
                ref = tuple(float(v) for v in ref_fn(*ref_in))
                at_pick = float(ref_fn(*ref_in[:-1], one)[1])
                why, rel = _disagreement(chip, ref, at_pick)
                verdicts.append((why, rel, g, ref))
                if why is None:
                    break
            why, rel, g, ref = verdicts[-1]
            if why is not None:
                why, rel, _, ref = verdicts[0]
                failures.append(f"{tag} {lane} {name}: {why}; tied grid "
                                f"points {grid}")
            ties[lane] += g is not None and why is None
            worst[lane] = max(worst[lane], rel)
            same_pick[lane] += chip[0] == int(ref[0])
    for lane in worst:
        print(f"{tag} agreement, {lane} lane: {len(states)} states, same "
              f"pick {same_pick[lane]}/{len(states)}, worst max-EI error "
              f"{worst[lane]:.3e} of the reference (limit {EI_RTOL} + "
              f"{EI_ATOL} x best); {ties[lane]} states agree at another "
              f"grid point of a float64 LML tie (within {LML_TIE} nats)")
    for line in failures:
        print(line)
    if failures:
        raise AssertionError(f"{tag}: {len(failures)} disagreements with "
                             "the CPU reference")


# ------------------------------------------------------------- drivers


def one_chip(dev, cpu) -> None:
    rng = np.random.default_rng(0)

    # (a) paper fleet, Ruya mode, default layout.
    jobs, outs = _twice("(a) paper fleet", lambda: cluster_fleet(JOB_ORDER),
                        devices=[dev])
    for job, out in zip(jobs, outs):
        prio, rest = split_search_space(
            job.space, out.profile.model, job.full_input_size,
            per_node_overhead=job.per_node_overhead, leeway=job.leeway,
            flat_fraction=job.flat_fraction,
        )
        if out.priority != tuple(prio) or out.remaining != tuple(rest):
            raise AssertionError(f"(a) {out.name}: §III-D split differs "
                                 "from the host rule")
    print(f"(a) §III-D masks equal to split_search_space: "
          f"{len(outs)}/{len(outs)}")
    cpu_jobs = cluster_fleet(JOB_ORDER)
    cpu_outs, _, _ = _drain(cpu_jobs, devices=[cpu])
    same = sum(a.best_index == b.best_index for a, b in zip(outs, cpu_outs))
    print(f"(a) same final config as the CPU backend: {same}/{len(outs)}")
    for a, b in zip(outs, cpu_outs):
        print(f"    {a.name:28s} tpu best {a.best_index:2d} after "
              f"{len(a.records):2d} trials (stop {a.stop_iteration}) | cpu "
              f"best {b.best_index:2d} after {len(b.records):2d} trials "
              f"(stop {b.stop_iteration})")
    paper_states = sample_states(
        jobs, outs,
        lambda o: trial_budget(len(o.priority), len(o.remaining), BOSettings()),
        SAMPLES_PER_JOB, rng,
    )

    # (b) catalog scale, both layouts.
    t0 = time.perf_counter()
    lowered, compiled = fused_update_text(dev, CATALOG_N, CATALOG_D, CATALOG_B)
    if "tpu_custom_call" not in lowered or "tpu_custom_call" not in compiled:
        raise AssertionError("(b) the fused chunk update holds no Pallas "
                             "kernel (tpu_custom_call)")
    print(f"(b) fused chunk update (extent 8, n={CATALOG_N}) lowered and "
          f"compiled with tpu_custom_call in {time.perf_counter() - t0:.3f} s")
    by_layout = {}
    for layout in ("feature", "fused"):
        jobs_b, outs_b = _twice(
            f"(b) catalog {layout}", catalog_jobs, devices=[dev],
            **catalog_kwargs(layout))
        by_layout[layout] = outs_b
    same = sum(a.as_dict() == b.as_dict()
               for a, b in zip(by_layout["feature"], by_layout["fused"]))
    print(f"(b) feature vs fused identical outcomes: {same}/{CATALOG_JOBS}")
    catalog_states = sample_states(
        jobs_b[:16], by_layout["fused"][:16], lambda o: CATALOG_B, 1, rng)

    # (c) agreement with the CPU reference.
    t0 = time.perf_counter()
    check_agreement("(c) paper", paper_states, dev, cpu)
    check_agreement("(c) catalog", catalog_states, dev, cpu)
    print(f"(c) agreement checks passed in {time.perf_counter() - t0:.3f} s")


def four_chips(devs) -> None:
    """Sharded catalog fleet vs unsharded on one chip, and a reshard."""
    for layout in ("feature", "fused"):
        kw = catalog_kwargs(layout)
        _, ref = _twice(f"(d) catalog {layout}, 1 chip", catalog_jobs,
                        devices=[devs[0]], **kw)

        placed = set()

        def record_placement(session, key, iteration):
            if iteration == 1:
                for ch in session._chunks_for(key):
                    placed.update(
                        d.id for d in ch.state.obs.sharding.device_set)

        _, sharded = _twice(f"(d) catalog {layout}, shard=4", catalog_jobs,
                            shard=4, pace=record_placement, **kw)
        if placed != {d.id for d in devs[:4]}:
            raise AssertionError(f"(d) shard=4 placed chunks on devices "
                                 f"{sorted(placed)} only")
        same = sum(a.as_dict() == b.as_dict() for a, b in zip(ref, sharded))
        print(f"(d) {layout}: chunk state spans devices {sorted(placed)}; "
              f"shard=4 vs 1 chip identical outcomes {same}/{len(ref)}")

        moved = []

        def reshard_at_4(session, key, iteration):
            # The group's own worker, between iterations: its stale chunk
            # list steps nothing ("gone"), the next pass finds the new ones.
            if iteration == 4 and not moved:
                moved.append(session.reshard(shard=2))

        jobs = catalog_jobs()
        outs, last_ei, dt = _drain(jobs, shard=4, pace=reshard_at_4, **kw)
        _check_outcomes(f"(d) {layout} reshard", jobs, outs, last_ei)
        same = sum(a.as_dict() == b.as_dict() for a, b in zip(sharded, outs))
        print(f"(d) {layout}: resharded {moved[0]} live searches 4 → 2 devices "
              f"after 3 steps; survivors identical to the undisturbed run "
              f"{same}/{len(outs)} ({dt:.3f} s)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devs[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees {len(devs)}",
              file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    print(f"devices: {len(devs)} x {devs[0].device_kind}; jax "
          f"{jax.__version__}; compile cache {cache}")
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(devs)
    else:
        one_chip(devs[0], jax.devices("cpu")[0])
    print(f"total {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs),
    }}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
