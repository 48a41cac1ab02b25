"""The three pinned golden-trace scenarios.

Each scenario builds a deterministic workload and runs it through a
`TuningSession`, returning the outcomes in submission order.  The session
variant under test is injected via ``layout`` / ``shard`` — the committed
fixtures are generated from the UNSHARDED feature-layout session after
`tests.golden.regen` cross-checks it against the sequential engine, and
every other lane (fused layout, shard counts 2/4, the legacy shims) must
reproduce the same bits.

Scenario catalog (ISSUE 5's pinned set):

  * ``n69-exhaustion`` — 4 CherryPick jobs over a synthetic 69-config
    space, run to exhaustion: the packed buffer completely full (B = n),
    the paper-replay regime.
  * ``n512-budgeted``  — 6 two-phase Ruya jobs over a 512-config space at
    max_iters = 10: the budgeted B ≪ n regime, with a phase boundary.
  * ``warm-session``   — a streaming session: a cold profiled wave is
    drained, then a second wave mixes warm-started same-class jobs with
    cold CherryPick jobs in the same lockstep chunks (seeding, padding
    inertness, and class-history determinism in one trace).
  * ``elastic-fleet``  — 8 two-class Ruya jobs whose profiles come from
    DETERMINISTIC linear run fns (exact fits, so retried profiling runs
    return identical models).  The fixture is the undisturbed run;
    `run_elastic_fleet_disturbed` replays it under adversity — transient
    profiling faults on two jobs, a ninth "victim" job cancelled
    mid-flight, and a live shard-loss `reshard` — and the survivors must
    be bit-identical to the fixture (modulo the fault-reporting fields;
    see `assert_outcomes_match(ignore=...)`).

Job counts are chosen so the sharded lanes really shard: at S = 2 every
scenario splits into ≥ 2 row-2/3 chunks, and n512 at S = 4 runs a 3-shard
bundle.
"""

import numpy as np

from repro.core.bayesopt import BOSettings
from repro.core.memory_model import fit_memory_model
from repro.core.profiler import ProfileResult
from repro.core.search_space import Configuration, SearchSpace
from repro.fleet import FleetJob, TuningSession

GiB = 1024.0**3


def synth_space_table(n, d=5, seed=0):
    """The repo's standard synthetic benchmark space (same generator as
    `tests/test_session.py` / `tests/test_fleet.py` — seeds must match so
    fixture traces line up with the engines' other identity tests)."""
    rng = np.random.default_rng(seed + n)
    feats = rng.normal(size=(n, d))
    space = SearchSpace(
        [
            Configuration(
                name=f"s{i}",
                features=tuple(float(v) for v in feats[i]),
                total_memory=float(i) * GiB,
            )
            for i in range(n)
        ]
    )
    w = rng.normal(size=d)
    z = feats @ w
    z = (z - z.mean()) / max(float(z.std()), 1e-9)
    return space, 1.0 + (z - 0.7) ** 2 + 0.05 * rng.random(n)


def flat_profile():
    model = fit_memory_model([1e9, 2e9, 3e9], [5e9, 5e9, 5e9])
    return ProfileResult(
        sizes=(1e9, 2e9, 3e9), readings=(5e9,) * 3, total_time_s=1.0,
        calibration_runs=1, model=model,
    )


def quad_space(n=20):
    return SearchSpace(
        [
            Configuration(name=f"c{i}", features=(float(i),),
                          total_memory=float(i) * GiB)
            for i in range(n)
        ]
    )


def quad_table(n=20, optimum=9):
    return np.array([1.0 + 0.05 * (i - optimum) ** 2 for i in range(n)])


def _session(layout, shard, engine=None, **kw):
    """``engine`` swaps the driver under a scenario: a factory called as
    ``engine(layout=..., shard=..., **session_kwargs)`` returning any
    object with the session's submit/drain/results surface — the async
    service lanes inject `TuningService` here and must reproduce the
    committed single-threaded fixtures bit-for-bit."""
    if engine is not None:
        return engine(layout=layout, shard=shard, **kw)
    return TuningSession(layout=layout, shard=shard, **kw)


def run_n69_exhaustion(layout="feature", shard=None, engine=None):
    space, table = synth_space_table(69)
    session = _session(layout, shard, engine,
                       mode="cherrypick", to_exhaustion=True)
    for s in range(4):
        session.submit(
            FleetJob(name=f"j{s}", space=space, cost_table=table), seed=s,
        )
    return session.drain()


def run_n512_budgeted(layout="feature", shard=None, engine=None):
    space, table = synth_space_table(512)
    st = BOSettings(max_iters=10)
    prio = list(range(0, 50))
    rest = list(range(50, 512))
    # 7 jobs: at S = 4 the group re-chunks to rows = 2 → a genuine 4-shard
    # bundle; at S = 2, rows = 4 → 2 shards.
    session = _session(layout, shard, engine, settings=st,
                       to_exhaustion=True)
    for s in range(7):
        session.submit(
            FleetJob(name=f"j{s}", space=space, cost_table=table),
            seed=s, priority=prio, remaining=rest,
        )
    return session.drain()


def run_warm_session(layout="feature", shard=None, engine=None):
    """Two waves through ONE warm-starting session; drained per wave so
    the class history every wave sees is shard-count-independent."""
    space, table = quad_space(), quad_table()
    prof = flat_profile()

    def job(name):
        return FleetJob(
            name=name, space=space, cost_table=table,
            full_input_size=10e9, profile_result=prof,
        )

    session = _session(layout, shard, engine,
                       warm_start=True, to_exhaustion=False)
    for s in range(3):  # cold profiled wave — builds the class history
        session.submit(job(f"cold{s}"), seed=s)
    session.drain()
    # Second wave: same-class warm starts sharing chunks with cold
    # CherryPick jobs (never seeded — no signature).
    for s in range(2):
        session.submit(job(f"warm{s}"), seed=10 + s)
    for s in range(2):
        session.submit(job(f"cp{s}"), seed=20 + s, mode="cherrypick")
    session.drain()
    return session.results()


def _linear_run(slope, runtime_per_byte=5e-7):
    """Deterministic single-machine profiling emulator: runtime linear in
    the sample (calibration run lands in the profiler's [30 s, 300 s]
    corridor at 1% of a 10 GB input), peak memory EXACTLY linear — the
    fit is noise-free, so a retried run returns the identical model."""

    def run(sample_bytes):
        return sample_bytes * runtime_per_byte, slope * sample_bytes + 1e9

    return run


def _elastic_job(name, idx):
    # Two memory classes (alternating): slope 0.8 → ~8.4 GiB requirement,
    # slope 1.2 → ~12.6 GiB — both split the 0..19 GiB catalog nontrivially.
    return FleetJob(
        name=name, space=quad_space(), cost_table=quad_table(),
        full_input_size=10e9, profile_run=_linear_run(0.8 if idx % 2 == 0 else 1.2),
    )


def run_elastic_fleet(layout="feature", shard=None, engine=None):
    """The undisturbed reference: 8 two-class Ruya jobs, profiled through
    the deterministic linear run fns, drained to completion."""
    session = _session(
        layout, shard, engine,
        settings=BOSettings(max_iters=12), warm_start=False,
    )
    for s in range(8):
        session.submit(_elastic_job(f"e{s}", s), seed=s)
    return session.drain()


def run_elastic_fleet_disturbed(
    layout="feature", shard=2, reshard_to=None, steps_before=3,
):
    """The adversarial replay of ``elastic-fleet``: transient profiling
    faults on jobs e0/e3 (retried — identical profiles, attempt counts
    surface in the outcome), a ninth victim job sharing the fleet, a
    mid-flight cancellation, and a live `reshard` from ``shard`` devices
    to ``reshard_to`` (shard loss by default; pass ``shard=None,
    reshard_to=2`` for a device JOIN).  Returns (survivor outcomes in
    submission order, victim outcome) — survivors must be bit-identical
    to the committed fixture modulo the fault-reporting fields."""
    from repro.cluster.faults import FaultPlan

    session = _session(
        layout, shard, settings=BOSettings(max_iters=12), warm_start=False,
    )
    handles = []
    for s in range(8):
        job = _elastic_job(f"e{s}", s)
        if s in (0, 3):
            plan = FaultPlan(seed=s, transient_run_failures=2)
            job.profile_run = plan.wrap_run(job.profile_run, job.name)
        handles.append(session.submit(job, seed=s))
    victim = session.submit(_elastic_job("victim", 0), seed=99)
    for _ in range(steps_before):
        session.step()
    assert victim.cancel()
    session.reshard(shard=reshard_to)
    session.drain()
    return [h.outcome() for h in handles], victim.outcome()


SCENARIOS = {
    "n69-exhaustion": run_n69_exhaustion,
    "n512-budgeted": run_n512_budgeted,
    "warm-session": run_warm_session,
    "elastic-fleet": run_elastic_fleet,
}
