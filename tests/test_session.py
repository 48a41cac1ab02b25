"""`TuningSession` tests: static-drain bit-identity with the pre-redesign
engines, streaming lifecycle (submit-after-step admission, heterogeneous
grouping), cross-job warm-start seeding/determinism, and the
`TrialRecord`/`SearchOutcome` round-trip property lane.

The identity tests pin the acceptance contract of the session redesign:
draining a statically submitted fleet must reproduce the golden-trace
fixtures (`tests/golden/` — themselves cross-checked against the
sequential engine at regen time, and re-pinned against it by
`tests/test_golden_traces.py`), for both packed geometry layouts, on
n = 69 (exhaustion, full packed buffer) and n = 512 (budgeted B ≪ n) —
and the legacy shims (`run_ruya`, `run_cherrypick`, `tune_fleet`,
`batched_search`) must keep returning the same bits now that they route
through the session.
"""

import json

import numpy as np
import pytest

from golden import assert_outcomes_match
from hypothesis_compat import HAVE_HYPOTHESIS, given, settings as hyp_settings, st

from repro.core.bayesopt import (
    BOSettings,
    cherrypick_search,
    ruya_search,
)
from repro.core.memory_model import fit_memory_model
from repro.core.profiler import ProfileResult
from repro.core.search_space import (
    Configuration,
    SearchSpace,
    split_search_space,
)
from repro.core.tuner import run_cherrypick, run_ruya
from repro.fleet import FleetJob, TuningSession, tune_fleet
from repro.fleet.session import SearchOutcome, TrialRecord

GiB = 1024.0**3
N = 20


def quad_space(n=N):
    return SearchSpace(
        [
            Configuration(name=f"c{i}", features=(float(i),),
                          total_memory=float(i) * GiB)
            for i in range(n)
        ]
    )


def quad_table(n=N, optimum=9):
    return np.array([1.0 + 0.05 * (i - optimum) ** 2 for i in range(n)])


def synth_space_table(n, d=5, seed=0):
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
    """A FLAT ProfileResult whose §III-D split is deterministic."""
    model = fit_memory_model([1e9, 2e9, 3e9], [5e9, 5e9, 5e9])
    return ProfileResult(
        sizes=(1e9, 2e9, 3e9), readings=(5e9,) * 3, total_time_s=1.0,
        calibration_runs=1, model=model,
    )


def linear_profile(slope=3.0):
    sizes = (1e9, 2e9, 3e9)
    readings = tuple(slope * s + 0.5 * GiB for s in sizes)
    return ProfileResult(
        sizes=sizes, readings=readings, total_time_s=1.0,
        calibration_runs=1, model=fit_memory_model(sizes, readings),
    )


def flat_job(name="job", n=N):
    return FleetJob(
        name=name, space=quad_space(n), cost_table=quad_table(n),
        full_input_size=10e9, profile_result=flat_profile(),
    )


def assert_trace_equal(trace, ref):
    assert trace.tried == ref.tried
    assert trace.costs == ref.costs
    assert trace.stop_iteration == ref.stop_iteration
    assert trace.phase_boundary == ref.phase_boundary


class TestStaticDrainIdentity:
    """drain() of a statically submitted fleet == the golden fixtures (the
    pre-redesign engines' pinned bits — `tests/golden/`)."""

    def test_drain_matches_golden_n69_exhaustion(self):
        """n = 69 to exhaustion: packed buffer completely full (B = n).
        A 2-job prefix of the pinned fleet, submitted through handles —
        lockstep extent 2 here vs 4 in the fixture run, so this also
        re-pins the batch-extent invariance the chunking rests on.  (The
        fused-layout and sharded variants ride `tests/test_golden_traces`;
        `batched_search` is now a session shim.)"""
        space, table = synth_space_table(69)
        session = TuningSession(mode="cherrypick", to_exhaustion=True)
        handles = [
            session.submit(
                FleetJob(name=f"j{s}", space=space, cost_table=table),
                seed=s,
            )
            for s in range(2)
        ]
        session.drain()
        outs = [h.outcome() for h in handles]
        for out in outs:
            assert len(out.records) == 69
            assert not out.seeded
        assert_outcomes_match("n69-exhaustion", outs, jobs=[0, 1])

    def test_drain_matches_golden_n512_budgeted_two_phase(self):
        space, table = synth_space_table(512)
        st_ = BOSettings(max_iters=10)
        prio = list(range(0, 50))
        rest = list(range(50, 512))
        for layout in ("feature", "fused"):
            session = TuningSession(settings=st_, to_exhaustion=True,
                                    layout=layout)
            handles = [
                session.submit(
                    FleetJob(name=f"j{s}", space=space, cost_table=table),
                    seed=s, priority=prio, remaining=rest,
                )
                for s in range(3)
            ]
            session.drain()
            outs = [h.outcome() for h in handles]
            for out in outs:
                assert len(out.records) == 10
            assert_outcomes_match("n512-budgeted", outs, jobs=[0, 1, 2])

    def test_shims_pin_ruya_pipeline_bits(self):
        """run_ruya(cost_table) — now session-backed, with the on-device
        split — must reproduce the pre-redesign host-split sequential
        pipeline exactly, profile reuse and report fields included."""
        job = flat_job()
        for seed in range(3):
            rep = run_ruya(
                space=job.space, cost_table=job.cost_table,
                rng=np.random.default_rng(seed),
                full_input_size=job.full_input_size,
                profile_result=job.profile_result,
                to_exhaustion=True,
            )
            prio, rest = split_search_space(
                job.space, job.profile_result.model, job.full_input_size,
            )
            ref = ruya_search(
                job.space,
                lambda i: float(job.cost_table[i]),
                np.random.default_rng(seed), prio, rest, to_exhaustion=True,
            )
            assert rep.priority == tuple(prio)
            assert rep.remaining == tuple(rest)
            assert rep.profile is job.profile_result
            assert_trace_equal(rep.trace, ref)

    def test_shims_pin_cherrypick_bits(self):
        space, table = quad_space(), quad_table()
        for seed in range(3):
            tr = run_cherrypick(
                space=space, cost_table=table,
                rng=np.random.default_rng(seed), to_exhaustion=True,
            )
            ref = cherrypick_search(
                space, lambda i: float(table[i]),
                np.random.default_rng(seed), to_exhaustion=True,
            )
            assert_trace_equal(tr, ref)

    def test_tune_fleet_cache_none_profiles_per_job(self):
        """cache=None must mean per-job profiling in BOTH engines — two
        distinct jobs whose cheap probes share a MemorySignature but whose
        full profiles differ must NOT silently share a profile (that is the
        opt-in `cache=ProfileCache()` behavior)."""

        def linear_run(slope):
            def run(sample_bytes):
                return 1.0, slope * sample_bytes + 0.5 * GiB

            return run

        # Memories up to 38 GiB so the two extrapolated requirements
        # (~33.6 vs ~35.8 GiB with leeway) cut the catalog differently.
        wide = SearchSpace(
            [
                Configuration(name=f"c{i}", features=(float(i),),
                              total_memory=2.0 * i * GiB)
                for i in range(20)
            ]
        )

        def job_for(slope, name):
            return FleetJob(
                name=name, space=wide, cost_table=quad_table(),
                full_input_size=10.0 * GiB, profile_run=linear_run(slope),
            )

        jobs = [job_for(3.0, "a"), job_for(3.2, "b")]  # same probe bucket
        bat = tune_fleet(jobs, [np.random.default_rng(s) for s in range(2)],
                         to_exhaustion=True)
        seq = tune_fleet(
            [job_for(3.0, "a"), job_for(3.2, "b")],
            [np.random.default_rng(s) for s in range(2)],
            to_exhaustion=True, engine="sequential",
        )
        assert bat[0].priority != bat[1].priority  # profiles really differ
        for b, s in zip(bat, seq):
            assert b.priority == s.priority
            assert_trace_equal(b.trace, s.trace)

        # Explicit cache: sharing is opted in, and both engines share alike.
        from repro.fleet import ProfileCache

        cache_b, cache_s = ProfileCache(), ProfileCache()
        bat_c = tune_fleet(
            [job_for(3.0, "a"), job_for(3.2, "b")],
            [np.random.default_rng(s) for s in range(2)],
            to_exhaustion=True, cache=cache_b,
        )
        seq_c = tune_fleet(
            [job_for(3.0, "a"), job_for(3.2, "b")],
            [np.random.default_rng(s) for s in range(2)],
            to_exhaustion=True, cache=cache_s, engine="sequential",
        )
        assert cache_b.hits == 1 and cache_s.hits == 1
        assert bat_c[0].priority == bat_c[1].priority
        for b, s in zip(bat_c, seq_c):
            assert b.priority == s.priority
            assert_trace_equal(b.trace, s.trace)

    def test_session_releases_per_job_state_at_retirement(self):
        """Finished jobs must not pin cost tables or encodings: the
        refcounted per-space and per-job cache entries are evicted when
        their last active submission retires."""
        session = TuningSession(mode="cherrypick", to_exhaustion=True,
                                settings=BOSettings(max_iters=4),
                                layout="feature")
        for s in range(2):
            session.submit(
                FleetJob(name=f"j{s}", space=quad_space(),
                         cost_table=quad_table()),
                seed=s,
            )
        session.drain()
        assert len(session.results()) == 2
        assert not session._spaces and not session._jobs

    def test_tune_fleet_engines_agree_in_ruya_mode(self):
        """tune_fleet batched (session, device split) vs sequential (host
        split): identical reports on flat AND linear profiled jobs."""
        jobs = [
            flat_job("flat"),
            FleetJob(
                name="linear", space=quad_space(), cost_table=quad_table(),
                full_input_size=4.0 * GiB, profile_result=linear_profile(),
            ),
        ] * 2
        rngs = lambda: [np.random.default_rng(s) for s in range(len(jobs))]
        bat = tune_fleet(jobs, rngs(), to_exhaustion=True)
        seq = tune_fleet(jobs, rngs(), to_exhaustion=True,
                         engine="sequential")
        for b, s in zip(bat, seq):
            assert b.priority == s.priority
            assert b.remaining == s.remaining
            assert_trace_equal(b.trace, s.trace)


class TestSessionLifecycle:
    def test_empty_session(self):
        session = TuningSession()
        assert session.step() == 0
        assert session.drain() == []
        assert len(session) == 0

    def test_submit_requires_exactly_one_rng_source(self):
        session = TuningSession()
        job = flat_job()
        with pytest.raises(ValueError):
            session.submit(job)
        with pytest.raises(ValueError):
            session.submit(job, np.random.default_rng(0), seed=1)

    def test_handle_status_transitions(self):
        session = TuningSession(mode="cherrypick", to_exhaustion=True,
                                settings=BOSettings(max_iters=4))
        h = session.submit(flat_job(), seed=0)
        assert h.status == "pending" and not h.done
        with pytest.raises(RuntimeError):
            h.outcome()
        session.step()
        assert h.status == "running"
        session.drain()
        assert h.status == "done" and h.done
        assert len(h.outcome().records) == 4

    def test_submit_after_step_admission_is_bit_exact(self):
        """A job admitted mid-flight joins its own lockstep chunk and must
        produce the identical trace a statically submitted job would."""
        space, table = quad_space(), quad_table()
        ref = cherrypick_search(
            space, lambda i: float(table[i]), np.random.default_rng(7),
            to_exhaustion=True,
        )
        session = TuningSession(mode="cherrypick", to_exhaustion=True)
        session.submit(FleetJob(name="a", space=space, cost_table=table),
                       seed=0)
        for _ in range(3):
            session.step()
        late = session.submit(
            FleetJob(name="late", space=space, cost_table=table), seed=7,
        )
        session.drain()
        assert_trace_equal(late.outcome().trace(), ref)

    def test_heterogeneous_shapes_group_exactly(self):
        """Jobs with different space shapes in ONE session must each
        factorize at the sequential engine's extents — including the
        singleton-chunk dummy-pad path every one-job group takes.
        (Heterogeneous trial budgets on one shape are covered by
        `tests/test_fleet.py`, which routes through the same session.)"""
        sp_a, tb_a = synth_space_table(40, d=3)
        sp_b, tb_b = synth_space_table(24, d=6)
        st_ = BOSettings(max_iters=8)
        refs = [
            cherrypick_search(sp_a, lambda i: float(tb_a[i]),
                              np.random.default_rng(0), settings=st_,
                              to_exhaustion=True),
            cherrypick_search(sp_b, lambda i: float(tb_b[i]),
                              np.random.default_rng(1), settings=st_,
                              to_exhaustion=True),
        ]
        session = TuningSession(settings=st_, mode="cherrypick",
                                to_exhaustion=True)
        handles = [
            session.submit(FleetJob(name="a", space=sp_a, cost_table=tb_a),
                           seed=0),
            session.submit(FleetJob(name="b", space=sp_b, cost_table=tb_b),
                           seed=1),
        ]
        session.drain()
        for h, ref in zip(handles, refs):
            assert_trace_equal(h.outcome().trace(), ref)

    def test_results_in_submission_order(self):
        session = TuningSession(mode="cherrypick", to_exhaustion=True,
                                settings=BOSettings(max_iters=4))
        names = ["x", "y", "z"]
        for i, name in enumerate(names):
            session.submit(
                FleetJob(name=name, space=quad_space(),
                         cost_table=quad_table()),
                seed=i,
            )
        outs = session.drain()
        assert [o.name for o in outs] == names

    def test_step_counts_down_to_zero(self):
        session = TuningSession(mode="cherrypick", to_exhaustion=True,
                                settings=BOSettings(max_iters=3))
        session.submit(flat_job(), seed=0)
        remaining = session.step()
        assert remaining == 1  # budget 3 → needs 4 steps
        while remaining:
            remaining = session.step()
        assert session.step() == 0
        assert len(session.results()) == 1


class TestWarmStart:
    def mk_session(self, **kw):
        kw.setdefault("warm_start", True)
        kw.setdefault("to_exhaustion", False)
        return TuningSession(**kw)

    def test_same_class_seeds_and_converges_faster(self):
        session = self.mk_session()
        job = flat_job()
        cold = session.submit(job, seed=0)
        session.drain()
        warm = session.submit(job, seed=1)
        session.drain()
        c, w = cold.outcome(), warm.outcome()
        assert not c.seeded
        assert w.seeded, "same-signature job must be warm-started"
        assert all(r.source == "warm" for r in w.seeded)
        # Seeds are the class history: the cold job's trials, in completion
        # order, deduplicated by config index.
        assert [s.index for s in w.seeded] == [r.index for r in c.records]
        assert len(w.records) < len(c.records)
        assert session.warm_hits == 1
        assert session.warm_trials == len(w.seeded)

    def test_capacity_aware_seeding_respects_reserve(self):
        """History longer than B − reserve is truncated: seeded slots plus
        the reserve never exceed the packed capacity B."""
        n = 24
        job = FleetJob(
            name="big", space=quad_space(n), cost_table=quad_table(n),
            full_input_size=10e9, profile_result=flat_profile(),
        )
        st_ = BOSettings(max_iters=10)
        session = self.mk_session(settings=st_, to_exhaustion=True)
        session.submit(job, seed=0)
        session.drain()  # 10 completed trials in the class history
        warm = session.submit(job, seed=1)
        session.drain()
        w = warm.outcome()
        budget = 10
        reserve = max(st_.n_init, 1)
        assert len(w.seeded) == budget - reserve
        assert len(w.seeded) + len(w.records) <= budget

    def test_warm_start_is_deterministic_and_consumes_no_rng(self):
        """A warm-started search is a function of (class history, seed);
        with seeding active no RNG is drawn, so even different seeds give
        the identical trace when the history matches."""
        def run_pair(seed2):
            session = self.mk_session()
            session.submit(flat_job(), seed=0)
            session.drain()
            h = session.submit(flat_job(), seed=seed2)
            session.drain()
            return h.outcome()

        a, b = run_pair(1), run_pair(999)
        assert a.as_dict() == b.as_dict()

    def test_warm_neighbor_does_not_perturb_cold_jobs(self):
        """A seeded job sharing a lockstep chunk with cold jobs must leave
        the cold traces bit-identical to solo runs (padding exactness)."""
        job = flat_job()
        session = self.mk_session()
        session.submit(job, seed=0)
        session.drain()
        # Same chunk: one warm (same class) + one cold (cherrypick — no
        # signature, so never seeded); both share (shape, B).
        warm = session.submit(job, seed=1)
        cold = session.submit(job, seed=2, mode="cherrypick")
        session.drain()
        assert warm.outcome().seeded and not cold.outcome().seeded
        ref = cherrypick_search(
            job.space, lambda i: float(job.cost_table[i]),
            np.random.default_rng(2), to_exhaustion=False,
        )
        assert_trace_equal(cold.outcome().trace(), ref)

    def test_warm_start_disabled_session_never_seeds(self):
        session = self.mk_session(warm_start=False)
        session.submit(flat_job(), seed=0)
        session.drain()
        h = session.submit(flat_job(), seed=1)
        session.drain()
        assert not h.outcome().seeded

    def test_per_submit_warm_override(self):
        session = self.mk_session()
        session.submit(flat_job(), seed=0)
        session.drain()
        h = session.submit(flat_job(), seed=1, warm_start=False)
        session.drain()
        assert not h.outcome().seeded

    def test_different_class_is_not_seeded(self):
        session = self.mk_session()
        session.submit(flat_job(), seed=0)
        session.drain()
        other = FleetJob(
            name="linear", space=quad_space(), cost_table=quad_table(),
            full_input_size=4.0 * GiB, profile_result=linear_profile(),
        )
        h = session.submit(other, seed=1)
        session.drain()
        assert h.outcome().signature is not None
        assert not h.outcome().seeded


def _record_roundtrip(index, cost, slot, source):
    rec = TrialRecord(index=index, cost=cost, slot=slot, source=source)
    back = TrialRecord.from_dict(json.loads(json.dumps(rec.as_dict())))
    assert back == rec


class TestRecordRoundTrip:
    """`TrialRecord`/`SearchOutcome` JSON round-tripping — hypothesis lane
    when available, always-on seeded lane otherwise (same property)."""

    SOURCES = ("init", "search", "warm")

    if HAVE_HYPOTHESIS:

        @given(
            index=st.integers(min_value=0, max_value=10**6),
            cost=st.floats(allow_nan=False, allow_infinity=False,
                           width=32),
            slot=st.integers(min_value=0, max_value=4096),
            source=st.sampled_from(("init", "search", "warm")),
        )
        @hyp_settings(max_examples=100, deadline=None)
        def test_trial_record_roundtrip_hypothesis(self, index, cost, slot,
                                                   source):
            _record_roundtrip(index, float(cost), slot, source)

    def test_trial_record_roundtrip_seeded(self):
        rng = np.random.default_rng(1234)
        for _ in range(200):
            _record_roundtrip(
                int(rng.integers(0, 10**6)),
                float(np.float32(rng.normal() * 10.0 ** rng.integers(-3, 6))),
                int(rng.integers(0, 4096)),
                self.SOURCES[int(rng.integers(0, 3))],
            )

    def test_trial_record_rejects_unknown_source(self):
        with pytest.raises(ValueError):
            TrialRecord.from_dict(
                {"index": 0, "cost": 1.0, "slot": 0, "source": "psychic"}
            )

    def test_outcome_roundtrip_seeded(self):
        rng = np.random.default_rng(99)
        for _ in range(25):
            k, w = int(rng.integers(0, 8)), int(rng.integers(0, 5))
            recs = [
                TrialRecord(index=int(rng.integers(0, 50)),
                            cost=float(rng.random()), slot=w + i,
                            source="init" if i < 2 else "search")
                for i in range(k)
            ]
            seeds = [
                TrialRecord(index=int(rng.integers(0, 50)),
                            cost=float(rng.random()), slot=i, source="warm")
                for i in range(w)
            ]
            out = SearchOutcome(
                name="job",
                records=recs,
                seeded=seeds,
                stop_iteration=(None if rng.random() < 0.5
                                else int(rng.integers(0, w + k + 1))),
                phase_boundary=(None if rng.random() < 0.5
                                else int(rng.integers(0, w + k + 1))),
                priority=tuple(int(i) for i in rng.integers(0, 50, size=5)),
                remaining=tuple(int(i) for i in rng.integers(0, 50, size=5)),
            )
            back = SearchOutcome.from_dict(
                json.loads(json.dumps(out.as_dict()))
            )
            assert back.as_dict() == out.as_dict()

    def test_outcome_real_search_roundtrip_and_views(self):
        session = TuningSession(mode="cherrypick", to_exhaustion=True,
                                settings=BOSettings(max_iters=6))
        h = session.submit(flat_job(), seed=3)
        session.drain()
        out = h.outcome()
        back = SearchOutcome.from_dict(json.loads(json.dumps(out.as_dict())))
        assert back.as_dict() == out.as_dict()
        # Views agree with the record list.
        tr = out.trace()
        assert tr.tried == [r.index for r in out.records]
        assert tr.costs == [r.cost for r in out.records]
        assert out.best_cost == min(tr.costs)
        assert out.best_index == tr.best_index
        rep = out.report()
        assert rep.trace.tried == tr.tried
        assert rep.priority == out.priority
        # Sources: the first n_init trials are scripted random picks.
        assert [r.source for r in out.records[:3]] == ["init"] * 3
        assert all(r.source == "search" for r in out.records[3:])
