"""Async tuning-service lane: `TuningService` / `TuningDaemon`.

Three layers of guarantees, strongest first:

  * BIT-IDENTITY — every golden scenario replayed through the async
    service (per-group worker threads, no lockstep barrier) must equal
    the committed single-threaded fixtures byte-for-byte, unsharded and
    sharded, including the disturbed elastic fleet (victim cancelled and
    the fleet resharded while the pace gate holds the workers mid-
    flight).  The interleaving-fuzz tests then drive seeded adversarial
    sleeps through the pace hook and compare per-job `as_dict()` against
    a single-threaded reference drain of the same workload.
  * SCHEDULING CONTRACTS — bounded-queue backpressure ("block" parks the
    submitter until capacity frees; "raise" throws `ServiceSaturated`),
    graceful shutdown, thread-safe `ProfileCache` sharing.
  * OPERATIONAL SURFACE — the metrics snapshot schema (queue depth,
    per-group step latency, jobs/sec, PR-7 fault counters) and the
    `TuningDaemon` JSON snapshot file.

Every test here carries the ``service`` marker: conftest arms a 60 s
faulthandler watchdog, so a deadlock aborts with all-thread tracebacks
instead of wedging the suite.
"""

import json
import threading
import time

import pytest

from repro.core.bayesopt import BOSettings
from repro.fleet import (
    FleetJob,
    ProfileCache,
    ServiceSaturated,
    TuningService,
    TuningSession,
)
from repro.runtime.serve import TuningDaemon

from golden import assert_outcomes_match
from golden.scenarios import (
    SCENARIOS,
    _elastic_job,
    flat_profile,
    quad_space,
    quad_table,
    synth_space_table,
)
from test_golden_traces import FAULT_FIELDS

pytestmark = pytest.mark.service


class _ServiceEngine:
    """Session-surface adapter over a `TuningService` for the golden
    scenario runners.  ``paused=True`` parks the workers while a wave is
    being submitted and re-parks after every drain — the warm-session
    scenario needs each wave's class-history snapshots to be atomic
    (exactly what the synchronous session gives it); the no-history
    scenarios run unpaused so the lanes exercise REAL submit/step
    concurrency."""

    def __init__(self, paused=False, **kwargs):
        self.svc = TuningService(**kwargs)
        self.paused = paused
        if paused:
            self.svc.pause()

    def submit(self, *args, **kwargs):
        return self.svc.submit(*args, **kwargs)

    def drain(self):
        out = self.svc.drain()
        if self.paused:
            self.svc.pause()
        return out

    def results(self):
        return self.svc.results()

    def shutdown(self):
        self.svc.shutdown(drain=False)


def _run_through_service(scenario, layout, shard, paused):
    engines = []

    def engine(**kwargs):
        eng = _ServiceEngine(paused=paused, **kwargs)
        engines.append(eng)
        return eng

    try:
        return SCENARIOS[scenario](layout=layout, shard=shard, engine=engine)
    finally:
        for eng in engines:
            eng.shutdown()


@pytest.mark.golden
class TestGoldenThroughService:
    """The four committed scenarios through the async service — any
    worker interleaving must reproduce the lockstep fixtures exactly."""

    @pytest.mark.parametrize("scenario", sorted(SCENARIOS))
    def test_unsharded_matches_fixture(self, scenario):
        outs = _run_through_service(
            scenario, "feature", None, paused=(scenario == "warm-session")
        )
        assert_outcomes_match(scenario, outs)

    @pytest.mark.parametrize("scenario", ["n69-exhaustion", "n512-budgeted"])
    def test_sharded_matches_fixture(self, scenario):
        outs = _run_through_service(
            scenario, "feature", 2, paused=False
        )
        assert_outcomes_match(scenario, outs)


@pytest.mark.chaos
class TestDisturbedThroughService:
    def test_disturbed_elastic_fleet_survivors_match(self):
        """The adversarial elastic scenario driven through the service:
        the pace gate parks every group mid-flight (> 3 iterations in),
        the victim is cancelled and the fleet resharded 2 → 1 while the
        workers are held, then the gate opens and the drain finishes.
        Survivors must equal the UNDISTURBED fixture (modulo the fault-
        reporting fields), exactly like the synchronous disturbed test."""
        from repro.cluster.faults import FaultPlan

        gate = threading.Event()
        parked = set()
        parked_cv = threading.Condition()

        def pace(key, iteration):
            if gate.is_set() or iteration <= 3:
                return
            with parked_cv:
                parked.add(key)
                parked_cv.notify_all()
            gate.wait()

        svc = TuningService(
            layout="feature", shard=2,
            settings=BOSettings(max_iters=12), warm_start=False, pace=pace,
        )
        try:
            svc.pause()
            handles = []
            for s in range(8):
                job = _elastic_job(f"e{s}", s)
                if s in (0, 3):
                    plan = FaultPlan(seed=s, transient_run_failures=2)
                    job.profile_run = plan.wrap_run(job.profile_run, job.name)
                handles.append(svc.submit(job, seed=s))
            victim = svc.submit(_elastic_job("victim", 0), seed=99)
            keys = svc._session._pending_group_keys()
            svc.resume()
            deadline = time.monotonic() + 30.0
            with parked_cv:
                while parked != keys:
                    assert time.monotonic() < deadline, (parked, keys)
                    parked_cv.wait(0.1)
            assert victim.cancel()
            svc._session.reshard(shard=None)  # shard loss, mid-flight
            gate.set()
            svc.drain()
        finally:
            gate.set()
            svc.shutdown(drain=False)
        assert_outcomes_match(
            "elastic-fleet", [h.outcome() for h in handles],
            ignore=FAULT_FIELDS,
        )
        assert victim.status == "cancelled"
        assert victim.outcome().records  # trials landed before the cancel


def _fuzz_jobs():
    """A three-group mixed workload with unique names: cherrypick over
    n=69, explicit-split over n=512, profiled Ruya over n=20."""
    space69, table69 = synth_space_table(69)
    space512, table512 = synth_space_table(512)
    prof = flat_profile()
    jobs = []
    for s in range(4):
        jobs.append((FleetJob(name=f"a{s}", space=space69,
                              cost_table=table69), s, {"mode": "cherrypick"}))
    for s in range(4):
        jobs.append((
            FleetJob(name=f"b{s}", space=space512, cost_table=table512),
            10 + s,
            {"priority": list(range(0, 50)), "remaining": list(range(50, 512))},
        ))
    for s in range(4):
        jobs.append((
            FleetJob(name=f"c{s}", space=quad_space(), cost_table=quad_table(),
                     full_input_size=10e9, profile_result=prof),
            20 + s, {},
        ))
    return jobs


def _session_kwargs():
    return dict(
        layout="feature", settings=BOSettings(max_iters=10),
        warm_start=False,
    )


class TestInterleavingFuzz:
    @pytest.mark.parametrize("fuzz_seed", [0, 1, 2])
    def test_any_interleaving_matches_single_threaded(self, fuzz_seed):
        """Seeded adversarial scheduling: the pace hook injects a
        deterministic pseudo-random sleep per (group, iteration), skewing
        the three groups' relative progress differently per seed.  Every
        job's full `SearchOutcome.as_dict()` must equal the single-
        threaded lockstep drain of the identical workload."""
        reference = TuningSession(**_session_kwargs())
        for job, seed, kw in _fuzz_jobs():
            reference.submit(job, seed=seed, **kw)
        want = {o.name: o.as_dict() for o in reference.drain()}

        import hashlib

        def pace(key, iteration):
            h = hashlib.sha256(
                f"{fuzz_seed}/{key}/{iteration}".encode()
            ).digest()
            time.sleep((h[0] % 8) * 0.001)

        svc = TuningService(pace=pace, **_session_kwargs())
        try:
            # Unpaused: submissions race the workers' admission loops.
            handles = [
                svc.submit(job, seed=seed, **kw)
                for job, seed, kw in _fuzz_jobs()
            ]
            got = {o.name: o.as_dict() for o in svc.drain()}
        finally:
            svc.shutdown(drain=False)
        assert set(got) == set(want)
        for name in want:
            assert got[name] == want[name], f"job {name} diverged"
        assert all(h.status == "done" for h in handles)


class TestBackpressure:
    def test_saturation_raise(self):
        svc = TuningService(
            max_in_flight=2, saturation="raise", **_session_kwargs()
        )
        space, table = synth_space_table(69)
        try:
            svc.pause()  # nothing completes → the cap must bind
            for s in range(2):
                svc.submit(FleetJob(name=f"j{s}", space=space,
                                    cost_table=table),
                           seed=s, mode="cherrypick")
            with pytest.raises(ServiceSaturated):
                svc.submit(FleetJob(name="j2", space=space, cost_table=table),
                           seed=2, mode="cherrypick")
            outs = svc.drain()  # resumes, finishes the two admitted jobs
        finally:
            svc.shutdown(drain=False)
        assert [o.name for o in outs] == ["j0", "j1"]

    def test_saturation_block_parks_submitter_until_capacity(self):
        svc = TuningService(max_in_flight=1, **_session_kwargs())
        space, table = synth_space_table(69)

        def job(name):
            return FleetJob(name=name, space=space, cost_table=table)

        try:
            svc.pause()
            svc.submit(job("first"), seed=0, mode="cherrypick")
            second_done = threading.Event()

            def blocked_submit():
                svc.submit(job("second"), seed=1, mode="cherrypick")
                second_done.set()

            t = threading.Thread(target=blocked_submit, daemon=True)
            t.start()
            time.sleep(0.2)
            # Still parked: capacity is 1 and "first" cannot finish while
            # the service is paused.
            assert not second_done.is_set()
            svc.resume()  # "first" completes → capacity frees → unblocks
            assert second_done.wait(timeout=30.0)
            t.join(timeout=10.0)
            svc.drain()
        finally:
            svc.shutdown(drain=False)
        assert sorted(o.name for o in svc.results()) == ["first", "second"]

    def test_max_in_flight_validation(self):
        with pytest.raises(ValueError):
            TuningService(max_in_flight=0)
        with pytest.raises(ValueError):
            TuningService(saturation="drop")


class TestProfileCacheConcurrency:
    def test_concurrent_get_or_profile_single_class(self):
        """16 threads racing one empty cache with same-class jobs: the
        class must be profiled exactly once (one miss, 15 hits) and the
        store must not tear — the regression this pins is the unlocked
        probe→miss→store window double-profiling a class."""
        cache = ProfileCache()
        n_threads = 16
        barrier = threading.Barrier(n_threads)
        results, errors = [], []

        def run_fn(sample_bytes):
            time.sleep(0.001)  # widen the probe window
            return sample_bytes * 5e-7, 0.9 * sample_bytes + 1e9

        def worker():
            try:
                barrier.wait()
                results.append(cache.get_or_profile(run_fn, 10e9))
            except BaseException as e:  # pragma: no cover
                errors.append(e)

        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30.0)
        assert not errors
        assert len(results) == n_threads
        assert cache.misses == 1
        assert cache.hits == n_threads - 1
        # Every thread got the one shared profile object.
        assert all(r is results[0] for r in results)

    def test_shared_cache_across_concurrent_services(self):
        """Two services submitting same-class profiled jobs concurrently
        through ONE cache: exactly one full profile run in total."""
        cache = ProfileCache()

        def make_svc():
            return TuningService(
                cache=cache, settings=BOSettings(max_iters=8),
                warm_start=False,
            )

        def run_fn(sample_bytes):
            return sample_bytes * 5e-7, 0.8 * sample_bytes + 1e9

        svcs = [make_svc(), make_svc()]
        try:
            barrier = threading.Barrier(2)

            def drive(svc, tag):
                barrier.wait()
                for s in range(3):
                    svc.submit(
                        FleetJob(name=f"{tag}{s}", space=quad_space(),
                                 cost_table=quad_table(),
                                 full_input_size=10e9, profile_run=run_fn),
                        seed=s,
                    )
                svc.drain()

            threads = [
                threading.Thread(target=drive, args=(svc, tag), daemon=True)
                for svc, tag in zip(svcs, "xy")
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=45.0)
                assert not t.is_alive()
        finally:
            for svc in svcs:
                svc.shutdown(drain=False)
        assert cache.misses == 1
        assert cache.hits == 5  # six same-class jobs, one full profile


class TestMetricsSurface:
    def test_metrics_schema_and_counters(self):
        svc = TuningService(max_in_flight=8, **_session_kwargs())
        space, table = synth_space_table(69)
        try:
            for s in range(3):
                svc.submit(FleetJob(name=f"j{s}", space=space,
                                    cost_table=table),
                           seed=s, mode="cherrypick")
            svc.drain()
            m = svc.metrics()
        finally:
            svc.shutdown(drain=False)
        json.dumps(m)  # the whole surface must be JSON-able
        assert m["submitted"] == 3
        assert m["completed"] == 3
        assert m["in_flight"] == 0
        assert m["queue_depth"] == 0
        assert m["statuses"] == {"converged": 3}
        assert m["jobs_per_sec"] > 0
        assert m["faults"]["profile_attempts_total"] == 3  # 1 clean try each
        assert m["faults"]["retry_backoff_s_total"] == 0.0
        assert m["faults"]["straggler_trials"] == 0
        groups = m["groups"]
        assert len(groups) == 1  # one admission group in this workload
        (g,) = groups.values()
        assert g["iterations"] > 0 and g["steps"] > 0
        # Every step that found its chunk live enqueued one update.
        assert 0 < g["dispatches"] <= g["steps"]
        assert g["admit_s"] > 0 and g["dispatch_s"] > 0
        assert g["admissions"] >= 1
        assert g["polls"] >= 0 and g["poll_wait_s"] >= 0.0
        assert g["retire_s"] > 0
        assert m["lock_waits"] >= 0 and m["lock_wait_s"] >= 0.0
        assert g["admitted"] == 3
        assert g["live_chunks"] == 0

    def test_zero_job_snapshot_has_no_rate(self):
        """A fresh service has no completion window: `jobs_per_sec` must
        be None, not a division artifact."""
        svc = TuningService(**_session_kwargs())
        try:
            m = svc.metrics()
        finally:
            svc.shutdown(drain=False)
        json.dumps(m)
        assert m["submitted"] == 0 and m["completed"] == 0
        assert m["jobs_per_sec"] is None

    def test_one_job_snapshot_has_no_rate(self):
        """One completion's 'window' is just that job's latency — the old
        truthiness check plus the `max(span, 1e-9)` clamp extrapolated it
        into absurd (near-infinite) jobs/sec.  A single-completion
        snapshot must report None and leave the rest of the surface
        intact."""
        svc = TuningService(**_session_kwargs())
        space, table = synth_space_table(69)
        try:
            svc.submit(FleetJob(name="only", space=space, cost_table=table),
                       seed=0, mode="cherrypick")
            svc.drain()
            m = svc.metrics()
        finally:
            svc.shutdown(drain=False)
        json.dumps(m)
        assert m["completed"] == 1
        assert m["statuses"] == {"converged": 1}
        assert m["jobs_per_sec"] is None

    def test_fault_counters_aggregate_from_outcomes(self):
        from repro.cluster.faults import FaultPlan

        svc = TuningService(
            settings=BOSettings(max_iters=12), warm_start=False,
        )
        try:
            job = _elastic_job("faulty", 0)
            plan = FaultPlan(seed=0, transient_run_failures=2)
            job.profile_run = plan.wrap_run(job.profile_run, job.name)
            svc.submit(job, seed=0)
            svc.submit(_elastic_job("clean", 1), seed=1)
            svc.drain()
            m = svc.metrics()
        finally:
            svc.shutdown(drain=False)
        # 3 attempts for the faulted job + 1 for the clean one.
        assert m["faults"]["profile_attempts_total"] == 4
        assert m["faults"]["profile_retries_total"] == 2
        assert m["faults"]["retry_backoff_s_total"] > 0


class TestDaemon:
    def test_daemon_snapshots_metrics_json(self, tmp_path):
        path = tmp_path / "tuning_metrics.json"
        space, table = synth_space_table(69)
        with TuningDaemon(
            metrics_path=str(path), snapshot_every_s=0.05,
            **_session_kwargs(),
        ) as daemon:
            for s in range(2):
                daemon.submit(FleetJob(name=f"j{s}", space=space,
                                       cost_table=table),
                              seed=s, mode="cherrypick")
            outs = daemon.drain()
            assert [o.name for o in outs] == ["j0", "j1"]
        # stop() (via __exit__) flushed a final snapshot.
        payload = json.loads(path.read_text())
        assert payload["completed"] == 2
        assert payload["in_flight"] == 0
        assert "snapshot_unix_s" in payload
        assert payload["groups"]

    def test_shutdown_without_drain_keeps_finished_results(self):
        space, table = synth_space_table(69)
        svc = TuningService(**_session_kwargs())
        svc.submit(FleetJob(name="j0", space=space, cost_table=table),
                   seed=0, mode="cherrypick")
        svc.drain()
        svc.shutdown(drain=False)
        assert [o.name for o in svc.results()] == ["j0"]
        with pytest.raises(RuntimeError):
            svc.submit(FleetJob(name="j1", space=space, cost_table=table),
                       seed=1, mode="cherrypick")
