"""Fleet subsystem tests: batched↔sequential trace equivalence, the fleet
driver's single-code-path API, and Flora-style profile-cache behavior.

The equivalence tests assert *identical* `tried`/`costs`/`stop_iteration`
sequences between `batched_search` (J jobs advanced in device-resident
lockstep) and J runs of the sequential engine with the same seeds — the
contract that makes fleet mode a pure execution optimization — including
across packed-buffer capacities (heterogeneous trial budgets group by
(shape, B)) and space extents (n = 69 exhaustion = full buffer,
synthetic n = 512 and n = 8192 in the budgeted B ≪ n regime).  The fast tests
mostly share array shapes so the engine compiles few programs; the
exhaustive 69-config cluster sweep and the n = 8192 identity are marked
`slow`.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from golden import assert_traces_match
from repro.core.bayesopt import BOSettings, cherrypick_search, ruya_search
from repro.core.fast_bo import (
    FleetState,
    SequentialProbe,
    encode_features,
    fleet_step,
)
from repro.core.memory_model import fit_memory_model
from repro.core.search_space import Configuration, SearchSpace
from repro.fleet import (
    MemorySignature,
    ProfileCache,
    batched_search,
    cluster_fleet,
    replay_seeds,
    TuningSession,
    tune_fleet,
)
from repro.fleet.batched_engine import _fleet_update

GiB = 1024**3
N = 20
SEEDS = range(4)


def quad_space(n=N):
    return SearchSpace(
        [
            Configuration(name=f"c{i}", features=(float(i),), total_memory=float(i))
            for i in range(n)
        ]
    )


def quad_table(n=N, optimum=9):
    return np.array([1.0 + 0.05 * (i - optimum) ** 2 for i in range(n)])


def synth_space_table(n, d=5, seed=0):
    """Random-feature space + smooth synthetic cost table (scaling tests)."""
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
    return space, 1.0 + (z - 0.7) ** 2 + 0.05 * rng.random(n)


def assert_traces_equal(batched_trace, reference):
    assert batched_trace.tried == reference.tried
    assert batched_trace.costs == reference.costs
    assert batched_trace.stop_iteration == reference.stop_iteration
    assert batched_trace.phase_boundary == reference.phase_boundary


class TestTraceEquivalence:
    space = quad_space()
    table = quad_table()

    def cost_fn(self):
        table = self.table
        return lambda i: float(table[i])

    def test_cherrypick_identical_to_exhaustion(self):
        seq = [
            cherrypick_search(
                self.space, self.cost_fn(), np.random.default_rng(s),
                to_exhaustion=True,
            )
            for s in SEEDS
        ]
        bt = batched_search(
            self.space, [self.table] * len(seq),
            [np.random.default_rng(s) for s in SEEDS], to_exhaustion=True,
        )
        for j, ref in enumerate(seq):
            assert_traces_equal(bt.job_trace(j), ref)

    def test_cherrypick_identical_with_early_stop(self):
        seq = [
            cherrypick_search(
                self.space, self.cost_fn(), np.random.default_rng(s),
                to_exhaustion=False,
            )
            for s in SEEDS
        ]
        bt = batched_search(
            self.space, [self.table] * len(seq),
            [np.random.default_rng(s) for s in SEEDS], to_exhaustion=False,
        )
        for j, ref in enumerate(seq):
            assert_traces_equal(bt.job_trace(j), ref)
            assert bt.job_trace(j).stop_iteration is not None

    def test_ruya_two_phase_identical(self):
        prio = [7, 8, 9, 10, 11]
        rest = [i for i in range(N) if i not in prio]
        seq = [
            ruya_search(
                self.space, self.cost_fn(), np.random.default_rng(s), prio, rest,
                to_exhaustion=True,
            )
            for s in SEEDS
        ]
        bt = batched_search(
            self.space, [self.table] * len(seq),
            [np.random.default_rng(s) for s in SEEDS],
            priority=[prio] * len(seq), remaining=[rest] * len(seq),
            to_exhaustion=True,
        )
        for j, ref in enumerate(seq):
            assert_traces_equal(bt.job_trace(j), ref)
            assert bt.job_trace(j).phase_boundary == len(prio)

    def test_mixed_splits_in_one_batch(self):
        """Ruya and CherryPick jobs co-exist in one batched call."""
        prio = [0, 1, 2, 18, 19]
        rest = [i for i in range(N) if i not in prio]
        refs = [
            ruya_search(self.space, self.cost_fn(), np.random.default_rng(0),
                        prio, rest, to_exhaustion=True),
            cherrypick_search(self.space, self.cost_fn(),
                              np.random.default_rng(1), to_exhaustion=True),
            ruya_search(self.space, self.cost_fn(), np.random.default_rng(2),
                        list(range(N)), [], to_exhaustion=True),
            cherrypick_search(self.space, self.cost_fn(),
                              np.random.default_rng(3), to_exhaustion=True),
        ]
        bt = batched_search(
            self.space, [self.table] * 4,
            [np.random.default_rng(s) for s in range(4)],
            priority=[prio, list(range(N)), list(range(N)), list(range(N))],
            remaining=[rest, [], [], []],
            to_exhaustion=True,
        )
        for j, ref in enumerate(refs):
            assert_traces_equal(bt.job_trace(j), ref)

    def test_single_job_fleet(self):
        """J=1 must behave like any other fleet size (dummy-padding)."""
        ref = cherrypick_search(
            self.space, self.cost_fn(), np.random.default_rng(11),
            to_exhaustion=True,
        )
        bt = batched_search(
            self.space, [self.table], [np.random.default_rng(11)],
            to_exhaustion=True,
        )
        assert len(bt) == 1
        assert_traces_equal(bt.job_trace(0), ref)

    def test_max_iters_at_phase_boundary_records_it(self):
        """max_iters landing exactly on the phase-0/phase-1 boundary must
        still record phase_boundary, like the sequential engine does."""
        prio = [7, 8, 9, 10, 11]
        rest = [i for i in range(N) if i not in prio]
        st = BOSettings(max_iters=len(prio))
        seq = [
            ruya_search(self.space, self.cost_fn(), np.random.default_rng(s),
                        prio, rest, settings=st, to_exhaustion=True)
            for s in SEEDS
        ]
        bt = batched_search(
            self.space, [self.table] * len(seq),
            [np.random.default_rng(s) for s in SEEDS],
            priority=[prio] * len(seq), remaining=[rest] * len(seq),
            settings=st, to_exhaustion=True,
        )
        for j, ref in enumerate(seq):
            assert ref.phase_boundary == len(prio)
            assert_traces_equal(bt.job_trace(j), ref)

    def test_max_iters_below_init_count(self):
        """The sequential engine observes all scripted init picks before its
        first budget check; the fleet engine must match."""
        st = BOSettings(max_iters=2)  # < default n_init=3
        seq = [
            cherrypick_search(self.space, self.cost_fn(),
                              np.random.default_rng(s), settings=st,
                              to_exhaustion=True)
            for s in SEEDS
        ]
        bt = batched_search(
            self.space, [self.table] * len(seq),
            [np.random.default_rng(s) for s in SEEDS], settings=st,
            to_exhaustion=True,
        )
        for j, ref in enumerate(seq):
            assert len(ref.tried) == 3
            assert_traces_equal(bt.job_trace(j), ref)

    def test_max_iters_budget(self):
        st = BOSettings(max_iters=7)
        seq = [
            cherrypick_search(self.space, self.cost_fn(),
                              np.random.default_rng(s), settings=st,
                              to_exhaustion=True)
            for s in SEEDS
        ]
        bt = batched_search(
            self.space, [self.table] * len(seq),
            [np.random.default_rng(s) for s in SEEDS], settings=st,
            to_exhaustion=True,
        )
        for j, ref in enumerate(seq):
            assert len(bt.job_trace(j).tried) == 7
            assert_traces_equal(bt.job_trace(j), ref)

    def test_heterogeneous_budgets_group_by_capacity(self):
        """Jobs with different trial budgets (→ different packed capacities
        B) in one batched call: each must factorize at exactly the capacity
        the sequential engine uses for it (grouping by (shape, B)), so every
        trace stays identical — including the singleton dummy-pad path each
        one-job capacity group takes."""
        pools = [list(range(10)), list(range(N)), list(range(5, 12))]
        refs = [
            ruya_search(self.space, self.cost_fn(), np.random.default_rng(s),
                        pool, [], to_exhaustion=True)
            for s, pool in enumerate(pools)
        ]
        bt = batched_search(
            self.space, [self.table] * 3,
            [np.random.default_rng(s) for s in range(3)],
            priority=pools, remaining=[[], [], []],
            to_exhaustion=True,
        )
        for j, ref in enumerate(refs):
            assert len(ref.tried) == len(pools[j])  # budgets really differ
            assert_traces_equal(bt.job_trace(j), ref)


class TestTraceEquivalenceScaling:
    """Packed-engine identity at the paper's space extent and beyond it,
    pinned against the golden fixtures (`tests/golden/` — regenerated from
    the sequential reference, so these shim lanes still close the
    sequential↔batched loop, now through one committed artifact).

    n=69 runs to exhaustion (capacity B = n: the packed buffer completely
    full); n=512 runs the budgeted B ≪ n regime the packed layout targets.
    One set of shapes per test so each compiles once.
    """

    def test_n69_exhaustion_matches_golden(self):
        space, table = synth_space_table(69)
        bt = batched_search(
            space, [table] * 2, [np.random.default_rng(s) for s in range(2)],
            to_exhaustion=True,
        )
        assert all(len(bt.job_trace(j).tried) == 69 for j in range(2))
        assert_traces_match("n69-exhaustion", bt.traces(), jobs=[0, 1])

    def test_n512_budgeted_matches_golden(self):
        space, table = synth_space_table(512)
        st = BOSettings(max_iters=10)
        prio = list(range(0, 50))
        rest = list(range(50, 512))
        bt = batched_search(
            space, [table] * 3,
            [np.random.default_rng(s) for s in range(3)],
            priority=[prio] * 3, remaining=[rest] * 3, settings=st,
            to_exhaustion=True,
        )
        assert all(len(bt.job_trace(j).tried) == 10 for j in range(3))
        assert_traces_match("n512-budgeted", bt.traces(), jobs=[0, 1, 2])


@pytest.mark.slow
class TestTraceEquivalenceLargeSpace:
    """The 10⁴-regime identity (slow lane): a budgeted search over n = 8192
    must produce bit-identical traces from the sequential and the batched
    feature-buffer engines."""

    def test_n8192_budgeted_identical(self):
        space, table = synth_space_table(8192)
        st = BOSettings(max_iters=12)
        prio = list(range(0, 64))
        rest = list(range(64, 8192))
        refs = [
            ruya_search(space, lambda i: float(table[i]),
                        np.random.default_rng(s), prio, rest, settings=st,
                        to_exhaustion=True)
            for s in range(2)
        ]
        bt = batched_search(
            space, [table] * 2, [np.random.default_rng(s) for s in range(2)],
            priority=[prio] * 2, remaining=[rest] * 2, settings=st,
            to_exhaustion=True,
        )
        for j, ref in enumerate(refs):
            assert len(ref.tried) == 12
            assert_traces_equal(bt.job_trace(j), ref)


@pytest.mark.slow
class TestTraceEquivalenceClusterSweep:
    """Exhaustive identity on the paper's real 69-config jobs."""

    def test_cluster_jobs_identical(self):
        from repro.core.profiler import profile_job
        from repro.core.search_space import split_search_space

        keys = ["kmeans/spark/huge", "terasort/hadoop/bigdata",
                "logregr/spark/huge"]
        jobs = cluster_fleet(keys)
        refs, rngs, prios, rests, tables, spaces = [], [], [], [], [], []
        for job in jobs:
            prof = profile_job(job.profile_run, job.full_input_size)
            prio, rest = split_search_space(
                job.space, prof.model, job.full_input_size,
                per_node_overhead=job.per_node_overhead,
            )
            for seed in range(3):
                refs.append(
                    ruya_search(
                        job.space,
                        lambda i, t=job.cost_table: float(t[i]),
                        np.random.default_rng(seed), prio, rest,
                        to_exhaustion=True,
                    )
                )
                rngs.append(np.random.default_rng(seed))
                prios.append(list(prio))
                rests.append(list(rest))
                tables.append(job.cost_table)
                spaces.append(job.space)
        bt = batched_search(
            spaces, tables, rngs, priority=prios, remaining=rests,
            to_exhaustion=True,
        )
        for j, ref in enumerate(refs):
            assert_traces_equal(bt.job_trace(j), ref)


class TestFleetDriver:
    def test_replay_seeds_cherrypick_reports(self):
        from repro.fleet.driver import FleetJob

        job = FleetJob(name="quad", space=quad_space(), cost_table=quad_table())
        jobs, rngs = replay_seeds(job, range(3))
        reports = tune_fleet(jobs, rngs, mode="cherrypick",
                             settings=BOSettings(max_iters=8),
                             to_exhaustion=True)
        assert len(reports) == 3
        for rep in reports:
            assert rep.profile is None
            assert len(rep.priority) == N and not rep.remaining
            assert len(rep.trace.tried) == 8

    def test_engine_flags_agree(self):
        from repro.fleet.driver import FleetJob

        job = FleetJob(name="quad", space=quad_space(), cost_table=quad_table())
        jobs, _ = replay_seeds(job, range(3))
        st = BOSettings(max_iters=10)
        bat = tune_fleet(jobs, [np.random.default_rng(s) for s in range(3)],
                         mode="cherrypick", settings=st, to_exhaustion=True)
        seq = tune_fleet(jobs, [np.random.default_rng(s) for s in range(3)],
                         mode="cherrypick", settings=st, to_exhaustion=True,
                         engine="sequential")
        for b, s in zip(bat, seq):
            assert b.trace.tried == s.trace.tried
            assert b.trace.costs == s.trace.costs

    def test_rejects_mismatched_rngs(self):
        from repro.fleet.driver import FleetJob

        job = FleetJob(name="quad", space=quad_space(), cost_table=quad_table())
        with pytest.raises(ValueError):
            tune_fleet([job, job], [np.random.default_rng(0)])


_LAYOUT_ENTRY_POINTS = {
    "TuningSession": lambda layout: TuningSession(layout=layout),
    "fleet_step": lambda layout: fleet_step(*[None] * 11, layout=layout),
    "SequentialProbe": lambda layout: SequentialProbe(
        np.zeros((4, 2), np.float32), 2, layout=layout),
    "batched_search": lambda layout: batched_search(
        quad_space(), [quad_table()], [np.random.default_rng(0)],
        layout=layout),
}


@pytest.mark.parametrize("layout", ["feature", "fused"])
def test_lockstep_update_donates_its_state(layout):
    """The chunk update donates its state: after one call the input
    state's buffers are deleted (XLA aliased them to the outputs), so a
    chunk's steps update the mask and the packed trial, target and
    feature buffers in place instead of copying them."""
    n, rows, b = 16, 2, 6
    space, table = synth_space_table(n)
    enc = encode_features(space.encoded())
    d = enc.shape[1]
    state = FleetState(
        obs=jnp.zeros((rows, n), bool),
        tried=jnp.full((rows, b), -1, jnp.int32),
        py=jnp.zeros((rows, b), jnp.float32),
        feats=jnp.zeros((rows, b, d), jnp.float32),
        t=jnp.zeros(rows, jnp.int32),
        stop=jnp.full(rows, -1, jnp.int32),
        pb=jnp.full(rows, -1, jnp.int32),
        done=jnp.zeros(rows, bool),
        last_ei=jnp.zeros(rows, jnp.float32),
        last_best=jnp.full(rows, jnp.inf, jnp.float32),
    )
    old = (state.obs, state.tried, state.py, state.feats)
    new = _fleet_update(
        state, jnp.asarray(np.stack([enc] * rows)),
        jnp.asarray(np.stack([table] * rows), jnp.float32),
        jnp.ones((rows, n), bool), jnp.zeros((rows, n), bool),
        jnp.zeros((rows, 1), jnp.int32), jnp.zeros(rows, jnp.int32),
        jnp.full(rows, b, jnp.int32), jnp.asarray(0, jnp.int32),
        jnp.asarray(0.0, jnp.float32), jnp.asarray(True),
        xi=0.0, layout=layout,
    )
    jax.block_until_ready(new.t)
    assert [buf.is_deleted() for buf in old] == [True] * 4
    assert int(new.t[0]) == 1  # the step ran: one trial observed per row


@pytest.mark.parametrize("entry", sorted(_LAYOUT_ENTRY_POINTS))
def test_unknown_layout_is_refused(entry):
    """Every entry point that takes a layout knows exactly two, and
    names them when refusing another."""
    with pytest.raises(ValueError, match=r"\('feature', 'fused'\)"):
        _LAYOUT_ENTRY_POINTS[entry]("gather")


def linear_run_fn(slope_gb, base_gb=0.5, rate_s_per_gb=50.0):
    """Emulates a clean linear-memory job: sample_bytes -> (runtime, peak)."""

    def run(sample_bytes):
        gb = sample_bytes / GiB
        return rate_s_per_gb * gb, (slope_gb * gb + base_gb) * GiB

    return run


def flat_run_fn(base_gb=4.0, rate_s_per_gb=50.0):
    def run(sample_bytes):
        return rate_s_per_gb * sample_bytes / GiB, base_gb * GiB

    return run


class TestProfileCache:
    def test_same_pattern_hits(self):
        cache = ProfileCache()
        p1 = cache.get_or_profile(linear_run_fn(3.0), 100.0 * GiB)
        p2 = cache.get_or_profile(linear_run_fn(3.0), 100.0 * GiB)
        assert cache.misses == 1 and cache.hits == 1
        assert p2 is p1  # the expensive profile ran once

    def test_similar_slope_hits_same_bucket(self):
        cache = ProfileCache()
        cache.get_or_profile(linear_run_fn(3.0), 100.0 * GiB)
        cache.get_or_profile(linear_run_fn(3.2), 120.0 * GiB)
        assert cache.hits == 1 and cache.misses == 1

    def test_different_category_misses(self):
        cache = ProfileCache()
        cache.get_or_profile(linear_run_fn(3.0), 100.0 * GiB)
        cache.get_or_profile(flat_run_fn(), 100.0 * GiB)
        assert cache.hits == 0 and cache.misses == 2
        assert len(cache) == 2

    def test_very_different_slope_misses(self):
        cache = ProfileCache()
        cache.get_or_profile(linear_run_fn(1.0), 100.0 * GiB)
        cache.get_or_profile(linear_run_fn(8.0), 100.0 * GiB)
        assert cache.hits == 0 and cache.misses == 2

    def test_signature_of_model(self):
        m_lin = fit_memory_model([1.0, 2.0, 3.0], [3.0, 6.0, 9.0])
        m_flat = fit_memory_model([1.0, 2.0, 3.0], [5.0, 5.0, 5.0])
        s_lin = MemorySignature.of(m_lin)
        s_flat = MemorySignature.of(m_flat)
        assert s_lin.category == "linear"
        assert s_flat.category == "flat"
        assert s_lin != s_flat
