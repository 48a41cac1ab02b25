"""Search-space split invariants (paper §III-D) — unit + hypothesis — and
the loop↔vectorized split identity that lets `TuningSession` narrow a
whole catalog at once while staying bit-identical to the host-split
drivers."""

import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.core.memory_model import MemoryCategory, MemoryModel, fit_memory_model
from repro.core.search_space import (
    Configuration,
    SearchSpace,
    split_priority_mask,
    split_search_space,
)


def make_space(mems):
    return SearchSpace(
        [
            Configuration(
                name=f"c{i}", features=(float(i), float(m)), total_memory=float(m),
                num_nodes=1,
            )
            for i, m in enumerate(mems)
        ]
    )


def model_with(category, slope=1.0, intercept=0.0, readings=(1.0, 2.0)):
    return MemoryModel(
        category=category, slope=slope, intercept=intercept, r2=1.0,
        sizes=(1.0, 2.0), readings=readings,
    )


class TestSplit:
    def test_unclear_means_no_split(self):
        space = make_space([10, 20, 30])
        prio, rest = split_search_space(
            space, model_with(MemoryCategory.UNCLEAR), 100.0
        )
        assert prio == [0, 1, 2] and rest == []

    def test_flat_picks_lowest_memory(self):
        space = make_space([50, 10, 40, 20, 30, 60, 70])
        prio, rest = split_search_space(
            space, model_with(MemoryCategory.FLAT), 100.0, flat_fraction=2 / 7
        )
        assert prio == [1, 3]  # the two lowest-memory configs
        assert set(prio) | set(rest) == set(range(7))

    def test_linear_prioritizes_sufficient_memory(self):
        space = make_space([10, 50, 100, 200])
        prio, rest = split_search_space(
            space, model_with(MemoryCategory.LINEAR, slope=1.0), 90.0, leeway=0.0
        )
        assert prio == [2, 3]

    def test_linear_requirement_above_all_goes_to_extremes(self):
        space = make_space(list(range(10, 110, 10)))  # 10..100
        prio, rest = split_search_space(
            space, model_with(MemoryCategory.LINEAR, slope=10.0), 1000.0,
            leeway=0.0, extreme_fraction=0.2,
        )
        # both the lowest and the highest memory configs are prioritized
        assert 0 in prio and 1 in prio and 8 in prio and 9 in prio
        assert len(prio) == 4

    def test_linear_requirement_met_by_all_degrades_to_baseline(self):
        space = make_space([100, 200, 300])
        prio, rest = split_search_space(
            space, model_with(MemoryCategory.LINEAR, slope=0.1), 10.0, leeway=0.0
        )
        assert prio == [0, 1, 2] and rest == []


class TestSplitProperties:
    @given(
        mems=st.lists(st.floats(1.0, 1e4), min_size=2, max_size=69),
        input_size=st.floats(1.0, 1e4),
        slope=st.floats(0.01, 10.0),
    )
    @settings(max_examples=60, deadline=None)
    def test_partition_is_exact(self, mems, input_size, slope):
        space = make_space(mems)
        for cat in MemoryCategory:
            prio, rest = split_search_space(
                space, model_with(cat, slope=slope), input_size
            )
            assert sorted(prio + rest) == list(range(len(mems)))
            assert not (set(prio) & set(rest))
            assert len(prio) >= 1

    @given(mems=st.lists(st.floats(1.0, 1e4), min_size=3, max_size=40))
    @settings(max_examples=40, deadline=None)
    def test_flat_group_is_memory_minimal(self, mems):
        space = make_space(mems)
        prio, rest = split_search_space(
            space, model_with(MemoryCategory.FLAT), 1.0, flat_fraction=0.15
        )
        if rest:
            assert max(mems[i] for i in prio) <= min(mems[j] for j in rest) + 1e-9


def assert_masks_match_host(space, model, input_size, **kw):
    prio, rest = split_search_space(space, model, input_size, **kw)
    mask = split_priority_mask(space, model, input_size, **kw)
    assert mask.dtype == bool and mask.shape == (len(space),)
    assert list(np.flatnonzero(mask)) == prio
    assert list(np.flatnonzero(~mask)) == rest


class TestDeviceSplitIdentity:
    """`split_priority_mask` (vectorized float64, stable sort) must reproduce
    `split_search_space` EXACTLY — the priority mask is the sorted-index
    host split bit-for-bit, every category and fallback included."""

    def random_space(self, n, seed, multi_node=True):
        rng = np.random.default_rng(seed)
        return SearchSpace(
            [
                Configuration(
                    name=f"c{i}",
                    features=(float(i),),
                    total_memory=float(rng.choice([1, 2, 4, 8, 16, 32, 64]))
                    * float(rng.integers(1, 9)) * 2.0**30,
                    num_nodes=int(rng.integers(1, 17)) if multi_node else 1,
                )
                for i in range(n)
            ]
        )

    def test_all_categories_and_fallbacks(self):
        for n in (3, 20, 69):
            for seed in range(4):
                space = self.random_space(n, seed)
                for cat in MemoryCategory:
                    for inp, slope in ((1.0, 0.01), (40 * 2.0**30, 1.0),
                                       (1e15, 10.0)):
                        assert_masks_match_host(
                            space, model_with(cat, slope=slope), inp,
                            per_node_overhead=0.5 * 2.0**30,
                        )

    def test_borderline_requirement_equality(self):
        """Configs whose memory EQUALS the float64 requirement must land on
        the same side of the ≥ as the host rule (this is what float32 — or
        a TPU's float32-pair emulation of float64 — could get wrong, and
        why the split runs in IEEE float64 on the host)."""
        model = model_with(MemoryCategory.LINEAR, slope=3.0,
                           intercept=1.23456789e9)
        inp = 17.123456789e9
        req = model.estimate(inp) * 1.1 + 0.5 * 2.0**30 * 4
        space = SearchSpace(
            [
                Configuration(name="eq", features=(0.0,),
                              total_memory=float(req), num_nodes=4),
                Configuration(name="below", features=(1.0,),
                              total_memory=float(np.nextafter(req, 0.0)),
                              num_nodes=4),
                Configuration(name="above", features=(2.0,),
                              total_memory=float(np.nextafter(req, np.inf)),
                              num_nodes=4),
            ]
        )
        assert_masks_match_host(
            space, model, inp, leeway=0.10,
            per_node_overhead=0.5 * 2.0**30,
        )

    def test_flat_stable_ties(self):
        """Equal memories: the stable argsort must break ties like
        np.argsort(kind='stable') — first occurrence wins."""
        space = make_space([5.0, 1.0, 1.0, 1.0, 5.0, 1.0, 9.0])
        assert_masks_match_host(
            space, model_with(MemoryCategory.FLAT), 1.0, flat_fraction=0.3
        )

    def test_cluster_catalog_splits(self):
        """The paper's real 69-config catalog, every profiled workload."""
        from repro.cluster.simulator import ClusterSimulator
        from repro.core.profiler import profile_job

        for key in ("kmeans/spark/huge", "terasort/hadoop/bigdata",
                    "pagerank/spark/huge"):
            sim = ClusterSimulator.for_job(key)
            GiB = 2.0**30
            prof = profile_job(sim.profile_run_fn(), sim.job.input_gb * GiB)
            assert_masks_match_host(
                sim.space, prof.model, sim.job.input_gb * GiB,
                per_node_overhead=0.5 * GiB,
            )

    @given(
        mems=st.lists(st.floats(1.0, 1e12), min_size=2, max_size=69),
        input_size=st.floats(1.0, 1e12),
        slope=st.floats(0.01, 10.0),
    )
    @settings(max_examples=40, deadline=None)
    def test_identity_property(self, mems, input_size, slope):
        space = make_space(mems)
        for cat in MemoryCategory:
            assert_masks_match_host(
                space, model_with(cat, slope=slope), input_size
            )

    def test_identity_seeded_lane(self):
        """Always-on randomized lane (mirrors the hypothesis property when
        hypothesis is unavailable)."""
        rng = np.random.default_rng(7)
        for _ in range(12):
            n = int(rng.integers(2, 40))
            mems = (10.0 ** rng.uniform(0, 12, size=n)).tolist()
            space = make_space(mems)
            for cat in MemoryCategory:
                assert_masks_match_host(
                    space,
                    model_with(cat, slope=float(10.0 ** rng.uniform(-2, 1))),
                    float(10.0 ** rng.uniform(0, 12)),
                )
