"""Golden-trace differential tests (`pytest -m golden`): every engine
variant must reproduce the committed fixtures bit-for-bit.

This is the acceptance harness of the sharded fleet engine: the three
pinned scenarios (n = 69 exhaustion, n = 512 budgeted two-phase, and a
streaming warm-start session — `tests/golden/scenarios.py`) are replayed
through the unsharded reference AND across shard counts 2/4, on both
layouts — "feature" and the "fused" streaming-kernel lane
(`repro.kernels.ei_argmax`) — and compared to `tests/golden/*.json` with
the shared `assert_outcomes_match` helper.  The sequential per-job engine
is pinned against the same fixtures, which closes the loop:

    sequential == golden == session(layout × shard count)

Fixtures regenerate via `PYTHONPATH=src python -m tests.golden.regen`
(which re-runs the sequential cross-check before writing); drift in a
regenerated fixture means the reference numerics changed and must be an
explicit, reviewed decision.

These tests run in the default tier-1 lane and are additionally selectable
alone with `-m golden`.  Shard lanes need the multi-device CPU topology
`conftest.py` forces (guarded with a skip for exotic invocations).
"""

import numpy as np
import pytest

import jax

from golden import assert_outcomes_match, assert_traces_match, load
from golden.scenarios import (
    SCENARIOS, run_elastic_fleet_disturbed, synth_space_table,
)
from repro.core.bayesopt import BOSettings, cherrypick_search, ruya_search

pytestmark = pytest.mark.golden

# The fault-reporting fields honestly differ under injected faults (a
# retried profile returns identical results but more attempts); the
# bit-identity claim is about the search trace.
FAULT_FIELDS = ("profile_attempts", "retry_backoff_s")

SHARD_COUNTS = (None, 2, 4)  # None = the single-device reference path


def _need_devices(shard):
    if shard is not None and jax.device_count() < shard:
        pytest.skip(
            f"needs {shard} devices; XLA_FLAGS force-count not in effect"
        )


@pytest.mark.parametrize("shard", SHARD_COUNTS)
@pytest.mark.parametrize("layout", ("feature", "fused"))
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_scenario_matches_golden(scenario, layout, shard):
    _need_devices(shard)
    outcomes = SCENARIOS[scenario](layout=layout, shard=shard)
    assert_outcomes_match(scenario, outcomes)


class TestSequentialReference:
    """The per-job sequential engine reproduces the golden fixtures (a
    2-job prefix keeps the Python-loop engine's cost down; the full-width
    fleet identity rides the session lanes above)."""

    def test_n69_exhaustion_sequential(self):
        space, table = synth_space_table(69)
        traces = [
            cherrypick_search(
                space, lambda i: float(table[i]), np.random.default_rng(s),
                to_exhaustion=True,
            )
            for s in range(2)
        ]
        assert_traces_match("n69-exhaustion", traces, jobs=[0, 1])

    def test_n512_budgeted_sequential(self):
        space, table = synth_space_table(512)
        st = BOSettings(max_iters=10)
        prio = list(range(0, 50))
        rest = list(range(50, 512))
        traces = [
            ruya_search(
                space, lambda i: float(table[i]), np.random.default_rng(s),
                prio, rest, settings=st, to_exhaustion=True,
            )
            for s in range(2)
        ]
        assert_traces_match("n512-budgeted", traces, jobs=[0, 1])


@pytest.mark.chaos
class TestDisturbedFleet:
    """The adversarial replay of ``elastic-fleet``: survivors of a fleet
    hit by transient profiling faults, a mid-flight cancellation, and live
    device churn must reproduce the undisturbed fixture bit-for-bit."""

    def test_shard_loss_survivors_bit_identical(self):
        _need_devices(2)
        survivors, victim = run_elastic_fleet_disturbed(
            shard=2, reshard_to=None,
        )
        assert_outcomes_match("elastic-fleet", survivors, ignore=FAULT_FIELDS)
        assert victim.status == "cancelled"
        assert victim.records, "victim should have partial trials"

    def test_device_join_survivors_bit_identical(self):
        _need_devices(2)
        survivors, victim = run_elastic_fleet_disturbed(
            shard=None, reshard_to=2,
        )
        assert_outcomes_match("elastic-fleet", survivors, ignore=FAULT_FIELDS)
        assert victim.status == "cancelled"

    def test_fault_reporting_surfaces(self):
        _need_devices(2)
        survivors, _ = run_elastic_fleet_disturbed()
        # e0 and e3 were wrapped with 2 scripted transient failures each:
        # 3 attempts, positive charged backoff, identical profile (the
        # trace identity above is the proof), clean jobs untouched.
        for j in (0, 3):
            assert survivors[j].profile_attempts == 3
            assert survivors[j].retry_backoff_s > 0.0
        for j in (1, 2, 4, 5, 6, 7):
            assert survivors[j].profile_attempts == 1
            assert survivors[j].retry_backoff_s == 0.0
        assert all(s.status == "converged" for s in survivors)


class TestFixtureIntegrity:
    def test_fixtures_declare_their_regen_path(self):
        for name in SCENARIOS:
            d = load(name)
            assert d["scenario"] == name
            assert "tests.golden.regen" in d["regen"]
            assert d["outcomes"], f"{name}: empty fixture"

    def test_warm_session_fixture_is_really_warm(self):
        """The streaming scenario must pin actual warm-start behavior:
        seeded jobs exist, their seeds carry donor costs, and the cold
        CherryPick neighbors sharing their chunks are unseeded."""
        outs = load("warm-session")["outcomes"]
        warm = [o for o in outs if o["seeded"]]
        cold = [o for o in outs if not o["seeded"]]
        assert len(warm) == 2 and len(cold) == 5
        for o in warm:
            assert all(s["source"] == "warm" for s in o["seeded"])
            assert len(o["records"]) == 0  # fully amortized on this class
