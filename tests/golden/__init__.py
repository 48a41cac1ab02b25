"""Golden-trace differential harness for the fleet/session engines.

The committed JSON fixtures under this directory pin the engines'
bit-exact behavior on three scenarios (see `.scenarios`): every engine
variant — unsharded/sharded, feature/fused layout, session API or legacy
shim — must reproduce the fixture traces verbatim.  `assert_outcomes_match`
is THE assertion every lane uses; `assert_traces_match` adapts it to the
legacy `SearchTrace` view for the `batched_search`/`tune_fleet` shims.

Fixtures are regenerated with

    PYTHONPATH=src python -m tests.golden.regen

which re-derives every scenario from the unsharded feature-layout session
AND cross-checks the sequential reference engine (`cherrypick_search` /
`ruya_search`) against it before writing anything — so a fixture can only
change when the reference numerics deliberately change, and the diff shows
up in review.
"""

import json
import os

GOLDEN_DIR = os.path.dirname(os.path.abspath(__file__))


def fixture_path(name: str) -> str:
    return os.path.join(GOLDEN_DIR, f"{name}.json")


def load(name: str) -> dict:
    with open(fixture_path(name)) as f:
        return json.load(f)


def golden_outcome_dicts(name: str):
    """The fixture's outcomes, in submission order, as plain dicts
    (`SearchOutcome.as_dict` form — JSON round-tripped, so float-exact)."""
    return load(name)["outcomes"]


def assert_outcomes_match(name: str, outcomes, jobs=None, ignore=()) -> None:
    """Assert `SearchOutcome`s reproduce the golden fixture bit-for-bit.

    ``outcomes`` is the submission-ordered list an engine produced;
    ``jobs`` optionally selects a subset of fixture indices (for lanes
    that only run a prefix/slice of the pinned fleet).  ``ignore`` drops
    the named top-level keys from BOTH sides before comparing — the
    disturbed-fleet lanes use it for the fault-reporting fields
    ("profile_attempts", "retry_backoff_s"): a retried profile returns
    identical results but honestly reports more attempts, and the
    bit-identity claim is about the SEARCH trace.
    """
    want = golden_outcome_dicts(name)
    idx = list(range(len(want))) if jobs is None else list(jobs)
    assert len(outcomes) == len(idx), (
        f"{name}: got {len(outcomes)} outcomes for fixture rows {idx}"
    )
    for j, out in zip(idx, outcomes):
        got = json.loads(json.dumps(out.as_dict()))
        ref = dict(want[j])
        for key in ignore:
            got.pop(key, None)
            ref.pop(key, None)
        if got != ref:
            raise AssertionError(
                f"golden mismatch: scenario {name!r} job {j} "
                f"({want[j]['name']!r})\n  want: {ref}\n  got:  {got}"
            )


def golden_traces(name: str):
    """Fixture outcomes as legacy `SearchTrace`s (the `.trace()` view)."""
    from repro.fleet.session import SearchOutcome

    return [
        SearchOutcome.from_dict(d).trace() for d in golden_outcome_dicts(name)
    ]


def assert_traces_match(name: str, traces, jobs=None) -> None:
    """Assert legacy `SearchTrace`s match the fixture's `.trace()` views —
    the same fixture `assert_outcomes_match` pins, adapted for the
    pre-session shim types (`batched_search`, `run_*`, `tune_fleet`)."""
    want = golden_traces(name)
    idx = list(range(len(want))) if jobs is None else list(jobs)
    assert len(traces) == len(idx), (
        f"{name}: got {len(traces)} traces for fixture rows {idx}"
    )
    for j, tr in zip(idx, traces):
        ref = want[j]
        assert tr.tried == ref.tried, f"{name} job {j}: tried differ"
        assert tr.costs == ref.costs, f"{name} job {j}: costs differ"
        assert tr.stop_iteration == ref.stop_iteration, (
            f"{name} job {j}: stop_iteration differs"
        )
        assert tr.phase_boundary == ref.phase_boundary, (
            f"{name} job {j}: phase_boundary differs"
        )
