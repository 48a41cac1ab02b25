"""`Indices`: the outcome's priority/remaining pools as a read-only array.

A published `SearchOutcome` carries the index arrays the §III-D split made
at submit, uncopied and unboxed.  These tests hold the sequence to the
tuple it replaced (equality, membership, slicing, iteration, hash), the
serialized form to the legacy JSON at catalog scale, and publication to
sharing the submit-time buffer.
"""

import json

import numpy as np
import pytest

from repro.core.bayesopt import BOSettings
from repro.fleet import TuningSession
from repro.fleet.session import Indices, SearchOutcome, TrialRecord

from test_session import flat_job

CATALOG_N = 129_024

VALUES = (7, 0, 3, 11, 3)


@pytest.mark.parametrize("other", [
    VALUES, list(VALUES), np.asarray(VALUES), np.asarray(VALUES, np.int32),
    Indices(VALUES),
], ids=["tuple", "list", "ndarray", "ndarray-int32", "Indices"])
def test_equal_to_every_form_of_the_same_values(other):
    idx = Indices(VALUES)
    assert (idx == other) is True
    assert (idx != other) is False


@pytest.mark.parametrize("other", [
    VALUES[:-1], VALUES + (1,), list(VALUES[::-1]), np.asarray(VALUES) + 1,
    Indices(VALUES[1:]), (), (7, 0, 3, 11, "3"),
], ids=["shorter", "longer", "reversed", "shifted", "Indices-shorter",
        "empty", "str-element"])
def test_unequal_gives_a_bool(other):
    idx = Indices(VALUES)
    assert (idx == other) is False
    assert (idx != other) is True


def test_other_types_are_unequal():
    idx = Indices(VALUES)
    assert idx != "7, 0, 3, 11, 3"
    assert idx != 7
    assert idx is not None and idx != None  # noqa: E711
    assert Indices() == () and not Indices()


def test_sequence_reads_like_the_tuple():
    idx, tup = Indices(VALUES), VALUES
    assert len(idx) == len(tup) and bool(idx)
    assert [type(v) for v in idx] == [int] * len(tup)
    assert list(idx) == list(tup)
    assert idx[0] == tup[0] and idx[-1] == tup[-1]
    assert type(idx[np.int64(2)]) is int
    with pytest.raises(IndexError):
        idx[len(tup)]
    for s in (slice(1, 4), slice(None, None, -1), slice(5, 9)):
        assert isinstance(idx[s], Indices)
        assert idx[s] == tup[s]
    assert idx.index(3) == tup.index(3) and idx.count(3) == tup.count(3)
    assert list(reversed(idx)) == list(reversed(tup))
    assert hash(idx) == hash(tup)
    assert hash(Indices()) == hash(())
    assert repr(idx) == "Indices([7, 0, 3, 11, 3])"


@pytest.mark.parametrize("value, expected", [
    (11, True), (np.int64(0), True), (3.0, True), (4, False), (-1, False),
    ("3", False), (None, False),
])
def test_membership(value, expected):
    assert (value in Indices(VALUES)) is expected
    assert (value in VALUES) is expected


def test_backing_array_is_read_only_and_uncopied():
    idx = Indices(VALUES)
    a = np.asarray(idx)
    assert a.dtype == np.int64 and a.shape == (len(VALUES),)
    assert np.shares_memory(a, np.asarray(idx))
    with pytest.raises(ValueError):
        a[0] = 1
    with pytest.raises(ValueError):
        a[1:3] += 1
    assert idx == VALUES


def test_constructor_copies_its_input():
    src = np.asarray(VALUES, np.int64)
    idx = Indices(src)
    src[0] = 99
    assert idx == VALUES
    assert not np.shares_memory(np.asarray(idx), src)


def test_numpy_reads_it_as_the_array():
    idx = Indices(VALUES)
    table = np.arange(20) * 10
    assert table[np.asarray(idx)].tolist() == [10 * v for v in VALUES]
    assert np.array(idx, np.int32).dtype == np.int32
    copied = np.array(idx)
    copied[0] = -1  # a requested copy is the caller's own
    assert idx[0] == VALUES[0]


def test_catalog_scale_json_round_trip_is_the_legacy_form():
    rng = np.random.default_rng(18)
    mask = rng.random(CATALOG_N) < 0.37
    prio, rest = np.flatnonzero(mask), np.flatnonzero(~mask)
    out = SearchOutcome(
        name="catalog-job",
        records=[TrialRecord(index=int(prio[0]), cost=1.5, slot=0,
                             source="init")],
        seeded=[],
        stop_iteration=None,
        phase_boundary=1,
        priority=Indices(prio),
        remaining=Indices(rest),
    )
    # The legacy form: the split's tuples, serialized int by int.
    legacy = {
        "name": "catalog-job",
        "records": [r.as_dict() for r in out.records],
        "seeded": [],
        "stop_iteration": None,
        "phase_boundary": 1,
        "priority": [int(i) for i in tuple(prio.tolist())],
        "remaining": [int(i) for i in tuple(rest.tolist())],
        "status": "converged",
        "profile_attempts": 1,
        "retry_backoff_s": 0.0,
        "failure": None,
    }
    text = json.dumps(out.as_dict())
    assert text == json.dumps(legacy)
    back = SearchOutcome.from_dict(json.loads(text))
    assert isinstance(back.priority, Indices)
    assert isinstance(back.remaining, Indices)
    assert back.priority == tuple(prio.tolist())
    assert back.remaining == tuple(rest.tolist())
    assert len(back.priority) + len(back.remaining) == CATALOG_N
    assert json.dumps(back.as_dict()) == text
    assert back == out


def test_publication_shares_the_submit_time_arrays():
    session = TuningSession(settings=BOSettings(max_iters=6))
    h = session.submit(flat_job(), seed=3)
    (rec,) = session._pending
    prio, rest = rec.prio_idx, rec.rem_idx
    session.drain()
    out = h.outcome()
    assert isinstance(out.priority, Indices)
    assert prio.size and rest.size  # the split left both pools
    assert np.shares_memory(np.asarray(out.priority), prio)
    assert np.shares_memory(np.asarray(out.remaining), rest)
    assert out.priority == tuple(prio.tolist())
    assert out.remaining == tuple(rest.tolist())
    rep = out.report()
    assert np.shares_memory(np.asarray(rep.priority), prio)


def test_caller_writes_after_submit_do_not_reach_the_outcome():
    job = flat_job()
    n = len(job.cost_table)
    prio = np.arange(0, n, 2, dtype=np.int64)
    rest = np.arange(1, n, 2, dtype=np.int64)
    session = TuningSession(settings=BOSettings(max_iters=6))
    h = session.submit(job, seed=3, priority=prio, remaining=rest)
    want_p, want_r = tuple(prio.tolist()), tuple(rest.tolist())
    prio[:] = 0
    rest[:] = 1
    session.drain()
    out = h.outcome()
    assert out.priority == want_p
    assert out.remaining == want_r
    assert all(r.index in want_p for r in out.records[:3])
    prio[:] = 5
    assert out.priority == want_p
