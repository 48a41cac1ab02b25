"""`TuningSession`: one streaming session API over every tuning path.

Ruya's workflow is inherently incremental — profile, narrow, iterate BO
until convergence — but the repo historically exposed it as three one-shot
entry points (`run_ruya`, `run_cherrypick`, `tune_fleet`) that assume every
job is known up front.  The session turns tuning into a service:

    session = TuningSession(cache=ProfileCache(), warm_start=True)
    handle  = session.submit(job, seed=0)     # profile → split → enqueue
    session.step()                            # ONE batched BO iteration for
                                              # every live search; newly
                                              # submitted jobs are admitted
                                              # into lockstep chunks between
                                              # steps
    outcomes = session.drain()                # step until everything is done
    handle.outcome().records                  # first-class TrialRecords

Execution model.  Submitted jobs wait in a pending queue; at the next
`step()` they are grouped by (space shape, packed capacity B) — the same
grouping rule as `repro.fleet.batched_engine` — and formed into lockstep
chunks of ≤ `_CHUNK` jobs.  Each `step()` applies the donated, vmapped
`fast_bo.fleet_step` update once to every live chunk, so the whole session
advances one BO iteration per call with no data-dependent host decisions;
chunks retire when their step budget is exhausted (or, with early stopping,
when a periodic poll of the on-device done flags comes back all-True).
Draining a statically submitted fleet therefore replays `batched_search`'s
exact array program — same grouping, same chunking, same scripted-init
draws in submission order, same singleton dummy padding, same jitted update
— and is bitwise trace-identical to the pre-session engines
(`tests/test_session.py` pins this seed-for-seed against the sequential
engine for both layouts).

Cross-job warm-starting (Flora's signature classes, Blink's recurring-job
amortization).  The session owns the tuning state: give it a
`ProfileCache` to share probe-classified profiles across jobs (without
one, each distinct job profiles exactly once, like the one-shot drivers);
either way every profiled job gets a `MemorySignature`, and completed
trials are logged per (signature, space shape) class.  A job submitted into a class with history is *seeded*: its
packed `(B,)` trial/target buffers and `(B,d)` feature buffer start
pre-filled with up to B − reserve class trials (capacity-aware — the seeds
consume packed slots and trial budget, so a seeded search runs at the same
static extents as a cold one), its observation mask marks the seeded
configs, and the scripted random initialization is skipped — the GP opens
with the class's knowledge and typically fires the EI convergence
threshold after a handful of fresh trials.  Seeding preserves `fast_bo`'s
exact padding rules: seeded slots are ordinary observations (slots < t),
written with the same canonical float32 encoding rows an on-device
observation would have produced.  A warm-started search is a deterministic
function of (class history, seed): the history is ordered by completion,
deduplicated by config index, and truncated capacity-aware, and no RNG is
consumed when seeding happens.

Memory-aware narrowing is vectorized: the §III-D priority split comes from
`repro.core.search_space.split_priority_mask` (float64 NumPy on the host,
bit-equal to the host rule), so admission cost scales with the catalog — no
Python loop over 10⁴–10⁵ configurations.

`run_ruya` / `run_cherrypick` / `tune_fleet` / `batched_search` remain as
thin deprecation shims over this engine.
"""

from __future__ import annotations

import dataclasses
import hashlib
import time
import weakref
from typing import (
    Callable, Dict, List, Optional, Sequence, Set, Tuple, TYPE_CHECKING,
    Union,
)

import numpy as np

import jax

from repro.core.bayesopt import BOSettings, SearchTrace, trial_budget
from repro.core.fast_bo import _LAYOUTS, FleetState, encode_features
from repro.core.profiler import (
    ProfileResult,
    ProfilingRunError,
    profile_job,
)
from repro.core.search_space import split_priority_mask
from repro.core.tuner import RuyaReport
# The jitted lockstep update and the chunking constants are shared verbatim
# with the pre-session engine (see `repro.fleet.batched_engine` for why 8:
# f32 numerics are batch-extent-invariant only in [2, 8] on XLA:CPU, and
# chunks of one are padded with an inert dummy because extent-1 programs
# compile to different float32 numerics).
from repro.fleet.batched_engine import _CHUNK, _POLL_PERIOD, _fleet_update
from repro.fleet.profile_cache import MemorySignature, ProfileCache
from repro.fleet.retry import RetryPolicy, RetryStats, call_with_retry
from repro.fleet.sharding import (
    collapse_rows,
    resolve_shard_devices,
    sharded_update,
)
from repro.fleet.staging import pack, stage
from repro.fleet.telemetry import (
    GroupCounters, Telemetry, TimedLock, ei_work, head_slots, recording,
    span,
)

if TYPE_CHECKING:  # import cycle: driver imports session for tune_fleet
    from repro.fleet.driver import FleetJob

__all__ = [
    "FleetFailedError",
    "JobHandle",
    "SearchOutcome",
    "TrialRecord",
    "TuningSession",
    "canonical_objective",
    "objective_table",
]

_TRIAL_SOURCES = ("init", "search", "warm")

# A tuning objective is "runtime" (the legacy table — every committed
# golden trace), "cost" (runtime×price under the job's catalog), or a
# weight mapping over both.  The canonical form is the string, or a
# sorted tuple of (axis, weight) pairs — hashable, so it can extend the
# warm-start class key (histories from different objectives score trials
# on different scales and must never cross-seed).
Objective = Union[str, Tuple[Tuple[str, float], ...]]
_OBJECTIVE_AXES = ("runtime", "cost")


def canonical_objective(objective) -> Objective:
    """Validate and canonicalize an objective spec (see `Objective`)."""
    if isinstance(objective, str):
        if objective not in _OBJECTIVE_AXES:
            raise ValueError(
                f"unknown objective {objective!r}; want one of "
                f"{_OBJECTIVE_AXES} or a weight mapping over them"
            )
        return objective
    if isinstance(objective, tuple):
        objective = dict(objective)
    if isinstance(objective, dict):
        extra = set(objective) - set(_OBJECTIVE_AXES)
        if extra or not objective:
            raise ValueError(
                f"objective weights must be over {_OBJECTIVE_AXES}, got "
                f"{sorted(objective) if objective else 'no axes'}"
            )
        weights = {k: float(v) for k, v in objective.items()}
        if min(weights.values()) < 0.0 or sum(weights.values()) <= 0.0:
            raise ValueError(
                f"objective weights must be >= 0 with a positive sum, "
                f"got {weights}"
            )
        return tuple(sorted(weights.items()))
    raise TypeError(
        f"objective must be a string or a weight mapping, got "
        f"{type(objective).__name__}"
    )


def objective_table(job: "FleetJob", objective: Objective) -> np.ndarray:
    """The (n,) float64 score table a search over ``job`` observes.

    ``"runtime"`` is the job's own ``cost_table``, byte-for-byte — the
    pinned legacy path.  ``"cost"`` scores by runtime×price from the
    job's pricing axes, normalized by its minimum (the same conditioning
    the legacy tables have); a weight mapping blends the two normalized
    axes.  Non-runtime objectives need a priced job (build one via
    `cluster_fleet(..., catalog=...)`).
    """
    obj = canonical_objective(objective)
    table = np.asarray(job.cost_table, np.float64)
    if obj == "runtime":
        return table
    rt = getattr(job, "runtime_table", None)
    price = getattr(job, "price_table", None)
    if rt is None or price is None:
        raise ValueError(
            f"job {job.name!r}: objective {objective!r} needs the job's "
            "runtime_table and price_table pricing axes — build priced "
            "jobs via cluster_fleet(..., catalog=...) or set both fields"
        )
    usd = np.asarray(rt, np.float64) * np.asarray(price, np.float64)
    usd_norm = usd / usd.min()
    if obj == "cost":
        return usd_norm
    weights = dict(obj)
    rt_norm = table / table.min()
    total = sum(weights.values())
    return (
        weights.get("runtime", 0.0) * rt_norm
        + weights.get("cost", 0.0) * usd_norm
    ) / total

# Terminal status of a search.  "converged" is the normal retirement (EI
# threshold fired or trial budget exhausted); the other three are
# first-class partial results: "cancelled" (caller revoked the job),
# "failed" (profiling failed permanently / retry budget exhausted, or an
# external executor died mid-flight), "preempted" (evicted for a
# higher-priority job — resubmit to continue from the class history).
_STATUSES = ("converged", "cancelled", "failed", "preempted")


class FleetFailedError(RuntimeError):
    """`drain()` was waiting exclusively on jobs that permanently failed.

    Partial fleets keep going — one broken job must not sink its
    chunk-mates — so failures surface as first-class "failed" outcomes.
    But when EVERY job live at the drain call ends "failed", returning
    normally would read as success; the session raises this instead (the
    outcomes stay available via `results()`)."""


@dataclasses.dataclass(frozen=True)
class TrialRecord:
    """One observation: which config, what it cost, when, and why.

    ``slot`` is the packed-buffer slot (= engine trial counter value when the
    observation was made, warm seeds included).  ``source`` is "init"
    (scripted random initialization), "search" (BO pick), or "warm" (seeded
    from the signature class's history — the cost is the donor's).
    ``attempts`` is the number of cluster runs the trial took (> 1 when a
    straggler run was re-dispatched — reported latency only, the observed
    cost is always the deterministic table value).

    ``runtime_h``/``usd`` are the trial's RAW axes — hours and dollars
    under the job's price catalog — populated only for priced jobs
    (`FleetJob.runtime_table`/`price_table` set); ``cost`` stays the
    objective's score.  Unpriced records serialize without the two keys,
    so every committed golden fixture round-trips unchanged.
    """

    index: int
    cost: float
    slot: int
    source: str = "search"
    attempts: int = 1
    runtime_h: Optional[float] = None
    usd: Optional[float] = None

    def as_dict(self) -> dict:
        d = {
            "index": int(self.index),
            "cost": float(self.cost),
            "slot": int(self.slot),
            "source": str(self.source),
            "attempts": int(self.attempts),
        }
        if self.runtime_h is not None:
            d["runtime_h"] = float(self.runtime_h)
        if self.usd is not None:
            d["usd"] = float(self.usd)
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "TrialRecord":
        src = str(d["source"])
        if src not in _TRIAL_SOURCES:
            raise ValueError(f"unknown trial source {src!r}")
        rt = d.get("runtime_h")
        usd = d.get("usd")
        return cls(
            index=int(d["index"]), cost=float(d["cost"]),
            slot=int(d["slot"]), source=src,
            attempts=int(d.get("attempts", 1)),
            runtime_h=None if rt is None else float(rt),
            usd=None if usd is None else float(usd),
        )


@dataclasses.dataclass
class SearchOutcome:
    """Everything one finished search produced — subsumes
    `SearchTrace`/`RuyaReport` (both are views: `trace()` / `report()`).

    ``records`` are the trials THIS search executed (sources "init" and
    "search"), in trial order; ``seeded`` are the warm-start seeds that
    pre-filled the packed buffers (source "warm", donor costs).
    ``stop_iteration`` / ``phase_boundary`` are the engine's registers and
    count packed slots — i.e. seeds included; `trace()` re-bases them onto
    the executed trials so cold searches round-trip exactly.

    ``status`` (see `_STATUSES`) makes partial results first-class: a
    cancelled/failed/preempted search still carries every trial it
    completed.  ``profile_attempts`` / ``retry_backoff_s`` surface what
    the profiling phase cost under faults (1 / 0.0 = clean first try; the
    backoff is charged, not slept — see `repro.fleet.retry`), and
    ``failure`` carries the terminal error text for "failed" outcomes.

    ``objective`` is the canonical objective the search scored trials
    under (see `canonical_objective`); ``currency`` is set ("USD") for
    priced jobs, whose records carry raw runtime/dollar axes — the inputs
    to `pareto()`, `best_usd` and `best_runtime_h`.  Both serialize only
    when non-default, so unpriced runtime-objective outcomes (every
    committed golden fixture) keep their exact legacy `as_dict` form.
    """

    name: str
    records: List[TrialRecord]
    seeded: List[TrialRecord]
    stop_iteration: Optional[int]
    phase_boundary: Optional[int]
    priority: Tuple[int, ...]
    remaining: Tuple[int, ...]
    profile: Optional[ProfileResult] = None
    signature: Optional[MemorySignature] = None
    status: str = "converged"
    profile_attempts: int = 1
    retry_backoff_s: float = 0.0
    failure: Optional[str] = None
    objective: Objective = "runtime"
    currency: Optional[str] = None

    @property
    def memory_model(self):
        return None if self.profile is None else self.profile.model

    @property
    def observations(self) -> List[TrialRecord]:
        """Seeds + executed trials, in packed-slot order."""
        return list(self.seeded) + list(self.records)

    def _require_observations(self) -> List[TrialRecord]:
        obs = self.observations
        if not obs:
            raise RuntimeError(
                f"job {self.name!r} has no observations (status "
                f"{self.status!r}) — a search that failed or was revoked "
                "before its first trial has no best configuration"
            )
        return obs

    @property
    def best_cost(self) -> float:
        """Lowest recorded cost over seeds + executed trials (seeds carry
        donor costs — for recurring same-class jobs these are the point)."""
        return min(r.cost for r in self._require_observations())

    @property
    def best_index(self) -> int:
        return min(self._require_observations(), key=lambda r: r.cost).index

    def iterations_until(self, threshold_cost: float) -> Optional[int]:
        """1-based EXECUTED trial at which cost ≤ threshold was first seen
        (seeds excluded — this measures what the search itself had to do)."""
        for i, r in enumerate(self.records):
            if r.cost <= threshold_cost:
                return i + 1
        return None

    def _priced_observations(self) -> List[TrialRecord]:
        obs = [
            r for r in self._require_observations()
            if r.runtime_h is not None and r.usd is not None
        ]
        if not obs:
            raise RuntimeError(
                f"job {self.name!r} has no priced observations — runtime/"
                "cost axes exist only for jobs built with a price catalog "
                "(cluster_fleet(..., catalog=...))"
            )
        return obs

    def pareto(self) -> List[TrialRecord]:
        """The cost/runtime Pareto front: observed trials not dominated on
        the two RAW axes (hours, dollars), in trial order.

        A trial dominates another when it is no worse on both axes and
        strictly better on at least one.  Ties on both axes keep only the
        earliest trial (deterministic tie-break by trial order), so the
        front is a pure function of the observation sequence.
        """
        obs = self._priced_observations()
        front: List[TrialRecord] = []
        for i, r in enumerate(obs):
            dominated = False
            for j, o in enumerate(obs):
                if o.runtime_h <= r.runtime_h and o.usd <= r.usd and (
                    o.runtime_h < r.runtime_h or o.usd < r.usd
                ):
                    dominated = True
                    break
                # Exact tie on both axes: the earliest trial represents it.
                if (
                    j < i
                    and o.runtime_h == r.runtime_h
                    and o.usd == r.usd
                ):
                    dominated = True
                    break
            if not dominated:
                front.append(r)
        return front

    @property
    def best_usd(self) -> float:
        """Cheapest observed trial in dollars (priced jobs only)."""
        return min(r.usd for r in self._priced_observations())

    @property
    def best_runtime_h(self) -> float:
        """Fastest observed trial in hours (priced jobs only)."""
        return min(r.runtime_h for r in self._priced_observations())

    def trace(self) -> SearchTrace:
        """The executed trials as the legacy `SearchTrace` (bit-exact for
        cold searches; warm searches re-base the registers past the seeds)."""
        w = len(self.seeded)
        stop = self.stop_iteration
        pb = self.phase_boundary
        return SearchTrace(
            tried=[r.index for r in self.records],
            costs=[r.cost for r in self.records],
            stop_iteration=None if stop is None else max(stop - w, 0),
            phase_boundary=None if pb is None else max(pb - w, 0),
        )

    def report(self) -> RuyaReport:
        """The legacy `RuyaReport` view (single-job / fleet driver output)."""
        return RuyaReport(
            profile=self.profile,
            priority=self.priority,
            remaining=self.remaining,
            trace=self.trace(),
        )

    def as_dict(self) -> dict:
        """JSON-able view; drops `profile`/`signature` (not serializable).
        The cost-aware fields ("objective", "currency") are emitted only
        when non-default, so legacy fixtures compare byte-for-byte."""
        d = {
            "name": self.name,
            "records": [r.as_dict() for r in self.records],
            "seeded": [r.as_dict() for r in self.seeded],
            "stop_iteration": self.stop_iteration,
            "phase_boundary": self.phase_boundary,
            "priority": [int(i) for i in self.priority],
            "remaining": [int(i) for i in self.remaining],
            "status": str(self.status),
            "profile_attempts": int(self.profile_attempts),
            "retry_backoff_s": float(self.retry_backoff_s),
            "failure": self.failure,
        }
        if self.objective != "runtime":
            d["objective"] = (
                self.objective if isinstance(self.objective, str)
                else dict(self.objective)
            )
        if self.currency is not None:
            d["currency"] = str(self.currency)
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "SearchOutcome":
        stop = d["stop_iteration"]
        pb = d["phase_boundary"]
        status = str(d.get("status", "converged"))
        if status not in _STATUSES:
            raise ValueError(f"unknown outcome status {status!r}")
        failure = d.get("failure")
        currency = d.get("currency")
        return cls(
            name=str(d["name"]),
            records=[TrialRecord.from_dict(r) for r in d["records"]],
            seeded=[TrialRecord.from_dict(r) for r in d["seeded"]],
            stop_iteration=None if stop is None else int(stop),
            phase_boundary=None if pb is None else int(pb),
            priority=tuple(int(i) for i in d["priority"]),
            remaining=tuple(int(i) for i in d["remaining"]),
            status=status,
            profile_attempts=int(d.get("profile_attempts", 1)),
            retry_backoff_s=float(d.get("retry_backoff_s", 0.0)),
            failure=None if failure is None else str(failure),
            objective=canonical_objective(d.get("objective", "runtime")),
            currency=None if currency is None else str(currency),
        )


@dataclasses.dataclass
class JobHandle:
    """Ticket for one submitted job; query it any time.

    The session is held through a weakref and the outcome is attached to
    the handle at retirement, so handles never keep a drained session (and
    its cached device geometry) alive — one-shot shims create a session per
    call, and it must be reclaimed by refcount the moment the call returns.
    """

    uid: int
    name: str
    _session: "weakref.ref[TuningSession]" = dataclasses.field(repr=False)
    _outcome: Optional[SearchOutcome] = dataclasses.field(
        default=None, repr=False
    )

    @property
    def done(self) -> bool:
        return self._outcome is not None

    @property
    def status(self) -> str:
        if self.done:
            st = self._outcome.status
            return "done" if st == "converged" else st
        session = self._session()
        if session is None:
            return "detached"  # session dropped before the job finished
        with session._lock:
            if any(r.handle.uid == self.uid for r in session._pending):
                return "pending"
        return "running"

    def cancel(self) -> bool:
        """Cancel this job — pending or mid-flight (see
        `TuningSession.cancel`).  Returns False when the job already
        finished or the session is gone; cancelling twice is a no-op."""
        session = self._session()
        if session is None:
            return False
        return session.cancel(self)

    def outcome(self) -> SearchOutcome:
        if self._outcome is None:
            raise RuntimeError(
                f"job {self.name!r} (uid {self.uid}) has not finished — "
                "call session.step()/drain() first"
            )
        return self._outcome


@dataclasses.dataclass
class _JobRec:
    """Internal per-job state between submit and retire."""

    handle: JobHandle
    job: "FleetJob"
    table64: np.ndarray  # (n,) float64 — authoritative cost table
    enc: np.ndarray  # (n,d) canonical float32 encoding (encode_features)
    prio_mask: np.ndarray  # (n,) bool
    rem_mask: np.ndarray  # (n,) bool
    init_list: List[int]
    seed_trials: List[TrialRecord]
    budget: int  # trial budget == packed capacity B (trial_budget)
    profile: Optional[ProfileResult]
    signature: Optional[MemorySignature]
    class_key: Optional[Tuple[MemorySignature, int, int]]
    prio_idx: np.ndarray  # (p,) int64, pool order
    rem_idx: np.ndarray  # (r,) int64, pool order
    profile_attempts: int = 1  # profiling attempts incl. retries
    retry_backoff_s: float = 0.0  # charged profiling backoff
    status: str = "converged"  # terminal status, set before publication
    job_priority: int = 0  # preemption rank (see preempt_below)
    objective: Objective = "runtime"  # canonical scoring objective
    # (runtime_h, usd) raw-axis tables for priced jobs; None otherwise.
    axes64: Optional[Tuple[np.ndarray, np.ndarray]] = None


class _LiveChunk:
    """One lockstep chunk (or sharded chunk bundle) mid-flight.

    ``update`` is the jitted step program — the donated single-device
    `_fleet_update` for a plain chunk, or the `shard_map` bundle update
    (`repro.fleet.sharding.sharded_update`) when the session shards the
    job axis.  Member i always lives at flat row i of the state buffers
    once any leading shard axis is collapsed (`_retire` reshapes to
    (-1, ...)): shards slice the member list contiguously and dummy pads
    only trail the last rows of a shard — so retirement is layout-agnostic
    with no explicit row map.

    A member slot holds None after a mid-flight cancel/fail/preempt: the
    outcome was already published, the row's `done` flag is latched on
    device (the update leaves done rows untouched), and retirement skips
    the tombstone.  ``n_shards`` records the leading shard axis extent
    (1 = plain single-device chunk) for host-side row collapsing.

    ``group_key`` is the admission-group identity ((space shape, packed
    capacity)) — the unit the async service schedules: every chunk of one
    key is stepped by the same group thread (`repro.fleet.service`).
    ``t_admit`` is each row's observation count at admission, which with
    the count at retirement gives the group's `head_slots` counter.
    ``budget`` is each row's trial budget and ``polls`` the done flags
    read at each poll, as (steps done, flat flags): with ``t_admit`` they
    give the EI tail's rows and slots per dispatch (`telemetry.ei_work`).
    """

    __slots__ = ("state", "args", "members", "capacity", "update",
                 "steps_done", "steps_needed", "n_shards", "group_key",
                 "t_admit", "budget", "polls")

    def __init__(self, state, args, members, capacity, update,
                 steps_needed, n_shards=1, group_key=None, t_admit=None,
                 budget=None):
        self.state = state
        self.args = args
        self.members = members
        self.capacity = capacity
        self.update = update
        self.steps_done = 0
        self.steps_needed = steps_needed
        self.n_shards = n_shards
        self.group_key = group_key
        self.t_admit = t_admit  # (n_shards, rows) host copy of state.t
        self.budget = budget  # (n_shards, rows) host copy of max_trials
        self.polls: List[Tuple[int, np.ndarray]] = []

    def ei_work(self, steps) -> Tuple[np.ndarray, np.ndarray]:
        """(rows, slots) of the EI tail at each dispatch index of
        ``steps`` (see `telemetry.ei_work`)."""
        return ei_work(self.t_admit, self.budget, len(self.members),
                       self.polls, steps)


class _SpaceEntry:
    """Refcounted per-space cache: the strong reference to the space keeps
    its id() stable for the entry's lifetime; the entry (and the cached
    (n, d) encoding, on the host and on each device a chunk of the space
    was admitted to) is evicted when the last active submission over the
    space retires."""

    __slots__ = ("space", "count", "enc", "dev_geom")

    def __init__(self, space):
        self.space = space
        self.count = 0
        self.enc: Optional[np.ndarray] = None
        # device (None: JAX's default placement) → `enc` on that device
        self.dev_geom: Dict[object, jax.Array] = {}


class TuningSession:
    """Streaming multi-job tuning session (see module docstring).

    ``settings``/``to_exhaustion``/``layout`` are session-wide (jobs group
    by packed capacity, which `BOSettings` helps determine — one settings
    object per session keeps the grouping sound).  ``mode`` is the default
    per-submit mode ("ruya" profiles + splits; "cherrypick" searches the
    whole space).  ``cache`` is the session-owned `ProfileCache`: give one
    to enable Flora-style probe-classified profile SHARING across jobs;
    with ``cache=None`` (default) each distinct job is profiled exactly
    once, like the one-shot drivers — sharing profiles changes splits and
    traces, so it must be opted into.  Warm-start seeding works either way
    (the signature class key comes from each job's own resolved profile).
    ``warm_start`` enables signature-class seeding; ``warm_reserve`` packed
    slots are always left for fresh trials (default: max(n_init, 1)).

    ``shard``/``devices`` switch on job-axis sharding: with S > 1 devices
    resolved (``shard=S``, ``shard="auto"``, or an explicit device list),
    each (shape, capacity) group's lockstep chunks are bundled S at a time
    and advanced by ONE `shard_map` dispatch per step, one chunk per
    device (`repro.fleet.sharding`).  The default (``shard=None``) is the
    single-device reference path, and a sharded session is pinned
    bit-identical to it by the golden-trace harness (`tests/golden/`): the
    per-device program is the same vmapped `fast_bo.fleet_step` at a row
    extent in [2, 8], so the established batch-extent invariance carries
    the proof.  Sharded groups re-chunk to rows = min(8, ceil(M/S)) so
    small fleets spread across devices too — chunk membership never
    affects traces (each job's state and static extents are its own).
    Caveat: bundles RETIRE as a unit, so with warm-starting on, a job
    submitted mid-flight (no intervening drain) may see a different
    class-history snapshot — and different warm seeds — across shard
    counts; drain boundaries make warm seeding shard-count-independent
    (see `repro.fleet.sharding`).

    Failure semantics (the elastic/adversarial layer).  ``retry`` governs
    profiling-run faults: `TransientRunError`s are retried with the
    deterministic seeded backoff of `repro.fleet.retry` (per-job retry
    seed derived from ``seed`` — no live RNG, the BO draws stay aligned),
    `PermanentRunError`s fast-fail, and a job whose profiling cannot
    complete becomes a first-class "failed" outcome at submit instead of
    poisoning the fleet.  `cancel`/`fail`/`preempt`/`preempt_below` retire
    a live search mid-flight — its completed trials publish immediately
    and its chunk row is frozen via the engine's `done` flag, so
    chunk-mates' traces are bit-identical to an undisturbed run (vmap rows
    are independent; pinned by the golden disturbed-fleet scenario).
    `reshard` re-bundles every live search onto a new device set (device
    churn, both directions) with per-row state resumed verbatim.
    ``drift_tolerance`` (needs a ``cache``) turns on drift detection: a
    recurring job whose fresh probe no longer matches its cached class
    model is re-profiled and re-classed (`ProfileCache.model_drifted`),
    and the session refuses to warm-seed it from the stale class's trial
    history (``drift_events`` logs the job names).

    Finished jobs release their per-job state: cost tables, masks, cached
    encodings (refcounted per space, on the host and on each device, and
    evicted with the space's last job) are dropped at retirement, so a
    long-lived service session holds only the outcomes and the per-class
    trial history (bounded by deduplication at ≤ n entries per class).
    """

    def __init__(
        self,
        *,
        settings: BOSettings = BOSettings(),
        mode: str = "ruya",
        cache: Optional[ProfileCache] = None,
        warm_start: bool = True,
        warm_reserve: Optional[int] = None,
        to_exhaustion: bool = False,
        layout: str = "feature",
        shard: Union[None, int, str] = None,
        devices: Optional[Sequence] = None,
        seed: int = 0,
        retry: RetryPolicy = RetryPolicy(),
        drift_tolerance: Optional[float] = None,
        objective="runtime",
    ) -> None:
        if mode not in ("ruya", "cherrypick"):
            raise ValueError(f"unknown mode {mode!r}")
        if layout not in _LAYOUTS:
            raise ValueError(f"unknown layout {layout!r}; want one of {_LAYOUTS}")
        # "runtime" | "cost" | {"runtime": w1, "cost": w2} — the session
        # default; overridable per submit.  "runtime" is the pinned legacy
        # path (golden-fixture bit-identity); see `objective_table`.
        self.objective: Objective = canonical_objective(objective)
        # None → single-device reference path; else a tuple of ≥ 2 devices
        # the job axis is sharded over.
        self.shard_devices = resolve_shard_devices(shard, devices)
        self.settings = settings
        self.mode = mode
        self.cache = cache
        self.warm_start = bool(warm_start)
        self.warm_reserve = (
            max(int(warm_reserve), 0) if warm_reserve is not None
            else max(settings.n_init, 1)
        )
        self.to_exhaustion = bool(to_exhaustion)
        self.layout = layout
        self.seed = int(seed)
        self.retry = retry
        self.drift_tolerance = (
            None if drift_tolerance is None else float(drift_tolerance)
        )

        # Lock discipline (the async service, `repro.fleet.service`, steps
        # chunks from per-group host threads): every access to the shared
        # mutable session state — pending queue, chunk list, outcome /
        # history / cache tables — and every chunk state transition happens
        # under this re-entrant lock.  Device WAITS happen outside it
        # (`_step_chunk` captures the state ref under the lock, then blocks
        # on the device queue unlocked), so a slow group's compute never
        # stalls another group's dispatch.  The single-threaded paths
        # (`step()`/`drain()`) take the same lock — an uncontended
        # acquisition is a non-blocking try, well under a microsecond
        # against millisecond-scale chunk steps; a contended one is timed
        # into `telemetry` (`repro.fleet.telemetry`).
        self.telemetry = Telemetry()
        self._lock = TimedLock(self.telemetry)
        # Called (under the lock) with each published SearchOutcome — the
        # service hooks this for completion signalling and metrics.
        self._outcome_listeners: List[Callable[[SearchOutcome], None]] = []

        self.warm_hits = 0  # jobs that were seeded
        self.warm_trials = 0  # total seeded observations
        self.drift_events: List[str] = []  # job names flagged as drifted
        # uids that turned "failed" since the last drain — the drain guard
        # (FleetFailedError) considers these alongside live jobs, so a
        # fleet that failed entirely BEFORE the drain call still raises.
        self._failed_since_drain: List[int] = []

        self._pending: List[_JobRec] = []
        self._chunks: List[_LiveChunk] = []
        self._order: List[JobHandle] = []  # submission order
        self._outcomes: Dict[int, SearchOutcome] = {}
        # id(space) → refcounted encoding (strong space ref inside)
        self._spaces: Dict[int, _SpaceEntry] = {}
        # id(job) → [job, active submissions, profile, profiling attempts,
        # charged backoff seconds, drift flag]; evicted at zero refcount
        self._jobs: Dict[int, list] = {}
        # (signature, n, d) → (ordered [(index, cost)], seen index set)
        self._history: Dict[tuple, Tuple[List[Tuple[int, float]], Set[int]]] = {}

    # ------------------------------------------------------------- submit

    def submit(
        self,
        job: "FleetJob",
        rng: Optional[np.random.Generator] = None,
        *,
        seed: Optional[int] = None,
        mode: Optional[str] = None,
        priority: Optional[Sequence[int]] = None,
        remaining: Optional[Sequence[int]] = None,
        warm_start: Optional[bool] = None,
        job_priority: int = 0,
        objective=None,
    ) -> JobHandle:
        """Register one job; it joins a lockstep chunk at the next `step()`.

        ``rng`` (or ``seed``) scripts the random initialization exactly like
        the sequential engine.  ``mode`` defaults to the session mode.
        Passing ``priority``/``remaining`` explicitly skips profiling and
        uses the given split verbatim (the `batched_search` shim's path);
        otherwise "ruya" resolves a profile (``job.profile_result``, else the
        session `ProfileCache`) and computes the vectorized §III-D split,
        while "cherrypick" searches the whole space.  ``warm_start``
        overrides the session default for this job; seeding only happens for
        profiled jobs (the signature is the class key) and consumes no RNG.

        Profiling faults: transient run failures are retried per the
        session `RetryPolicy`; a permanent failure (or retry exhaustion)
        returns a handle whose outcome is already published with status
        "failed" — no exception, the rest of the fleet is unaffected.
        ``job_priority`` ranks the job for `preempt_below` (higher keeps
        running; it does not affect scheduling otherwise).  ``objective``
        overrides the session objective for this job (see
        `objective_table`; non-runtime objectives need a priced job).

        Thread-safe: concurrent submitters serialize on the session lock
        (the warm-start history snapshot, the scripted init draw, and the
        pending-queue append are one atomic unit — a submission is a
        deterministic function of the class history it observed).
        """
        with span("tuning.submit") as sp:
            with self._lock:
                handle = self._submit_locked(
                    job, rng, seed=seed, mode=mode, priority=priority,
                    remaining=remaining, warm_start=warm_start,
                    job_priority=job_priority, objective=objective,
                )
            if recording():
                sp.set_metadata(uid=handle.uid)
            return handle

    def _submit_locked(
        self,
        job: "FleetJob",
        rng: Optional[np.random.Generator] = None,
        *,
        seed: Optional[int] = None,
        mode: Optional[str] = None,
        priority: Optional[Sequence[int]] = None,
        remaining: Optional[Sequence[int]] = None,
        warm_start: Optional[bool] = None,
        job_priority: int = 0,
        objective=None,
    ) -> JobHandle:
        if (rng is None) == (seed is None):
            raise ValueError("provide exactly one of rng / seed")
        if rng is None:
            rng = np.random.default_rng(seed)
        mode = self.mode if mode is None else mode
        if mode not in ("ruya", "cherrypick"):
            raise ValueError(f"unknown mode {mode!r}")
        warm = self.warm_start if warm_start is None else bool(warm_start)
        obj = (
            self.objective if objective is None
            else canonical_objective(objective)
        )

        space = job.space
        n = len(space)
        d = space.encoded().shape[1]
        # The score table the engine observes.  objective="runtime" is
        # exactly `job.cost_table` (the pinned legacy path); "cost"/blends
        # derive it from the job's pricing axes.
        table64 = objective_table(job, obj)
        if table64.shape != (n,):
            raise ValueError(
                f"job {job.name!r}: cost table has shape {table64.shape}, "
                f"want ({n},)"
            )
        axes64: Optional[Tuple[np.ndarray, np.ndarray]] = None
        rt_tab = getattr(job, "runtime_table", None)
        price_tab = getattr(job, "price_table", None)
        if rt_tab is not None and price_tab is not None:
            rt64 = np.asarray(rt_tab, np.float64)
            price64 = np.asarray(price_tab, np.float64)
            if rt64.shape != (n,) or price64.shape != (n,):
                raise ValueError(
                    f"job {job.name!r}: pricing axes have shapes "
                    f"{rt64.shape}/{price64.shape}, want ({n},)"
                )
            axes64 = (rt64, rt64 * price64)

        profile: Optional[ProfileResult] = None
        signature: Optional[MemorySignature] = None
        if priority is not None:
            prio_idx = np.asarray(priority, np.int64).reshape(-1)
            rem_idx = (
                np.zeros(0, np.int64) if remaining is None
                else np.asarray(remaining, np.int64).reshape(-1)
            )
            if len(np.intersect1d(prio_idx, rem_idx)):
                raise ValueError(
                    f"job {job.name!r}: priority and remaining pools overlap"
                )
            prio_mask = np.zeros(n, bool)
            prio_mask[prio_idx] = True
            rem_mask = np.zeros(n, bool)
            if rem_idx.size:
                rem_mask[rem_idx] = True
        elif mode == "cherrypick":
            prio_idx = np.arange(n, dtype=np.int64)
            rem_idx = np.zeros(0, np.int64)
            prio_mask = np.ones(n, bool)
            rem_mask = np.zeros(n, bool)
        else:
            try:
                profile = self._resolve_profile(job)
            except ProfilingRunError as e:
                # Permanent failure / retry budget exhausted: a first-class
                # "failed" outcome, published immediately — partial fleets
                # keep going (see FleetFailedError for the all-failed case).
                return self._register_failed(job, e)
            je = self._jobs.get(id(job))
            if je is not None and je[5]:
                # The job's class drifted: its cached profile was refreshed
                # and re-classed, and the OLD class's trial history predates
                # the shift — warm-seeding from it would anchor the GP on
                # the stale cost surface, so this job always starts cold.
                warm = False
            signature = (
                self.cache.signature(profile.model)
                if self.cache is not None
                else MemorySignature.of(profile.model)
            )
            # §III-D narrowing, vectorized over the static per-config
            # arrays; remaining is always the complement.
            with span("tuning.split"):
                prio_mask = split_priority_mask(
                    space,
                    profile.model,
                    job.full_input_size,
                    per_node_overhead=job.per_node_overhead,
                    leeway=job.leeway,
                    flat_fraction=job.flat_fraction,
                )
                rem_mask = ~prio_mask
                prio_idx = np.flatnonzero(prio_mask)
                rem_idx = np.flatnonzero(rem_mask)

        budget = trial_budget(len(prio_idx), len(rem_idx), self.settings)

        # Warm-start seeding — decided (and the history snapshot taken) at
        # submit time, so a search is a deterministic function of (class
        # history, seed) no matter how the session is stepped afterwards.
        seed_trials: List[TrialRecord] = []
        # Non-runtime objectives score trials on a different scale, so
        # their class histories are keyed apart — a cost-objective search
        # must never warm-seed donor costs from a runtime-objective one.
        class_key = None
        if signature is not None:
            class_key = (
                (signature, n, d) if obj == "runtime"
                else (signature, n, d, obj)
            )
        if warm and class_key is not None and class_key in self._history:
            room = max(budget - self.warm_reserve, 0)
            hist = self._history[class_key][0][:room]
            seed_trials = [
                TrialRecord(
                    index=i, cost=c, slot=s, source="warm",
                    runtime_h=(
                        None if axes64 is None else float(axes64[0][i])
                    ),
                    usd=None if axes64 is None else float(axes64[1][i]),
                )
                for s, (i, c) in enumerate(hist)
            ]
            if seed_trials:
                self.warm_hits += 1
                self.warm_trials += len(seed_trials)

        # Scripted random initialization — the same draw, in the same order
        # (submission order), as the sequential engine's phase-0 block.  A
        # seeded search skips it (the GP already has observations) and
        # consumes no RNG.
        init_list: List[int] = []
        if len(prio_idx) and not seed_trials:
            n_init = min(self.settings.n_init, len(prio_idx))
            picked = rng.choice(len(prio_idx), size=n_init, replace=False)
            init_list = [int(prio_idx[int(i)]) for i in picked]

        # Past the last possible raise: retain the refcounted per-space /
        # per-job entries and register the submission.
        handle = JobHandle(
            uid=len(self._order), name=job.name, _session=weakref.ref(self)
        )
        self._retain(job)
        je = self._jobs[id(job)]
        rec = _JobRec(
            handle=handle,
            job=job,
            table64=table64,
            enc=self._encoding(space),
            prio_mask=prio_mask,
            rem_mask=rem_mask,
            init_list=init_list,
            seed_trials=seed_trials,
            budget=budget,
            profile=profile,
            signature=signature,
            class_key=class_key,
            prio_idx=prio_idx,
            rem_idx=rem_idx,
            profile_attempts=je[3],
            retry_backoff_s=je[4],
            job_priority=int(job_priority),
            objective=obj,
            axes64=axes64,
        )
        self._order.append(handle)
        self._pending.append(rec)
        return handle

    # -------------------------------------------------------------- step

    def step(self) -> int:
        """Admit pending jobs into lockstep chunks, then advance every live
        chunk by ONE batched BO iteration.  Returns the number of jobs still
        unfinished (0 → everything has retired)."""
        with self._lock:
            self._admit()
            chunks = list(self._chunks)
        for ch in chunks:
            self._step_chunk(ch)
        with self._lock:
            return self._unfinished()

    def _unfinished(self) -> int:
        """Jobs not yet published (pending + live chunk members); caller
        holds the lock."""
        return sum(
            sum(1 for m in c.members if m is not None) for c in self._chunks
        ) + len(self._pending)

    # ------------------------------------------- async-scheduling surface
    #
    # Engine-level primitives for `repro.fleet.service`: one group thread
    # per live (space shape, capacity) key drives its own chunks through
    # `_step_chunk` at its own pace, admitting ITS pending jobs at its own
    # iteration boundary.  Chunk membership never affects traces (vmap rows
    # are independent, extents stay in the invariant [2, 8] window), so the
    # async schedule is bit-identical per job to the lockstep one — the
    # golden fixtures pin it through the service lanes.

    def _pending_group_keys(self) -> Set[tuple]:
        """Admission-group keys with pending submissions."""
        with self._lock:
            return {(rec.enc.shape, rec.budget) for rec in self._pending}

    def _chunks_for(self, key: tuple) -> List["_LiveChunk"]:
        """Live chunks of one admission group (snapshot)."""
        with self._lock:
            return [ch for ch in self._chunks if ch.group_key == key]

    def _admit_group(self, key: tuple, device=None) -> int:
        """Admit every pending job of ONE admission group into chunks —
        the per-group half of `_admit`, run by that group's thread at its
        own iteration boundary.  ``device`` pins the new chunks' buffers
        (and therefore their compute) to one device, letting the service
        spread groups across the host topology; None keeps the default
        placement.  Returns the number of jobs admitted.

        Only a non-empty admission opens a ``tuning.admit`` span and adds
        to ``admit_s``; an empty poll just counts."""
        counters = self.telemetry.group(key)
        with self._lock:
            members = [
                rec for rec in self._pending
                if (rec.enc.shape, rec.budget) == key
            ]
            if not members:
                counters.empty_admissions += 1
                return 0
            t0 = time.perf_counter()
            with span("tuning.admit") as sp:
                self._pending = [
                    rec for rec in self._pending
                    if (rec.enc.shape, rec.budget) != key
                ]
                shape, cap = key
                n_init_slots = max(1, max(len(r.init_list) for r in members))
                if self.shard_devices is not None:
                    chunks = self._build_sharded(
                        members, shape, cap, n_init_slots
                    )
                else:
                    chunks = [
                        self._build_chunk(
                            members[lo : lo + _CHUNK], shape, cap,
                            n_init_slots, device=device,
                        )
                        for lo in range(0, len(members), _CHUNK)
                    ]
                self._chunks.extend(chunks)
                if recording():
                    sp.set_metadata(rows=len(members), chunks=len(chunks))
            counters.admissions += 1
            counters.admit_s += time.perf_counter() - t0
            return len(members)

    def _step_chunk(self, ch: "_LiveChunk") -> str:
        """Advance ONE chunk by one BO iteration; retire it if finished.

        Returns "stepped" (still live), "retired" (outcomes published),
        "dead" (every member was terminated mid-flight and published
        already), or "gone" (the chunk left `_chunks` under our feet — a
        concurrent `reshard` rebuilt the fleet; its rows were resumed into
        new chunks, nothing to do).

        All state transitions happen under the session lock — `cancel`'s
        mid-flight kill swaps `state.done`, and the update donates the old
        state's buffers, so an unlocked reader could touch deleted arrays.
        Device WAITS (the done-flag poll, the pre-retirement sync) happen
        OUTSIDE the lock on a captured state reference: only this chunk's
        owner ever advances it, so the captured buffers cannot be donated
        from under the wait.

        Three spans, each with its counter: ``tuning.dispatch`` (the
        enqueue of the update), ``tuning.poll`` (the done-flag sync) and
        ``tuning.retire`` (the pre-retirement sync, `_retire` and the
        publishes).  While a trace records, ``tuning.dispatch`` carries
        the EI tail's ``rows`` and ``slots`` for this update (`ei_work`);
        the poll keeps the flags it read for them."""
        counters = self.telemetry.group(ch.group_key)
        with self._lock:
            if ch not in self._chunks:
                return "gone"
            if all(m is None for m in ch.members):
                self._chunks.remove(ch)
                return "dead"
            t0 = time.perf_counter()
            with span("tuning.dispatch") as sp:
                if recording():
                    rows, slots = ch.ei_work([ch.steps_done])
                    sp.set_metadata(rows=int(rows[0]), slots=int(slots[0]))
                ch.state = ch.update(ch.state, ch.args)
            counters.dispatches += 1
            counters.dispatch_s += time.perf_counter() - t0
            ch.steps_done += 1
            retire = ch.steps_done >= ch.steps_needed
            poll = (
                not retire
                and not self.to_exhaustion
                and ch.steps_done % _POLL_PERIOD == 0
            )
            done_flags = ch.state.done if (poll or retire) else None
        if poll:
            # Blocks on this chunk's device queue only.
            t0 = time.perf_counter()
            with span("tuning.poll"):
                flags = np.asarray(done_flags).reshape(-1)
            counters.polls += 1
            counters.poll_wait_s += time.perf_counter() - t0
            retire = bool(flags.all())
            ch.polls.append((ch.steps_done, flags))
        if not retire:
            return "stepped"
        t0 = time.perf_counter()
        try:
            with span("tuning.retire", rows=len(ch.members)):
                jax.block_until_ready(done_flags)
                with self._lock:
                    if ch not in self._chunks:
                        return "gone"
                    self._retire(ch)
                    self._chunks.remove(ch)
                    return "retired"
        finally:
            counters.retire_s += time.perf_counter() - t0

    def drain(self) -> List[SearchOutcome]:
        """Step until every submitted job has finished; returns all outcomes
        (cumulative over the session's lifetime) in submission order.

        Raises `FleetFailedError` when every job this drain was waiting
        on — jobs live at the call, plus jobs that turned "failed" since
        the previous drain (profiling failures at submit, mid-flight
        `fail`s) — ends with status "failed".  All outcomes stay available
        via `results()`; a mixed fleet — some failed, some finished —
        returns normally."""
        with self._lock:
            waiting = {rec.handle.uid for rec in self._live_recs()}
            waiting.update(self._failed_since_drain)
            self._failed_since_drain = []
        while self._pending or self._chunks:
            self.step()
        self._check_all_failed(waiting)
        return self.results()

    def _check_all_failed(self, waiting: Set[int]) -> None:
        """The drain guard (see `drain`); shared with the async service's
        own drain, which waits on worker threads instead of stepping."""
        if not waiting:
            return
        with self._lock:
            outs = [self._outcomes.get(uid) for uid in sorted(waiting)]
        if all(o is not None and o.status == "failed" for o in outs):
            names = [o.name for o in outs]
            raise FleetFailedError(
                f"all {len(names)} job(s) this drain was waiting on "
                f"permanently failed: {names} — outcomes remain "
                "available via results()"
            )

    def results(self) -> List[SearchOutcome]:
        """Outcomes of all FINISHED jobs, in submission order."""
        with self._lock:
            return [
                self._outcomes[h.uid] for h in self._order
                if h.uid in self._outcomes
            ]

    def outcome(self, handle: JobHandle) -> SearchOutcome:
        return handle.outcome()

    def __len__(self) -> int:
        return len(self._order)

    # ---------------------------------------------------------- lifecycle

    def cancel(self, handle: JobHandle) -> bool:
        """Cancel a pending or mid-flight job.  Its completed trials
        publish immediately as a partial outcome (status "cancelled") and
        its chunk row is frozen via the engine's `done` flag — chunk-mates
        advance exactly as if nothing happened (vmap rows are independent;
        pinned bit-identical by the golden disturbed-fleet scenario).
        Returns False when the job already finished."""
        return self._terminate(handle, "cancelled")

    def fail(self, handle: JobHandle, reason: Optional[str] = None) -> bool:
        """Mark a live job failed (e.g. its external executor died): the
        same mid-flight retirement as `cancel`, status "failed"."""
        return self._terminate(handle, "failed", reason)

    def preempt(self, handle: JobHandle) -> bool:
        """Preempt a live job (status "preempted"): partial results are
        kept, the lockstep slot frees up, and — because completed trials of
        CONVERGED jobs are what feeds the class history — a later resubmit
        starts from the class's knowledge, not the victim's stale row."""
        return self._terminate(handle, "preempted")

    def preempt_below(self, min_priority: int) -> List[JobHandle]:
        """Preempt every live job whose submit-time ``job_priority`` is
        below ``min_priority`` (default priority is 0, so any positive
        floor evicts unranked work).  Returns the preempted handles."""
        with self._lock:
            victims = [
                rec.handle for rec in self._live_recs()
                if rec.job_priority < min_priority
            ]
            for handle in victims:
                self._terminate(handle, "preempted")
            return victims

    def _live_recs(self) -> List[_JobRec]:
        """Every unfinished submission: pending plus live chunk members."""
        recs = list(self._pending)
        for ch in self._chunks:
            recs.extend(m for m in ch.members if m is not None)
        return recs

    def _terminate(
        self, handle: JobHandle, status: str, reason: Optional[str] = None
    ) -> bool:
        with self._lock:
            return self._terminate_locked(handle, status, reason)

    def _terminate_locked(
        self, handle: JobHandle, status: str, reason: Optional[str] = None
    ) -> bool:
        if handle._outcome is not None:
            return False  # already finished (or already terminated)
        for j, rec in enumerate(self._pending):
            if rec.handle.uid == handle.uid:
                del self._pending[j]
                rec.status = status
                # Never admitted: no engine row to read — the outcome is
                # just the warm seeds (if any) and zero executed trials.
                self._publish(
                    rec, k=len(rec.seed_trials), tried_row=None,
                    stop=-1, pb=-1, failure=reason,
                )
                return True
        for ch in self._chunks:
            for i, rec in enumerate(ch.members):
                if rec is not None and rec.handle.uid == handle.uid:
                    rec.status = status
                    self._kill(ch, i, rec, reason)
                    return True
        return False  # not this session's handle

    def _kill(
        self, ch: _LiveChunk, i: int, rec: _JobRec,
        reason: Optional[str] = None,
    ) -> None:
        """Retire member ``i`` of a live chunk mid-flight: publish its
        partial outcome from a host snapshot of its row, tombstone the
        member slot, and freeze the row by latching the engine's `done`
        flag (`fast_bo.fleet_step` gates every write on
        ``live = ~done & budget_left``, so a done row is inert — its
        chunk-mates' traces are untouched)."""
        rows = collapse_rows(ch.state, ch.n_shards)
        self._publish(
            rec,
            k=int(rows.t[i]),
            tried_row=rows.tried[i],
            stop=int(rows.stop[i]),
            pb=int(rows.pb[i]),
            failure=reason,
        )
        ch.members[i] = None
        done = np.array(ch.state.done)  # writable host copy
        done.reshape(-1)[i] = True
        # Re-place with the row's original sharding (single-device chunks
        # carry a SingleDeviceSharding — the same call covers both).
        ch.state = ch.state._replace(
            done=jax.device_put(done, ch.state.done.sharding)
        )

    def reshard(
        self,
        shard: Union[None, int, str] = None,
        devices: Optional[Sequence] = None,
    ) -> int:
        """Live device churn: re-bundle every mid-flight search onto a new
        device set (devices leaving and joining are the same operation).
        Each live row's engine state is snapshotted on host
        (`repro.fleet.sharding.collapse_rows`), survivors are regrouped by
        the admission rule, and chunks are rebuilt at the new shard width
        with the rows resumed VERBATIM (dummy pads re-derived).

        Survivors' traces stay bit-identical to an undisturbed run: the
        resumed per-row state is exactly what the update would have kept
        on device, chunk membership never affects traces (vmap rows are
        independent), and the rebuilt row extent stays inside the
        batch-extent-invariant [2, 8] window — pinned by the golden
        disturbed-fleet scenario.  Pending jobs are untouched (they admit
        at the next `step()` under the new layout).  Returns the number of
        live searches re-bundled."""
        with self._lock:
            self.shard_devices = resolve_shard_devices(shard, devices)
            survivors: List[Tuple[_JobRec, FleetState]] = []
            for ch in self._chunks:
                rows = collapse_rows(ch.state, ch.n_shards)
                for i, rec in enumerate(ch.members):
                    if rec is None:
                        continue
                    row = jax.tree_util.tree_map(lambda x, _i=i: x[_i], rows)
                    survivors.append((rec, row))
            self._chunks = []
            groups: Dict[tuple, List[Tuple[_JobRec, FleetState]]] = {}
            for rec, row in survivors:
                groups.setdefault((rec.enc.shape, rec.budget), []).append(
                    (rec, row)
                )
            for (shape, cap), pairs in groups.items():
                members = [p[0] for p in pairs]
                resume = [p[1] for p in pairs]
                n_init_slots = max(1, max(len(r.init_list) for r in members))
                if self.shard_devices is not None:
                    self._chunks.extend(
                        self._build_sharded(
                            members, shape, cap, n_init_slots, resume=resume
                        )
                    )
                    continue
                for lo in range(0, len(members), _CHUNK):
                    self._chunks.append(
                        self._build_chunk(
                            members[lo : lo + _CHUNK], shape, cap,
                            n_init_slots, resume=resume[lo : lo + _CHUNK],
                        )
                    )
            return len(survivors)

    # ---------------------------------------------------------- internals

    def _retry_seed(self, job: "FleetJob") -> int:
        """Per-job retry-jitter seed: a hash of (session seed, job name) —
        deterministic, and independent across the fleet so synchronized
        backoff waves cannot form."""
        h = hashlib.sha256(f"{self.seed}/{job.name}".encode()).digest()
        return int.from_bytes(h[:8], "big")

    def _resolve_profile(self, job: "FleetJob") -> ProfileResult:
        if job.profile_result is not None:
            return job.profile_result
        if job.profile_run is None:
            raise ValueError(
                f"job {job.name!r} has neither profile_result nor profile_run"
            )
        # Memoized per job OBJECT (seed-replica fleets alias one FleetJob):
        # each distinct job profiles once.  An explicit session cache adds
        # Flora-style probe-classified sharing ACROSS jobs; without one the
        # semantics match the one-shot drivers exactly.  The whole
        # resolution (probe + full profile) is one retry unit: a transient
        # failure re-runs it from the top — emulated run fns are
        # deterministic in the sample size, so a retried resolution returns
        # an identical ProfileResult and the search trace is unchanged.
        entry = self._jobs.setdefault(
            id(job), [job, 0, None, 1, 0.0, False]
        )
        if entry[2] is None:
            stats = RetryStats(attempts=0)
            drifted = [False]

            def resolve() -> ProfileResult:
                if self.cache is not None:
                    # `last_drift` is a per-call report on a possibly
                    # shared cache: read it while still holding the
                    # cache lock so a concurrent submitter's call (from
                    # another session sharing this cache) cannot clobber
                    # it between the resolution and the read.
                    with self.cache.lock:
                        prof = self.cache.get_or_profile(
                            job.profile_run, job.full_input_size,
                            drift_tolerance=self.drift_tolerance,
                        )
                        drifted[0] = self.cache.last_drift
                    return prof
                return profile_job(job.profile_run, job.full_input_size)

            try:
                profile, stats = call_with_retry(
                    resolve, policy=self.retry,
                    seed=self._retry_seed(job), stats=stats,
                )
            finally:
                # Record the cost even when resolution ultimately failed —
                # the failed outcome reports what the attempts burned.
                entry[3], entry[4] = stats.attempts, stats.backoff_s
            entry[2] = profile
            if drifted[0]:
                entry[5] = True
                self.drift_events.append(job.name)
        return entry[2]

    def _register_failed(
        self, job: "FleetJob", error: BaseException
    ) -> JobHandle:
        """Profiling failed permanently (or exhausted its retry budget):
        publish a first-class "failed" outcome at submit time.  The job
        never enters the pending queue, so it cannot poison a chunk; the
        handle behaves like any finished job's."""
        je = self._jobs.get(id(job))
        handle = JobHandle(
            uid=len(self._order), name=job.name, _session=weakref.ref(self)
        )
        outcome = SearchOutcome(
            name=job.name,
            records=[],
            seeded=[],
            stop_iteration=None,
            phase_boundary=None,
            priority=(),
            remaining=(),
            status="failed",
            failure=f"{type(error).__name__}: {error}",
            profile_attempts=je[3] if je is not None else 1,
            retry_backoff_s=je[4] if je is not None else 0.0,
        )
        self._order.append(handle)
        self._outcomes[handle.uid] = outcome
        handle._outcome = outcome
        self._failed_since_drain.append(handle.uid)
        for listener in self._outcome_listeners:
            listener(outcome)
        return handle

    def _retain(self, job: "FleetJob") -> None:
        """Bump the refcounted per-space and per-job cache entries."""
        space = job.space
        se = self._spaces.get(id(space))
        if se is None:
            se = self._spaces[id(space)] = _SpaceEntry(space)
        se.count += 1
        je = self._jobs.setdefault(id(job), [job, 0, None, 1, 0.0, False])
        je[1] += 1

    def _release(self, rec: _JobRec) -> None:
        """Drop the retired job's share of the caches; evict empty entries
        (with their encodings on the host and on the devices)."""
        sid = id(rec.job.space)
        se = self._spaces.get(sid)
        if se is not None:
            se.count -= 1
            if se.count <= 0:
                del self._spaces[sid]
        jid = id(rec.job)
        je = self._jobs.get(jid)
        if je is not None:
            je[1] -= 1
            if je[1] <= 0:
                del self._jobs[jid]

    def _encoding(self, space) -> np.ndarray:
        """The space's (n, d) float32 encoding, once per space (seed-replica
        fleets alias one SearchSpace)."""
        entry = self._spaces[id(space)]
        if entry.enc is None:
            entry.enc = encode_features(space.encoded())
        return entry.enc

    def _admit(self) -> None:
        """Form lockstep chunks from the pending queue — the same (space
        shape, packed capacity) grouping and ≤`_CHUNK` slicing as
        `batched_search`, so a statically submitted fleet compiles and runs
        the identical array program.  With sharding on, each group's chunks
        are instead bundled across the shard devices (`_build_sharded`)."""
        for key in dict.fromkeys(
            (rec.enc.shape, rec.budget) for rec in self._pending
        ):
            self._admit_group(key)

    def _build_sharded(
        self, members: List[_JobRec], shape, cap: int, n_init_slots: int,
        resume: Optional[List[FleetState]] = None,
    ) -> List[_LiveChunk]:
        """Bundle one (shape, capacity) group's jobs across the shard
        devices: chunks of ``rows`` jobs, up to S of them per bundle, one
        `shard_map` dispatch per bundle per step.

        Rows are min(_CHUNK, ceil(M/S)) so a small fleet still spreads
        across devices — legal because chunk membership never affects
        traces (each job carries its own state and the row extent stays in
        the batch-extent-invariant [2, 8] window; pinned by the golden
        harness and the shard-invariance property suite).  A leftover
        bundle with a single chunk takes the plain single-device path.
        """
        S = len(self.shard_devices)
        counters = self.telemetry.group((shape, cap))
        m = len(members)
        rows = min(_CHUNK, max(2, -(-m // S)))
        out: List[_LiveChunk] = []
        for lo in range(0, m, S * rows):
            sl = members[lo : lo + S * rows]
            rs = None if resume is None else resume[lo : lo + S * rows]
            n_shards = -(-len(sl) // rows)
            if n_shards == 1:
                out.append(
                    self._build_chunk(sl, shape, cap, n_init_slots, resume=rs)
                )
                continue
            with span("tuning.chunk_arrays"):
                parts = [
                    self._chunk_arrays(
                        sl[k * rows : (k + 1) * rows], shape, cap,
                        n_init_slots, rows,
                        resume=(
                            None if rs is None
                            else rs[k * rows : (k + 1) * rows]
                        ),
                    )
                    for k in range(n_shards)
                ]
                arrays = [np.stack(xs) for xs in zip(*[
                    tuple(p[0])
                    + (self._host_geom(sl[k * rows : (k + 1) * rows], rows),)
                    + p[1] + self._settings_scalars()
                    for k, p in enumerate(parts)
                ])]
            update, sharding = sharded_update(
                self.shard_devices[:n_shards], self.settings.xi, self.layout
            )
            with span("tuning.device_put") as sp:
                on_dev = [jax.device_put(x, sharding) for x in arrays]
                self._count_puts(
                    counters, sp, len(arrays),
                    sum(x.nbytes for x in arrays),
                )
            n_state = len(FleetState._fields)
            out.append(
                _LiveChunk(
                    state=FleetState(*on_dev[:n_state]),
                    args=tuple(on_dev[n_state:]),
                    members=sl,
                    capacity=max(cap, 1),
                    update=lambda st, a, _u=update: _u(st, *a),
                    steps_needed=max(p[2] for p in parts),
                    n_shards=n_shards,
                    group_key=(shape, cap),
                    t_admit=np.stack([p[0].t for p in parts]),
                    budget=np.stack([p[1][5] for p in parts]),
                )
            )
        return out

    def _build_chunk(
        self, members: List[_JobRec], shape, cap: int, n_init_slots: int,
        resume: Optional[List[FleetState]] = None,
        device=None,
    ) -> _LiveChunk:
        """One lockstep chunk on ``device`` (None: JAX's default
        placement; otherwise committed, so the update runs there).

        Its inputs reach the device in one transfer (`staging.pack`),
        split there by `staging.stage`, which also stacks the rows'
        geometry from the per-space copies already on the device
        (`_device_geoms`).  The staged arrays equal the host build of
        `_chunk_arrays` bit for bit, dummy rows' zero geometry included."""
        rows = max(len(members), 2)
        counters = self.telemetry.group((shape, cap))
        with span("tuning.chunk_arrays"):
            state_np, args_np, steps_needed = self._chunk_arrays(
                members, shape, cap, n_init_slots, rows, resume=resume,
            )
            buf, spec = pack(
                tuple(state_np) + args_np + self._settings_scalars()
                + (np.int32(len(members)),)
            )
        with span("tuning.device_put") as sp:
            geoms, puts, nbytes = self._device_geoms(
                members, device, counters
            )
            geom, arrays = stage(
                jax.device_put(buf, device),
                geoms + geoms[:1] * (rows - len(members)),
                spec=spec,
            )
            self._count_puts(counters, sp, puts + 1, nbytes + buf.nbytes)
        n_state = len(FleetState._fields)
        xi, layout = self.settings.xi, self.layout
        return _LiveChunk(
            state=FleetState(*arrays[:n_state]),
            args=(geom,) + tuple(arrays[n_state:]),
            members=members,
            capacity=max(cap, 1),
            update=lambda st, a: _fleet_update(st, *a, xi=xi, layout=layout),
            steps_needed=steps_needed,
            group_key=(shape, cap),
            t_admit=state_np.t[None],
            budget=args_np[5][None],
        )

    def _settings_scalars(self) -> tuple:
        """The update's three settings scalars, as host arrays."""
        return (
            np.asarray(self.settings.min_observations, np.int32),
            np.asarray(self.settings.ei_stop_rel, np.float32),
            np.asarray(self.to_exhaustion),
        )

    def _device_geoms(
        self, members: List[_JobRec], device, counters: GroupCounters,
    ) -> Tuple[Tuple[jax.Array, ...], int, int]:
        """Each member's space encoding on ``device``, put there once per
        (space, device) and kept in the space's cache entry until the
        entry is evicted.  Returns the per-member arrays and the count
        and bytes of the puts made; counts the group's ``geom_puts`` and
        ``geom_reuses`` (one per distinct space of the chunk)."""
        puts = nbytes = 0
        for space in {id(r.job.space): r.job.space for r in members}.values():
            entry = self._spaces[id(space)]
            if device in entry.dev_geom:
                counters.geom_reuses += 1
                continue
            host = self._encoding(space)
            entry.dev_geom[device] = jax.device_put(host, device)
            counters.geom_puts += 1
            puts += 1
            nbytes += host.nbytes
        return tuple(
            self._spaces[id(r.job.space)].dev_geom[device] for r in members
        ), puts, nbytes

    def _host_geom(self, members: List[_JobRec], rows: int) -> np.ndarray:
        """The (rows, n, d) geometry of one chunk built on the host: each
        member's space encoding, zero for the dummy rows."""
        one = self._encoding(members[0].job.space)
        geom = np.zeros((rows,) + one.shape, one.dtype)
        for i, rec in enumerate(members):
            geom[i] = self._encoding(rec.job.space)
        return geom

    @staticmethod
    def _count_puts(
        counters: GroupCounters, sp, puts: int, nbytes: int,
    ) -> None:
        """Count a chunk's transfers on its group, and on its
        ``tuning.device_put`` span while a trace records."""
        counters.admit_puts += puts
        counters.admit_bytes += nbytes
        if recording():
            sp.set_metadata(puts=puts, bytes=nbytes)

    def _chunk_arrays(
        self, members: List[_JobRec], shape, cap: int, n_init_slots: int,
        rows: int, resume: Optional[List[FleetState]] = None,
    ) -> Tuple[FleetState, tuple, int]:
        """Host-side state and args, all but the geometry (see
        `_build_chunk`, `_host_geom`), for one lockstep chunk of ``rows`` rows
        (members first, then inert dummy rows — zero trial budget, cold
        defaults; rows ≥ 2 because XLA:CPU collapses singleton batch dims
        into unbatched programs with different float32 numerics).

        ``resume`` (the `reshard` path) supplies one host-side per-row
        `FleetState` per member: the row is restored VERBATIM instead of
        cold/warm-initialized, so a re-bundled search continues exactly
        where its old chunk left off.  Static args are rebuilt from the
        recs either way — they are a pure function of the submission, and
        a changed ``n_init_slots`` width is numerics-neutral (the scripted
        pick indexes it through a clip and is gated by ``init_count``)."""
        n, d = shape
        capacity = max(cap, 1)

        costs = np.zeros((rows, n), np.float32)
        prio_mask = np.zeros((rows, n), bool)
        rem_mask = np.zeros((rows, n), bool)
        init_picks = np.zeros((rows, n_init_slots), np.int32)
        init_count = np.zeros(rows, np.int32)
        max_trials = np.zeros(rows, np.int32)
        obs0 = np.zeros((rows, n), bool)
        tried0 = np.full((rows, capacity), -1, np.int32)
        py0 = np.zeros((rows, capacity), np.float32)
        feats0 = np.zeros((rows, capacity, d), np.float32)
        t0 = np.zeros(rows, np.int32)
        stop0 = np.full(rows, -1, np.int32)
        pb0 = np.full(rows, -1, np.int32)
        done0 = np.zeros(rows, bool)
        last_ei0 = np.zeros(rows, np.float32)
        last_best0 = np.full(rows, np.inf, np.float32)

        for i, rec in enumerate(members):
            costs[i] = rec.table64.astype(np.float32)
            prio_mask[i] = rec.prio_mask
            rem_mask[i] = rec.rem_mask
            init_picks[i, : len(rec.init_list)] = rec.init_list
            init_count[i] = len(rec.init_list)
            max_trials[i] = rec.budget
            if resume is not None:
                row = resume[i]
                obs0[i] = row.obs
                tried0[i] = row.tried
                py0[i] = row.py
                feats0[i] = row.feats
                t0[i] = row.t
                stop0[i] = row.stop
                pb0[i] = row.pb
                done0[i] = row.done
                last_ei0[i] = row.last_ei
                last_best0[i] = row.last_best
                continue
            w = len(rec.seed_trials)
            if w:
                idx = np.asarray([s.index for s in rec.seed_trials], np.int64)
                obs0[i, idx] = True
                tried0[i, :w] = idx.astype(np.int32)
                py0[i, :w] = np.asarray(
                    [s.cost for s in rec.seed_trials], np.float32
                )
                # Rows of the canonical float32 encoding — bit-identical to
                # what on-device observation writes would have accumulated.
                feats0[i, :w] = rec.enc[idx]
                t0[i] = w

        state = FleetState(
            obs=obs0,
            tried=tried0,
            py=py0,
            feats=feats0,
            t=t0,
            stop=stop0,
            pb=pb0,
            done=done0,
            last_ei=last_ei0,
            last_best=last_best0,
        )
        args = (
            costs, prio_mask, rem_mask, init_picks, init_count, max_trials,
        )
        # One extra pass beyond the largest fresh-trial budget: it observes
        # nothing, but it is where a budget-capped job records a phase
        # boundary reached exactly at its last trial, and where budget
        # exhaustion latches `done` (same schedule as the one-shot engine).
        steps_needed = int(max(max_trials[i] - t0[i] for i in range(rows))) + 1
        return state, args, steps_needed

    def _retire(self, ch: _LiveChunk) -> None:
        # Collapse any leading shard axis: member i lives at flat row i
        # whether the chunk ran on one device or a mesh (see _LiveChunk).
        cap = ch.capacity
        s_tried = np.asarray(ch.state.tried).reshape(-1, cap)
        s_t = np.asarray(ch.state.t).reshape(-1)
        s_stop = np.asarray(ch.state.stop).reshape(-1)
        s_pb = np.asarray(ch.state.pb).reshape(-1)
        counters = self.telemetry.group(ch.group_key)
        counters.head_slots += head_slots(
            ch.t_admit, s_t.reshape(ch.t_admit.shape), ch.steps_done
        )
        counters.head_capacity_slots += (
            ch.t_admit.shape[0] * ch.steps_done * cap
        )
        rows, slots = ch.ei_work(np.arange(ch.steps_done))
        counters.ei_rows += int(rows.sum())
        counters.ei_slots += int(slots.sum())
        for i, rec in enumerate(ch.members):
            if rec is None:
                continue  # retired mid-flight; outcome already published
            self._publish(
                rec, k=int(s_t[i]), tried_row=s_tried[i],
                stop=int(s_stop[i]), pb=int(s_pb[i]),
            )

    def _publish(
        self, rec: _JobRec, k: int, tried_row, stop: int, pb: int,
        failure: Optional[str] = None,
    ) -> None:
        """Build and register ``rec``'s `SearchOutcome` from its engine row
        (slots [w, k) are the executed trials) and release its caches.
        Shared by normal retirement, mid-flight kills (partial rows), and
        pending-queue terminations (k == w, no row)."""
        w = len(rec.seed_trials)
        n_init = len(rec.init_list)
        # Straggler latency is REPORTED (attempts = 2 for the re-dispatched
        # trial), never fed back: the observed cost is the deterministic
        # table value either way, so the trace is unchanged.
        plan = getattr(rec.job, "faults", None)
        # Priced jobs carry raw runtime/dollar axes on every record (the
        # Pareto-front inputs); unpriced jobs keep the exact legacy record
        # shape, so the golden fixtures stay byte-identical.
        rt64, usd64 = rec.axes64 if rec.axes64 is not None else (None, None)
        records = []
        for slot in range(w, k):
            idx = int(tried_row[slot])
            records.append(
                TrialRecord(
                    index=idx,
                    cost=float(rec.table64[idx]),
                    slot=slot,
                    source="init" if slot < n_init else "search",
                    attempts=(
                        2 if plan is not None
                        and plan.is_straggler(rec.job.name, slot) else 1
                    ),
                    runtime_h=None if rt64 is None else float(rt64[idx]),
                    usd=None if usd64 is None else float(usd64[idx]),
                )
            )
        outcome = SearchOutcome(
            name=rec.job.name,
            records=records,
            seeded=list(rec.seed_trials),
            stop_iteration=stop if stop >= 0 else None,
            phase_boundary=pb if pb >= 0 else None,
            # tolist() boxes at C speed; built once, at retirement.
            priority=tuple(rec.prio_idx.tolist()),
            remaining=tuple(rec.rem_idx.tolist()),
            profile=rec.profile,
            signature=rec.signature,
            status=rec.status,
            profile_attempts=rec.profile_attempts,
            retry_backoff_s=rec.retry_backoff_s,
            failure=failure,
            objective=rec.objective,
            currency=(
                getattr(rec.job, "currency", "USD")
                if rec.axes64 is not None else None
            ),
        )
        self._outcomes[rec.handle.uid] = outcome
        rec.handle._outcome = outcome
        if rec.status == "failed":
            self._failed_since_drain.append(rec.handle.uid)
        # Only CONVERGED searches feed the warm-start class history: a
        # revoked job's partial trials would make later warm seeds depend
        # on cancellation timing — the bit-identity invariant (survivors
        # match an undisturbed run) requires history from completed
        # searches only.
        if rec.status == "converged" and rec.class_key is not None:
            hist, seen = self._history.setdefault(
                rec.class_key, ([], set())
            )
            for r in records:
                if r.index not in seen:
                    seen.add(r.index)
                    hist.append((r.index, r.cost))
        # The rec (cost table, masks, encoding share) dies with the
        # chunk; evict its cache shares so a long-lived session holds
        # only outcomes and class history.
        self._release(rec)
        for listener in self._outcome_listeners:
            listener(outcome)
