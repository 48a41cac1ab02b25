"""Fleet tuning subsystem: streaming multi-job Bayesian-optimized search.

The paper evaluates Ruya one job at a time; related work (Flora, Blink)
pushes toward tuning as a *fleet service* — many jobs, shared knowledge,
negligible per-job overhead.  This package provides:

  * `session.TuningSession` — THE tuning engine: submit jobs over time,
    `step()` advances every live search one batched BO iteration (newly
    submitted jobs are admitted into lockstep chunks between steps),
    `drain()`/`results()` return first-class `TrialRecord`/`SearchOutcome`
    structures.  The session owns the `ProfileCache`, computes the
    vectorized §III-D split, and warm-starts searches from completed trials in the
    same memory-signature class.
  * `batched_engine.batched_search` — one-shot shim over a session: J
    independent Ruya/CherryPick searches in device-resident lockstep (one
    jitted vmapped `fleet_step` per iteration), trace-identical to the
    sequential engine in `repro.core.bayesopt`.
  * `profile_cache.ProfileCache` — Flora-style reuse of profiling runs
    across jobs whose memory patterns match (category + fitted coefficients).
  * `driver.tune_fleet` — one-shot shim: probe/profile (with cache), split,
    search, one `RuyaReport` per job — the same API `repro.core.tuner`
    exposes for J=1.
  * `sharding` — job-axis sharding across JAX devices: lockstep chunks are
    bundled S at a time and advanced by one `shard_map` dispatch
    (`TuningSession(shard=...)` / `batched_search(shard=...)`), pinned
    bit-identical to the single-device reference by `tests/golden/`.
  * `retry.RetryPolicy` — deterministic exponential backoff with seeded
    jitter for transient profiling-run failures; permanent failures
    fast-fail into first-class "failed" outcomes (`FleetFailedError` only
    when a drain is waiting on nothing else).
  * `service.TuningService` — the async daemon over a session: one host
    thread per live admission group drives its own dispatch loop at its
    own pace (no global lockstep barrier), thread-safe `submit()` with
    bounded-queue backpressure (`ServiceSaturated`), graceful shutdown,
    and a JSON metrics surface — bit-identical per job to the lockstep
    drain under any thread interleaving (pinned by `tests/test_service.py`).
"""

from repro.fleet.batched_engine import BatchedTrace, batched_search
from repro.fleet.driver import FleetJob, cluster_fleet, replay_seeds, tune_fleet
from repro.fleet.profile_cache import MemorySignature, ProfileCache
from repro.fleet.retry import RetryPolicy, RetryStats, call_with_retry
from repro.fleet.service import ServiceSaturated, TuningService
from repro.fleet.sharding import resolve_shard_devices
from repro.fleet.session import (
    FleetFailedError,
    JobHandle,
    SearchOutcome,
    TrialRecord,
    TuningSession,
    canonical_objective,
    objective_table,
)

__all__ = [
    "BatchedTrace",
    "batched_search",
    "FleetFailedError",
    "FleetJob",
    "cluster_fleet",
    "replay_seeds",
    "tune_fleet",
    "JobHandle",
    "MemorySignature",
    "ProfileCache",
    "RetryPolicy",
    "RetryStats",
    "call_with_retry",
    "canonical_objective",
    "objective_table",
    "resolve_shard_devices",
    "SearchOutcome",
    "ServiceSaturated",
    "TrialRecord",
    "TuningService",
    "TuningSession",
]
