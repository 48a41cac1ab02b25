"""End-to-end Ruya tuner: profile → categorize → split → two-phase BO search.

This module is environment-agnostic.  An environment supplies:
  * a profiling run function   run(sample_size) -> (runtime_s, peak_mem_bytes)
  * the full input size        (bytes, or tokens-per-device for the TPU tuner)
  * the discrete search space  (SearchSpace)
  * a trial cost function      cost_fn(config_index) -> float

Two environments ship with the repo: the Scout-like cluster emulator
(`repro.cluster`) reproducing the paper's evaluation, and the TPU
sharding-configuration autotuner (`repro.launch.autotune`).

Both execution styles run the packed-observation BO engine (`fast_bo`):
`cost_table` replay goes through the batched fleet engine (since PR 4 a
deprecation shim over `repro.fleet.session.TuningSession`), a live
`cost_fn` through the sequential driver's device-resident probe — one
shared compiled step, identical traces (see `fast_bo` for the layout and
the float32 discipline).  For streaming workloads, shared profiling, and
cross-job warm-starting, hold a `TuningSession` directly.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence, Tuple

import numpy as np

from repro.core.bayesopt import (
    BOSettings,
    SearchTrace,
    cherrypick_search,
    ruya_search,
)
from repro.core.memory_model import MemoryModel
from repro.core.profiler import ProfileResult, profile_job
from repro.core.search_space import SearchSpace, split_search_space

__all__ = ["RuyaReport", "run_ruya", "run_cherrypick"]


@dataclasses.dataclass
class RuyaReport:
    """One job's profile, §III-D split and search trace.

    ``priority`` / ``remaining`` are the split's pools, in pool order:
    tuples from `run_ruya`, and from a session's `SearchOutcome.report()`
    the outcome's `Indices`, an immutable integer sequence backed by a
    read-only array that compares equal to the tuple and converts with
    ``np.asarray`` at no copy."""

    profile: ProfileResult
    priority: Sequence[int]
    remaining: Sequence[int]
    trace: SearchTrace

    @property
    def memory_model(self) -> MemoryModel:
        return self.profile.model


def run_ruya(
    *,
    profile_run: Optional[Callable[[float], Tuple[float, float]]] = None,
    full_input_size: float = 0.0,
    space: SearchSpace,
    cost_fn: Optional[Callable[[int], float]] = None,
    cost_table: Optional[np.ndarray] = None,
    rng: np.random.Generator,
    per_node_overhead: float = 0.0,
    leeway: float = 0.10,
    flat_fraction: float = 1.0 / 7.0,
    settings: BOSettings = BOSettings(),
    to_exhaustion: bool = False,
    profile_result: Optional[ProfileResult] = None,
    objective="runtime",
) -> RuyaReport:
    """The full Ruya pipeline.  ``profile_result`` can be injected to reuse a
    previous profiling phase (the paper: profiling only repeats when the
    execution context changes).

    Costs come either from ``cost_fn`` (live trials, driven by the sequential
    engine) or from ``cost_table`` (recorded/emulated workload replay, driven
    by the batched fleet engine as a fleet of one).  Both engines are
    trace-identical, so the choice is purely about execution style.

    ``objective`` routes the replay scoring ("runtime" — the default,
    pinned legacy path — or "cost" / a weight mapping; see
    `repro.fleet.session.objective_table`).  Non-runtime objectives need
    the ``cost_table`` path with pricing axes (``runtime_table`` /
    ``price_table``) — a live ``cost_fn`` observes one scalar per trial
    and has no second axis to trade against.

    .. deprecated:: PR 4
        The ``cost_table`` path is a one-shot deprecation shim over
        `repro.fleet.session.TuningSession` (a session of one job, drained
        immediately — bit-identical, pinned by `tests/test_session.py`).
        New replay/fleet code should hold a session; the live ``cost_fn``
        path remains the sequential probe driver.
    """
    if (cost_fn is None) == (cost_table is None):
        raise ValueError("provide exactly one of cost_fn / cost_table")
    if cost_table is not None:
        from repro.fleet.driver import FleetJob, tune_fleet

        job = FleetJob(
            name="job",
            space=space,
            cost_table=np.asarray(cost_table, np.float64),
            full_input_size=full_input_size,
            profile_run=profile_run,
            profile_result=profile_result,
            per_node_overhead=per_node_overhead,
            leeway=leeway,
            flat_fraction=flat_fraction,
        )
        return tune_fleet(
            [job], [rng], settings=settings, to_exhaustion=to_exhaustion,
            objective=objective,
        )[0]
    from repro.fleet.session import canonical_objective

    if canonical_objective(objective) != "runtime":
        raise ValueError(
            "non-runtime objectives need the cost_table path with pricing "
            "axes; a live cost_fn observes a single scalar per trial"
        )

    if profile_result is None and profile_run is None:
        raise ValueError("provide profile_run or profile_result")
    prof = profile_result or profile_job(profile_run, full_input_size)
    prio, rest = split_search_space(
        space,
        prof.model,
        full_input_size,
        per_node_overhead=per_node_overhead,
        leeway=leeway,
        flat_fraction=flat_fraction,
    )
    trace = ruya_search(
        space,
        cost_fn,
        rng,
        prio,
        rest,
        settings=settings,
        to_exhaustion=to_exhaustion,
    )
    return RuyaReport(
        profile=prof, priority=tuple(prio), remaining=tuple(rest), trace=trace
    )


def run_cherrypick(
    *,
    space: SearchSpace,
    cost_fn: Optional[Callable[[int], float]] = None,
    cost_table: Optional[np.ndarray] = None,
    rng: np.random.Generator,
    settings: BOSettings = BOSettings(),
    to_exhaustion: bool = False,
) -> SearchTrace:
    """The baseline, for side-by-side evaluation (paper §IV-C).

    Like `run_ruya`, accepts either a live ``cost_fn`` or a recorded
    ``cost_table`` (the latter runs on the batched fleet engine — since
    PR 4 a deprecation shim over `repro.fleet.session.TuningSession`).
    """
    if (cost_fn is None) == (cost_table is None):
        raise ValueError("provide exactly one of cost_fn / cost_table")
    if cost_table is not None:
        from repro.fleet.driver import FleetJob, tune_fleet

        job = FleetJob(
            name="job", space=space, cost_table=np.asarray(cost_table, np.float64)
        )
        return tune_fleet(
            [job], [rng], mode="cherrypick", settings=settings,
            to_exhaustion=to_exhaustion,
        )[0].trace
    return cherrypick_search(
        space, cost_fn, rng, settings=settings, to_exhaustion=to_exhaustion
    )
