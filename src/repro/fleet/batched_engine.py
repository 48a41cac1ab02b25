"""Fully-batched multi-job BO search: J searches in lockstep on device.

The sequential engine (`repro.core.bayesopt._bo_loop`) drives one job per
Python-loop iteration, paying a dispatch + host round-trip per BO step —
thousands of synchronizations for a fleet.  Here the whole fleet advances in
lockstep.  (Since the `TuningSession` redesign the chunk lifecycle — group,
admit, step, retire — lives in `repro.fleet.session`, which also serves
streaming submission and warm-starting; `batched_search` below is the
retained one-shot shim, and this module keeps the jitted lockstep update
`_fleet_update` plus the chunking constants both entry points share.
With `shard=`/`devices=`, chunks are additionally bundled across JAX
devices and advanced by one `shard_map` dispatch — see
`repro.fleet.sharding`; traces stay bit-identical either way.)

  * `jax.vmap` over jobs lifts the per-job state (observation mask, packed
    trial log/targets/features — `fast_bo.FleetState`) into batched arrays
    that stay resident on device;
  * one jitted call per iteration applies `fast_bo.fleet_step` to every job
    at once, with the state DONATED to the call so XLA updates the buffers
    in place instead of copying them per iteration; the host only counts
    iterations (all bookkeeping — including per-job stopping — happens on
    device, and iterations dispatch asynchronously, so there are no
    per-step host round-trips);
  * per-job geometry is the static (n,d) float32 encoding — both layouts
    compute their (B,B)/(B,n) distances on the fly from the packed (B,d)
    feature buffer each step (``layout="fused"`` streams the (B,n) part
    through the fused EI/argmax kernel), so nothing of extent n² is ever
    materialized and 10⁴–10⁵-point spaces run in O(n·d) memory;
  * `fleet_step` is the *same compiled program* the sequential path probes,
    so the two engines are trace-identical — `tests/test_fleet.py` asserts
    equal `tried`/`costs`/`stop_iteration` sequences seed-for-seed.  (A
    `lax.while_loop` formulation was rejected: XLA:CPU executes while bodies
    ~5-8× slower than the identical standalone program, and its different
    float32 numerics break trace equivalence with any per-step engine.)

Per-job structure is encoded as masks over a padded configuration axis:
`priority_mask` / `remaining_mask` delimit Ruya's two phases (CherryPick is
priority=everything, remaining=empty), and padded slots belong to neither
pool, so they are never candidates and — by `fast_bo`'s exact masking —
contribute nothing to any posterior.  Jobs are grouped by (space shape,
packed capacity B): the packed factorizations run at static extent B, so a
job must run at exactly the capacity the sequential engine would use for it
to stay float32-identical.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import List, Optional, Sequence, Union

import jax
import numpy as np

from repro.core.bayesopt import BOSettings, SearchTrace, trial_budget
from repro.core.fast_bo import _LAYOUTS, fleet_step
from repro.core.search_space import SearchSpace

__all__ = ["BatchedTrace", "batched_search"]


@dataclasses.dataclass
class BatchedTrace:
    """Trial logs for J searches, padded to the longest run.

    ``tried[j, k]`` is the k-th configuration index tried by job j (-1 pad);
    ``costs`` is aligned with ``tried``; ``stop_iteration``/``phase_boundary``
    are -1 where the event never happened.  ``job_trace(j)`` converts one row
    to the sequential engine's `SearchTrace` so everything downstream of
    either engine speaks the same type.
    """

    tried: np.ndarray  # (J, T) int32, -1 padded
    costs: np.ndarray  # (J, T) float64, aligned with tried
    n_tried: np.ndarray  # (J,) int32
    stop_iteration: np.ndarray  # (J,) int32, -1 = criterion never fired
    phase_boundary: np.ndarray  # (J,) int32, -1 = never left the priority phase

    def __len__(self) -> int:
        return self.tried.shape[0]

    def job_trace(self, j: int) -> SearchTrace:
        k = int(self.n_tried[j])
        stop = int(self.stop_iteration[j])
        pb = int(self.phase_boundary[j])
        return SearchTrace(
            tried=[int(i) for i in self.tried[j, :k]],
            costs=[float(c) for c in self.costs[j, :k]],
            stop_iteration=stop if stop >= 0 else None,
            phase_boundary=pb if pb >= 0 else None,
        )

    def traces(self) -> List[SearchTrace]:
        return [self.job_trace(j) for j in range(len(self))]


# Jobs are processed in lockstep chunks of this extent: small enough that
# the (CHUNK·18, B, B) factorization intermediates stay cache-resident on
# CPU, large enough to amortize dispatch.  Chunk extent must not affect
# results: float32 numerics are batch-extent-invariant for extents in
# [2, 8] (extent 1 compiles to different unbatched programs, hence the ≥2
# padding below; extents ≥ 12 vectorize some reductions differently and
# diverge — verified empirically against the sequential engine, do not
# raise this without re-running tests/test_fleet.py).
_CHUNK = 8
# With early stopping enabled, the host polls the done flags at this period
# (each poll syncs the dispatch queue once).
_POLL_PERIOD = 8


@partial(jax.jit, static_argnames=("xi", "layout"), donate_argnums=(0,))
def _fleet_update(
    state, geom, costs, prio_mask, rem_mask, init_picks, init_count,
    max_trials, min_obs, ei_stop_rel, to_exhaustion, *, xi: float,
    layout: str = "feature",
):
    """One lockstep iteration for a chunk of jobs (vmapped `fleet_step`).

    The state is donated: its buffers alias the outputs, so each fleet
    iteration updates in place — no per-iteration device copies of the
    observation mask or the packed trial/target/feature buffers (asserted
    by `tests/test_fleet.py`).
    """

    def one(s, g, c, p, r, ip, ic, mt):
        return fleet_step(
            s, g, c, p, r, ip, ic, mt, min_obs, ei_stop_rel, to_exhaustion,
            xi, layout,
        )

    return jax.vmap(one)(
        state, geom, costs, prio_mask, rem_mask, init_picks, init_count,
        max_trials,
    )


def _as_space_list(
    spaces: Union[SearchSpace, Sequence[SearchSpace]], n_jobs: int
) -> List[SearchSpace]:
    if isinstance(spaces, SearchSpace):
        return [spaces] * n_jobs
    spaces = list(spaces)
    if len(spaces) != n_jobs:
        raise ValueError(f"{len(spaces)} spaces for {n_jobs} jobs")
    return spaces


def batched_search(
    spaces: Union[SearchSpace, Sequence[SearchSpace]],
    cost_tables: Sequence[np.ndarray],
    rngs: Sequence[np.random.Generator],
    *,
    priority: Optional[Sequence[Sequence[int]]] = None,
    remaining: Optional[Sequence[Sequence[int]]] = None,
    settings: BOSettings = BOSettings(),
    to_exhaustion: bool = False,
    layout: str = "feature",
    shard=None,
    devices=None,
) -> BatchedTrace:
    """Run J independent BO searches in lockstep on device.

    ``spaces`` may be a single shared `SearchSpace` or one per job.  Jobs are
    grouped by (space shape, trial budget) — each group runs unpadded at its
    own packed capacity, so a heterogeneous fleet stays bitwise-identical to
    the per-job sequential engine (padding a 10-config job into a 20-slot
    batch, or a 10-trial budget into a 20-slot packed buffer, would be
    mathematically exact but not float32-identical).  ``cost_tables[j][i]``
    is the cost job j observes for configuration i — the full table lives on
    device so the loop never leaves it.  ``priority``/``remaining`` give
    each job's Ruya split (omitted → plain CherryPick over the whole space).
    The random initialization consumes ``rngs[j]`` exactly like the
    sequential engine, so seed-matched runs produce identical traces.
    ``layout`` selects the EI tail: "feature" (default, the (B,n) cross
    block in XLA) or "fused" (the streaming EI/argmax kernel); both hold
    O(n·d) memory.
    ``shard``/``devices`` shard the job axis across JAX devices
    (`repro.fleet.sharding`) — a pure execution optimization, pinned
    bit-identical to the single-device default by `tests/golden/`.

    Since the `TuningSession` redesign this is a thin shim: submit every
    job to a fresh session (no profiling, no warm-starting — the splits are
    passed verbatim), drain it, and repackage the outcomes.  A statically
    submitted session runs the identical grouping/chunking/array program
    this module ran pre-redesign, so traces are unchanged bit-for-bit
    (`tests/test_fleet.py` / `tests/test_session.py`).
    """
    from repro.fleet.driver import FleetJob
    from repro.fleet.session import TuningSession

    if layout not in _LAYOUTS:
        raise ValueError(f"unknown layout {layout!r}; want one of {_LAYOUTS}")
    n_jobs = len(cost_tables)
    if len(rngs) != n_jobs:
        raise ValueError(f"{len(rngs)} rngs for {n_jobs} jobs")
    space_list = _as_space_list(spaces, n_jobs)
    if priority is None:
        priority = [list(range(len(s))) for s in space_list]
    if remaining is None:
        remaining = [[] for _ in range(n_jobs)]

    session = TuningSession(
        settings=settings, mode="cherrypick", warm_start=False,
        to_exhaustion=to_exhaustion, layout=layout, shard=shard,
        devices=devices,
    )
    for j, (space, table, rng) in enumerate(zip(space_list, cost_tables, rngs)):
        session.submit(
            FleetJob(name=f"job{j}", space=space, cost_table=table),
            rng,
            priority=[int(i) for i in priority[j]],
            remaining=[int(i) for i in remaining[j]],
        )
    outs = session.drain()

    budgets = [
        trial_budget(len(priority[j]), len(remaining[j]), settings)
        for j in range(n_jobs)
    ]
    max_T = max(max(budgets, default=0), 1)
    tried = np.full((n_jobs, max_T), -1, np.int32)
    out_costs = np.zeros((n_jobs, max_T), np.float64)
    n_tried = np.zeros(n_jobs, np.int32)
    stop = np.full(n_jobs, -1, np.int32)
    pb = np.full(n_jobs, -1, np.int32)
    for j, out in enumerate(outs):
        k = len(out.records)
        tried[j, :k] = [r.index for r in out.records]
        out_costs[j, :k] = [r.cost for r in out.records]
        n_tried[j] = k
        stop[j] = -1 if out.stop_iteration is None else out.stop_iteration
        pb[j] = -1 if out.phase_boundary is None else out.phase_boundary
    return BatchedTrace(
        tried=tried,
        costs=out_costs,
        n_tried=n_tried,
        stop_iteration=stop,
        phase_boundary=pb,
    )
