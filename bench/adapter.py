"""The benchmark's one bridge to the tuning service under test.

Everything the benchmark knows of the program is here: how a deployment's
settings become a `TuningService`, how the space data of `spaces/` becomes
`FleetJob`s, and the hooks that time a search.  The rest of `bench/`
imports nothing of `src/`.

The public surface (`TuningService.submit`, `pause`, `drain`, `metrics`,
`shutdown`, `JobHandle`) drives the traffic.  `BenchSession` subclasses
`TuningSession` for what that surface does not give; each override is a
private hook of the program:

  * `_admit_group` — when a search is admitted into a chunk (queue wait);
  * `_step_chunk` — the count of chunk dispatches;
  * `_publish` — when a search's outcome is published (completion).

Each is wrapped in a `jax.profiler.TraceAnnotation` so that a traced run
can attribute the device's idle gaps to what the host was doing.
"""

from __future__ import annotations

import itertools
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if os.path.join(ROOT, "src") not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
from jax.profiler import TraceAnnotation  # noqa: E402

from repro.core.bayesopt import BOSettings  # noqa: E402
from repro.core.memory_model import MemoryCategory, MemoryModel  # noqa: E402
from repro.core.profiler import ProfileResult  # noqa: E402
from repro.core.search_space import Configuration, SearchSpace  # noqa: E402
from repro.fleet import FleetJob, TuningService, TuningSession  # noqa: E402


class BenchSession(TuningSession):
    """`TuningSession` with the benchmark's timestamps and counters (see
    the module docstring).  Times are `time.perf_counter` seconds."""

    def __init__(self, **kwargs) -> None:
        super().__init__(**kwargs)
        self.admitted_at: dict = {}
        self.completed_at: dict = {}
        self._dispatches = itertools.count(1)
        self.dispatches = 0

    def _admit_group(self, key, device=None) -> int:
        with TraceAnnotation("bench.admit"), self._lock:
            uids = [r.handle.uid for r in self._pending
                    if (r.enc.shape, r.budget) == key]
            admitted = super()._admit_group(key, device=device)
            now = time.perf_counter()
            for uid in uids:
                self.admitted_at[uid] = now
        return admitted

    def _step_chunk(self, ch) -> str:
        with TraceAnnotation("bench.chunk_step"):
            self.dispatches = next(self._dispatches)
            return super()._step_chunk(ch)

    def _publish(self, rec, *args, **kwargs) -> None:
        self.completed_at[rec.handle.uid] = time.perf_counter()
        super()._publish(rec, *args, **kwargs)


def build_jobs(data: dict) -> list:
    """One `FleetJob` per job of a space generator's data; the jobs share
    one `SearchSpace`.  A job with a memory model gets its profile
    resolved here, so that submits do no profiling runs."""
    space = SearchSpace([
        Configuration(name=f"c{i}", features=tuple(float(v) for v in f),
                      total_memory=float(m), num_nodes=int(k))
        for i, (f, m, k) in enumerate(zip(
            data["features"], data["total_memory"], data["num_nodes"]))
    ])
    jobs = []
    for spec in data["jobs"]:
        job = FleetJob(name=spec["name"], space=space, cost_table=spec["cost"])
        if "memory_model" in spec:
            mm, prof = spec["memory_model"], spec["profile"]
            model = MemoryModel(
                category=MemoryCategory(mm["category"]), slope=mm["slope"],
                intercept=mm["intercept"], r2=mm["r2"],
                sizes=tuple(prof["sizes"]), readings=tuple(prof["readings"]),
            )
            job.full_input_size = spec["full_input_size"]
            job.per_node_overhead = spec["per_node_overhead"]
            job.leeway = spec["leeway"]
            job.flat_fraction = spec["flat_fraction"]
            job.profile_result = ProfileResult(
                sizes=tuple(prof["sizes"]), readings=tuple(prof["readings"]),
                total_time_s=prof["total_time_s"],
                calibration_runs=prof["calibration_runs"], model=model,
            )
        jobs.append(job)
    return jobs


def make_service(cfg: dict, chips: int):
    """(service, session): a `TuningService` over a `BenchSession` with the
    deployment's settings, on the first ``chips`` devices."""
    svc_cfg = cfg["service"]
    session = BenchSession(
        settings=BOSettings(**svc_cfg["settings"]), mode=svc_cfg["mode"],
        warm_start=svc_cfg["warm_start"],
        to_exhaustion=svc_cfg["to_exhaustion"], layout=svc_cfg["layout"],
        shard=svc_cfg["shard"],
    )
    return TuningService(session, devices=jax.devices()[:chips]), session


def submit(svc: TuningService, job, seed: int):
    with TraceAnnotation("bench.submit"):
        return svc.submit(job, seed=seed)


def view(handle) -> dict:
    """A finished search as plain data for the checks: its trials in
    order and its split."""
    out = handle.outcome()
    return {
        "status": out.status,
        "trials": [r.index for r in out.observations],
        "costs": [r.cost for r in out.observations],
        "n_init": sum(r.source == "init" for r in out.records),
        "priority": out.priority,
        "remaining": out.remaining,
    }
