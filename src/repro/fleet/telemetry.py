"""Spans and counters of the tuning service.

Spans are `jax.profiler.TraceAnnotation`s named ``tuning.*``: while a
profiler trace records (`jax.profiler.trace` around a window), they land
on the host threads that ran them, on the same clock as the device's
"XLA Ops", and nesting on one thread gives the parent.  Off, a span costs
one inactive annotation (well under a microsecond); its keyword
arguments are attached only while a trace records.

    span                 where (repro.fleet)          covers
    tuning.submit        TuningSession.submit         profile, split, enqueue
    tuning.split         TuningSession._submit_locked the §III-D split
    tuning.admit         TuningSession._admit_group   a non-empty admission
    tuning.chunk_arrays  _build_chunk/_build_sharded  host state and args
    tuning.device_put    _build_chunk/_build_sharded  their transfers
                                                      (puts, bytes)
    tuning.dispatch      TuningSession._step_chunk    enqueue of one update
                                                      (rows, slots: `ei_work`)
    tuning.poll          TuningSession._step_chunk    the done-flag sync
    tuning.retire        TuningSession._step_chunk    sync, retire, publish
    tuning.publish       TuningSession._publish's     one search's outcome,
                         callers                      history, listeners
    tuning.lock_wait     the session lock             a contended acquire
    tuning.idle          TuningService._idle_wait     a worker with no work

Counters are always on and are timed with `time.perf_counter` at the
boundaries of the matching span: per admission group (`GroupCounters`)
and session-wide (`Telemetry`, the lock waits).  `TuningService.metrics()`
reports them.  Besides times, a group counts the GP head's column-loop
trips on the TPU and its capacity (`head_slots`), the rows and observed
slots the EI tail worked on (`ei_work`), and admission's transfers: all
of them with their bytes, and those of a space's geometry, which stays
on the device for the chunks after (`geom_puts`, `geom_reuses`).
"""

from __future__ import annotations

import threading
import time
from typing import Dict

import numpy as np
from jax.profiler import TraceAnnotation

__all__ = ["GroupCounters", "Telemetry", "TimedLock", "ei_work",
           "head_slots", "recording", "span"]

recording = TraceAnnotation.is_enabled


def span(name: str, **args) -> TraceAnnotation:
    """A ``with`` span on the profiler's clock; ``args`` are attached
    only while a trace records."""
    if args and recording():
        return TraceAnnotation(name, **args)
    return TraceAnnotation(name)


class GroupCounters:
    """One admission group's counters.  Each group is advanced by one
    thread at a time (its service worker, or the caller of `step()`), so
    the fields are plain attributes."""

    __slots__ = ("dispatches", "polls", "admissions", "empty_admissions",
                 "admit_s", "dispatch_s", "poll_wait_s", "retire_s",
                 "head_slots", "head_capacity_slots", "ei_rows", "ei_slots",
                 "admit_puts", "admit_bytes", "geom_puts", "geom_reuses")

    def __init__(self) -> None:
        self.dispatches = 0  # update enqueues (`tuning.dispatch`)
        self.polls = 0  # done-flag syncs (`tuning.poll`)
        self.admissions = 0  # non-empty admissions (`tuning.admit`)
        self.empty_admissions = 0  # admission polls that found nothing
        self.admit_s = 0.0
        self.dispatch_s = 0.0
        self.poll_wait_s = 0.0
        self.retire_s = 0.0
        # Over retired chunks: the GP head's trip bound summed over each
        # chunk's dispatches (`head_slots`), and its capacity B summed
        # over the same dispatches.
        self.head_slots = 0
        self.head_capacity_slots = 0
        # Over retired chunks: the EI tail's rows and slots (`ei_work`)
        # summed over each chunk's dispatches, as the dispatch spans
        # give them one by one.
        self.ei_rows = 0
        self.ei_slots = 0
        # Host-to-device transfers at admission and their bytes, as the
        # `tuning.device_put` spans give them; of them, the puts of a
        # space's geometry to a device, and the chunks' uses of one
        # already there (one per distinct space of a chunk).
        self.admit_puts = 0
        self.admit_bytes = 0
        self.geom_puts = 0
        self.geom_reuses = 0

    def as_dict(self) -> dict:
        return {f: getattr(self, f) for f in self.__slots__}


def head_slots(t_admit, t_retired, dispatches: int) -> int:
    """The GP head's trip bound summed over a chunk's ``dispatches``.

    On the TPU the head's column loop runs to the largest observation
    count t among the rows it advances together (`fast_bo._factor_loop`
    under the chunk's vmap).  A row's t grows by one per dispatch until
    its search stops, and never after, so at dispatch s it is
    min(t_admit + s, t_retired): the bound follows from each row's t at
    admission and at retirement, with no device sync of its own.
    ``t_admit`` and ``t_retired`` are (chunks, rows), one chunk per shard.
    """
    s = np.arange(dispatches)[:, None, None]
    per_step = np.minimum(np.asarray(t_admit) + s, np.asarray(t_retired))
    return int(per_step.max(axis=-1).sum())


def ei_work(t_admit, budget, members: int, polls, steps):
    """(rows, slots) of the EI tail at each of a chunk's dispatch indices
    ``steps``: ``rows`` counts its first ``members`` rows (dummy pads
    trail them) not known done at the last poll before the dispatch, and
    ``slots`` sums those rows' observation counts t.

    A row's t at dispatch s is at most min(t_admit + s, budget): it grows
    by one per dispatch until the search stops, which the host learns
    only at a poll.  ``t_admit`` and ``budget`` are (chunks, rows), one
    chunk per shard, flattened in member order; ``polls`` is
    [(dispatches done, flat done flags)] in order.  Returns two arrays
    over ``steps``.
    """
    s = np.asarray(steps, np.int64)[:, None]
    t0 = np.asarray(t_admit).reshape(-1)[:members]
    cap = np.asarray(budget).reshape(-1)[:members]
    searching = np.ones((s.shape[0], members), bool)
    for at, flags in polls:
        searching[s[:, 0] >= at] = ~flags[:members]
    t = np.minimum(t0 + s, cap)
    return searching.sum(axis=1), np.where(searching, t, 0).sum(axis=1)


class Telemetry:
    """A session's counters: one `GroupCounters` per admission group,
    and the session lock's contended waits."""

    def __init__(self) -> None:
        self._groups: Dict[tuple, GroupCounters] = {}
        self._new = threading.Lock()
        self.lock_waits = 0
        self.lock_wait_s = 0.0

    def group(self, key: tuple) -> GroupCounters:
        g = self._groups.get(key)
        if g is None:
            with self._new:
                g = self._groups.setdefault(key, GroupCounters())
        return g

    def groups(self) -> Dict[tuple, dict]:
        with self._new:
            items = list(self._groups.items())
        return {k: g.as_dict() for k, g in items}


class TimedLock:
    """The session's re-entrant lock, recording contended acquisitions.

    A non-blocking try comes first; only when it fails is the wait
    timed, inside a ``tuning.lock_wait`` span.  The counters are updated
    once the lock is held, so they need no lock of their own."""

    __slots__ = ("_lock", "_telemetry")

    def __init__(self, telemetry: Telemetry) -> None:
        self._lock = threading.RLock()
        self._telemetry = telemetry

    def acquire(self, blocking: bool = True, timeout: float = -1) -> bool:
        if self._lock.acquire(False):
            return True
        if not blocking:
            return False
        t0 = time.perf_counter()
        with TraceAnnotation("tuning.lock_wait"):
            got = self._lock.acquire(True, timeout)
        if got:
            self._telemetry.lock_waits += 1
            self._telemetry.lock_wait_s += time.perf_counter() - t0
        return got

    def release(self) -> None:
        self._lock.release()

    def __enter__(self) -> bool:
        return self.acquire()

    def __exit__(self, *exc) -> None:
        self._lock.release()
