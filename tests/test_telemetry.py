"""The tuning service's spans and counters (`repro.fleet.telemetry`).

Runs the service under `jax.profiler.trace` into a temporary directory
and reads the ``tuning.*`` spans back from the recorded ``.xplane.pb``
with `jax.profiler.ProfileData`: which spans appear, how they nest on
their thread, and that each counter of `TuningService.metrics()` agrees
with its span.
"""

import glob
import importlib.util
import json
import os
import sys
import threading
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.bayesopt import BOSettings
from repro.fleet import FleetJob, TuningService, TuningSession
from repro.fleet.telemetry import Telemetry, TimedLock

from golden.scenarios import synth_space_table

pytestmark = pytest.mark.service

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _session_kwargs():
    return dict(layout="feature", settings=BOSettings(max_iters=10),
                warm_start=False)


def _spans(log_dir):
    """Every ``tuning.*`` span of the trace as a dict with its thread
    (line), its interval, its arguments and its enclosing spans on the
    same thread (innermost first)."""
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line_no, line in enumerate(plane.lines):
            evs = sorted(
                ((e.start_ns, e.start_ns + e.duration_ns, e.name,
                  dict(e.stats)) for e in line.events
                 if e.name.startswith("tuning.")),
                key=lambda ev: (ev[0], -ev[1]))
            stack = []
            for s, e, name, args in evs:
                while stack and stack[-1]["end"] <= s:
                    stack.pop()
                sp = {"name": name, "thread": (plane.name, line_no),
                      "start": s, "end": e, "args": args,
                      "parents": [p["name"] for p in reversed(stack)]}
                out.append(sp)
                stack.append(sp)
    return out


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


class TestSpans:
    def test_two_group_service_spans_nest_as_documented(self, tmp_path):
        small, small_table = synth_space_table(24)
        large, large_table = synth_space_table(69)
        svc = TuningService(**_session_kwargs())
        with jax.profiler.trace(str(tmp_path)):
            svc.pause()
            for s in range(3):
                for tag, space, table in (("a", small, small_table),
                                          ("b", large, large_table)):
                    svc.submit(FleetJob(name=f"{tag}{s}", space=space,
                                        cost_table=table),
                               seed=s, mode="cherrypick")
            time.sleep(0.05)  # the paused workers idle
            svc.drain()
            # Joins the workers, so that every span has closed.
            svc.shutdown(drain=False)
        m = svc.metrics()
        json.dumps(m)  # the operator's surface stays JSON-able
        spans = _spans(str(tmp_path))

        names = {s["name"] for s in spans}
        assert names >= {"tuning.submit", "tuning.admit",
                         "tuning.chunk_arrays", "tuning.device_put",
                         "tuning.dispatch", "tuning.poll", "tuning.retire",
                         "tuning.idle"}
        assert len(_named(spans, "tuning.submit")) == 6
        assert sorted(s["args"]["uid"]
                      for s in _named(spans, "tuning.submit")) == list(
                          range(6))
        for name in ("tuning.chunk_arrays", "tuning.device_put"):
            assert all("tuning.admit" in s["parents"]
                       for s in _named(spans, name))
        for name in ("tuning.poll", "tuning.retire"):
            assert all("tuning.dispatch" not in s["parents"]
                       for s in _named(spans, name))
        admits = _named(spans, "tuning.admit")
        assert sum(s["args"]["rows"] for s in admits) == 6
        assert all(s["args"]["chunks"] == 1 for s in admits)

        groups = m["groups"]
        assert len(groups) == 2
        # Each counter counts exactly the spans opened at its boundary.
        assert sum(g["dispatches"] for g in groups.values()) == len(
            _named(spans, "tuning.dispatch"))
        assert sum(g["polls"] for g in groups.values()) == len(
            _named(spans, "tuning.poll"))
        assert sum(g["admissions"] for g in groups.values()) == len(admits)
        for g in groups.values():
            assert g["dispatches"] > 0 and g["dispatch_s"] > 0
            assert g["admissions"] >= 1 and g["admit_s"] > 0
            assert g["retire_s"] > 0 and g["poll_wait_s"] >= 0
        assert len(_named(spans, "tuning.lock_wait")) == m["lock_waits"]

    def test_empty_admission_emits_no_span(self, tmp_path):
        session = TuningSession(**_session_kwargs())
        key = ((24, 5), 10)
        with jax.profiler.trace(str(tmp_path)):
            assert session._admit_group(key) == 0
        assert _named(_spans(str(tmp_path)), "tuning.admit") == []
        counters = session.telemetry.groups()[key]
        assert counters["empty_admissions"] == 1
        assert counters["admissions"] == 0 and counters["admit_s"] == 0.0

    def test_blocked_submit_records_one_lock_wait(self, tmp_path):
        session = TuningSession(**_session_kwargs())
        space, table = synth_space_table(24)
        held, release = threading.Event(), threading.Event()

        def worker():
            with session._lock:
                held.set()
                release.wait(10.0)

        def submitter():
            session.submit(FleetJob(name="late", space=space,
                                    cost_table=table),
                           seed=0, mode="cherrypick")

        with jax.profiler.trace(str(tmp_path)):
            holder = threading.Thread(target=worker)
            holder.start()
            assert held.wait(10.0)
            sub = threading.Thread(target=submitter)
            sub.start()
            time.sleep(0.05)
            assert sub.is_alive()  # parked on the session lock
            release.set()
            sub.join(10.0)
            holder.join(10.0)
        assert not sub.is_alive() and not holder.is_alive()
        assert session.telemetry.lock_waits == 1
        assert session.telemetry.lock_wait_s >= 0.04
        (wait,) = _named(_spans(str(tmp_path)), "tuning.lock_wait")
        assert wait["parents"] == ["tuning.submit"]
        assert wait["end"] - wait["start"] >= 0.04e9

        svc = TuningService(session)
        try:
            m = svc.metrics()
        finally:
            svc.shutdown(drain=False)
        json.dumps(m)
        assert m["lock_waits"] == 1
        assert m["lock_wait_s"] == session.telemetry.lock_wait_s


class TestHeadSlots:
    @pytest.mark.parametrize("cancel", [False, True])
    def test_static_chunk_counts_its_largest_t_per_dispatch(self, cancel):
        """``head_slots`` is the GP head's trip bound (the chunk's largest
        t) summed over the chunk's dispatches, read before each one here;
        ``head_capacity_slots`` is B per dispatch.  A member cancelled
        mid-flight keeps its frozen t in the chunk."""
        session = TuningSession(**_session_kwargs())
        space, table = synth_space_table(24)
        handles = [
            session.submit(FleetJob(name=f"j{s}", space=space,
                                    cost_table=table),
                           seed=s, mode="cherrypick")
            for s in range(5)
        ]
        session._admit()
        (ch,) = session._chunks
        bounds = []
        while session._chunks:
            bounds.append(int(np.asarray(ch.state.t).max()))
            session.step()
            if cancel and len(bounds) == 4:
                assert session.cancel(handles[0])
        (g,) = session.telemetry.groups().values()
        assert g["dispatches"] == len(bounds)
        assert g["head_slots"] == sum(bounds)
        assert g["head_capacity_slots"] == len(bounds) * ch.capacity
        assert 0 < g["head_slots"] < g["head_capacity_slots"]


class TestAdmissionTransfers:
    def test_one_put_per_chunk_and_per_new_space(self, tmp_path,
                                                 monkeypatch):
        """Admission makes one host-to-device transfer per chunk, and one
        per space whose geometry is not on the device yet.  The counters
        equal what the ``tuning.device_put`` spans' arguments say, and
        what the transfers carried."""
        session = TuningSession(**_session_kwargs())
        space, table = synth_space_table(24)
        sent = []
        put = jax.device_put

        def counting_put(x, *args, **kwargs):
            sent.append(np.asarray(x).nbytes)
            return put(x, *args, **kwargs)

        monkeypatch.setattr(jax, "device_put", counting_put)

        def admit(first, count):
            for s in range(first, first + count):
                session.submit(FleetJob(name=f"j{s}", space=space,
                                        cost_table=table),
                               seed=s, mode="cherrypick")
            (key,) = session._pending_group_keys()
            del sent[:]
            assert session._admit_group(key) == count
            return len(sent), sum(sent)

        with jax.profiler.trace(str(tmp_path)):
            # Two chunks (8 + 2 rows) and the space's geometry.
            puts_a, bytes_a = admit(0, 10)
            # Chunks of the first admission keep the geometry on device.
            puts_b, bytes_b = admit(10, 9)
        assert (puts_a, puts_b) == (3, 2)

        (g,) = session.telemetry.groups().values()
        assert g["admit_puts"] == 5 and g["admit_bytes"] == bytes_a + bytes_b
        assert (g["geom_puts"], g["geom_reuses"]) == (1, 3)
        spans = _named(_spans(str(tmp_path)), "tuning.device_put")
        assert len(spans) == 4
        assert all("tuning.admit" in s["parents"] for s in spans)
        assert sum(s["args"]["puts"] for s in spans) == g["admit_puts"]
        assert sum(s["args"]["bytes"] for s in spans) == g["admit_bytes"]


class TestPublishSpans:
    @pytest.mark.parametrize("cancel", [False, True])
    def test_one_publish_span_per_published_search(self, tmp_path, cancel):
        """Every publication opens one ``tuning.publish`` span: those of a
        chunk's retirement under ``tuning.retire``, a mid-flight cancel's
        outside it."""
        session = TuningSession(**_session_kwargs())
        space, table = synth_space_table(24)
        with jax.profiler.trace(str(tmp_path)):
            handles = [
                session.submit(FleetJob(name=f"j{s}", space=space,
                                        cost_table=table),
                               seed=s, mode="cherrypick")
                for s in range(5)
            ]
            if cancel:
                session._admit()
                session.step()
                assert session.cancel(handles[0])
            outs = session.drain()
        spans = _named(_spans(str(tmp_path)), "tuning.publish")
        assert len(outs) == 5
        assert len(spans) == len(outs)
        retired = [s for s in spans if "tuning.retire" in s["parents"]]
        assert len(retired) == 5 - int(cancel)
        assert [o.status for o in outs].count("cancelled") == int(cancel)


def _bench_module(monkeypatch, *parts):
    """A module of the benchmark (``bench/``), loaded as the harness
    loads it, with ``bench/`` importable for its own imports."""
    bench = os.path.join(ROOT, "bench")
    monkeypatch.syspath_prepend(bench)
    path = os.path.join(bench, *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + "_".join(parts), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestPublishReader:
    """``bench/metrics/publish_ms_per_search.catalog.py`` on a hand-made
    span table: the mean ``tuning.publish`` span in the window, in ms."""

    @staticmethod
    def _trace(names):
        a, b = ("/host:CPU", 1), ("/host:CPU", 2)
        spans = {
            "tuning.publish": [
                (-2_000_000, 1_000_000, a),  # starts before the window
                (10_000_000, 13_000_000, a),  # under a retirement
                (14_000_000, 15_000_000, a),
                (30_000_000, 32_000_000, b),  # a cancel's, elsewhere
            ],
            "tuning.retire": [(9_000_000, 20_000_000, a)],
        }
        return {
            "devices": {},
            "spans": sorted((s, e, name, tid) for name in names
                            for s, e, tid in spans[name]),
            "window": (0, 100_000_000),
        }

    def test_mean_publish_span_in_ms(self, monkeypatch):
        reader = _bench_module(monkeypatch, "metrics",
                               "publish_ms_per_search.catalog.py")
        trace = self._trace(["tuning.publish", "tuning.retire"])
        monkeypatch.setattr(reader.program_trace, "for_run",
                            lambda ctx: reader.program_trace.reduce(trace))
        assert reader.read({}) == pytest.approx((3.0 + 1.0 + 2.0) / 3)

    def test_nothing_to_read_gives_none(self, monkeypatch):
        reader = _bench_module(monkeypatch, "metrics",
                               "publish_ms_per_search.catalog.py")
        pt = reader.program_trace
        # A program without the span, as before publication was traced.
        trace = self._trace(["tuning.retire"])
        monkeypatch.setattr(pt, "for_run", lambda ctx: pt.reduce(trace))
        assert reader.read({}) is None
        monkeypatch.setattr(pt, "for_run", lambda ctx: None)
        assert reader.read({}) is None


class TestTimedLock:
    def test_reentrant_and_uncontended_acquires_count_nothing(self):
        tel = Telemetry()
        lock = TimedLock(tel)
        with lock:
            with lock:
                assert lock.acquire(blocking=False)
                lock.release()
        assert tel.lock_waits == 0 and tel.lock_wait_s == 0.0

    def test_non_blocking_try_on_a_held_lock_fails_uncounted(self):
        tel = Telemetry()
        lock = TimedLock(tel)
        got = []
        with lock:
            t = threading.Thread(
                target=lambda: got.append(lock.acquire(blocking=False)))
            t.start()
            t.join(10.0)
        assert not t.is_alive()
        assert got == [False]
        assert tel.lock_waits == 0

    def test_contended_waits_are_counted_without_lost_updates(self):
        """Many threads on one lock: every failed non-blocking try becomes
        exactly one counted wait, and the lock still excludes."""

        class CountingRLock:
            def __init__(self):
                self._inner = threading.RLock()
                self._guard = threading.Lock()
                self.failed_tries = 0

            def acquire(self, blocking=True, timeout=-1):
                got = self._inner.acquire(blocking, timeout)
                if not blocking and not got:
                    with self._guard:
                        self.failed_tries += 1
                return got

            def release(self):
                self._inner.release()

        tel = Telemetry()
        lock = TimedLock(tel)
        inner = lock._lock = CountingRLock()
        shared = [0]
        n_threads, n_iter = 16, 200

        def work():
            for _ in range(n_iter):
                with lock:
                    v = shared[0]
                    time.sleep(0)
                    shared[0] = v + 1

        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [threading.Thread(target=work)
                       for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60.0)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads)
        assert shared[0] == n_threads * n_iter
        assert inner.failed_tries > 0
        assert tel.lock_waits == inner.failed_tries
        assert tel.lock_wait_s > 0.0
