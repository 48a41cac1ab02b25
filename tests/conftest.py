"""Pytest config: make `repro` importable without install; multi-device CPU.

The shard lanes (golden-trace differential tests, shard-invariance
properties) run IN-PROCESS across 1/2/4 shards, so the CPU backend must
expose several host devices before it initializes — this module is
imported before any test module, which makes it the one reliable place to
set the flag (appended only when the caller has not already forced a
count).  Single-device numerics do not depend on the forced count: the
pre-PR-5 tier-1 process ran with 512 forced devices whenever XLA_FLAGS
was unset, and the full suite passes identically at 4.  Subprocess
suites (sharding
equivalence, tiny-mesh dry-runs) still spawn with their own XLA_FLAGS via
`run_with_devices`.

Timing: instead of a single noisy wall-clock warning (the host wobbles
±2×, so a fixed budget produced unattributable alarms), every run of the
tier-1 lane reports its top-10 slowest tests and writes the full per-test
timing table to `artifacts/tier1_timing.json` — regressions are pinned to
a test, not to the weather.  Tests carrying the `kernel` marker (the
Pallas kernel-identity lane) additionally get a per-test 30 s attention
flag in the summary.
"""

import faulthandler
import json
import os
import sys
import time

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
if SRC not in sys.path:
    sys.path.insert(0, os.path.abspath(SRC))

from repro.hostdevices import force_host_device_count  # noqa: E402

force_host_device_count(4)  # shard lanes run 1/2/4 shards in-process

TIMING_JSON = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "artifacts",
                 "tier1_timing.json")
)
_SESSION_T0 = {"t0": None}
_DURATIONS = {}  # nodeid -> summed setup+call+teardown seconds
_KERNEL_NODES = set()  # nodeids carrying the `kernel` marker
_KERNEL_BUDGET_S = 30.0  # per-test ceiling for the kernel-identity lane


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.get_closest_marker("kernel") is not None:
            _KERNEL_NODES.add(item.nodeid)


_SERVICE_WATCHDOG_S = 60.0  # per-test ceiling for threaded service tests


@pytest.fixture(autouse=True)
def _service_watchdog(request):
    """Deadlock insurance for the threaded `service` lane: a wedged
    worker/CV interaction must abort the process WITH all-thread
    tracebacks after 60 s, not hang the suite.  Stdlib `faulthandler`
    (pytest-timeout is not a dependency); armed only for tests carrying
    the ``service`` marker, disarmed on the way out either way."""
    if request.node.get_closest_marker("service") is None:
        yield
        return
    faulthandler.dump_traceback_later(_SERVICE_WATCHDOG_S, exit=True)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()


def pytest_sessionstart(session):
    _SESSION_T0["t0"] = time.time()


def pytest_runtest_logreport(report):
    _DURATIONS[report.nodeid] = (
        _DURATIONS.get(report.nodeid, 0.0) + report.duration
    )


def pytest_terminal_summary(terminalreporter, exitstatus, config):
    t0 = _SESSION_T0["t0"]
    if t0 is None or not _DURATIONS:
        return
    elapsed = time.time() - t0
    markexpr = (getattr(config.option, "markexpr", "") or "").strip()
    is_tier1 = markexpr == "not slow"
    top = sorted(_DURATIONS.items(), key=lambda kv: kv[1], reverse=True)[:10]
    terminalreporter.write_line(
        f"{'tier-1' if is_tier1 else 'lane'} wall clock {elapsed:.0f}s — "
        "10 slowest tests:"
    )
    for nodeid, dur in top:
        terminalreporter.write_line(f"  {dur:7.2f}s  {nodeid}")
    # The kernel-identity lane rides tier-1, so each of its tests carries a
    # hard attention budget: flag (don't fail) any kernel test over 30 s so
    # a compile-time or interpreter regression is pinned the run it lands.
    slow_kernel = sorted(
        ((n, d) for n, d in _DURATIONS.items()
         if n in _KERNEL_NODES and d > _KERNEL_BUDGET_S),
        key=lambda kv: kv[1], reverse=True,
    )
    for nodeid, dur in slow_kernel:
        terminalreporter.write_line(
            f"KERNEL-LANE SLOW: {dur:.1f}s > {_KERNEL_BUDGET_S:.0f}s budget "
            f"— {nodeid}", yellow=True,
        )
    # Machine-readable trail for FULL tier-1 runs only: a file/-k-restricted
    # invocation (or another -m selection) has a different test population
    # and would overwrite the baseline with non-comparable numbers.
    partial = bool(getattr(config.option, "keyword", "")) or bool(
        getattr(config.option, "file_or_dir", [])
    )
    if not is_tier1 or partial:
        return
    payload = {
        "total_s": elapsed,
        "markexpr": markexpr,
        "exitstatus": int(exitstatus),
        "n_tests": len(_DURATIONS),
        "top10": [{"nodeid": n, "s": d} for n, d in top],
        "tests": {n: d for n, d in sorted(_DURATIONS.items())},
    }
    try:
        os.makedirs(os.path.dirname(TIMING_JSON), exist_ok=True)
        with open(TIMING_JSON, "w") as f:
            json.dump(payload, f, indent=1)
        terminalreporter.write_line(f"wrote {TIMING_JSON}")
    except OSError as e:  # never fail the suite over a timing artifact
        terminalreporter.write_line(f"could not write {TIMING_JSON}: {e}",
                                    yellow=True)


def run_with_devices(code: str, n_devices: int = 8, timeout: int = 560) -> str:
    """Run a python snippet in a subprocess with N fake host devices.

    The snippet should print its assertions' evidence; raises on failure.
    """
    import subprocess
    import textwrap

    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = os.path.abspath(SRC)
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    if proc.returncode != 0:
        raise AssertionError(
            f"subprocess failed:\nSTDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-3000:]}"
        )
    return proc.stdout


@pytest.fixture
def devices_runner():
    return run_with_devices
