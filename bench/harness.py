"""One run of one benchmark cell: set-up, the measured window, the checks.

Everything that belongs to one cell is found by name: the cell's entry
in ``BENCHMARK.json`` names its configuration (``configs/<name>.json``)
and traffic mix (``traffic/<name>.json``); the configuration names its
space generator (``spaces/<generator>.py``), the mix its kind
(``kinds/<kind>.py``), and each per-layer metric that lists the cell has
its reader (``metrics/<metric>.py``).

`run` returns the result line's object and the lines for standard error;
`bench/run.py` is the command that prints them.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time
from types import SimpleNamespace

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
OUT_DIR = os.path.join(ROOT, "bench_out")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import check  # noqa: E402


def _module(kind: str, name: str):
    path = os.path.join(BENCH, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str, bench: dict | None = None) -> dict:
    """The cell's workload entry with its configuration, traffic and
    metric definitions."""
    bench = bench or _json(ROOT, "BENCHMARK.json")
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = work[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(m):
        return name in m.get("workloads", [name])

    return {
        "workload": w,
        "cfg": _json(ROOT, cfg_entry["file"]),
        "traffic": _json(BENCH, "traffic", w["traffic"] + ".json"),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


def process_age() -> float:
    """Seconds since this process started (Linux /proc, clock ticks)."""
    with open("/proc/self/stat") as f:
        start = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start / os.sysconf("SC_CLK_TCK")


class _CompileCounter:
    """Counts JAX traces and backend compiles while ``on``."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        import jax.monitoring

        self.on = False
        self.seen = {e: 0 for e in self.EVENTS}
        jax.monitoring.register_event_duration_secs_listener(self._hear)

    def _hear(self, event, duration, **kwargs):
        if self.on and event in self.seen:
            self.seen[event] += 1


def setup(cell: dict, seed: int, chips: int):
    """Data from the seed, the service, and warm-up of the shapes the
    cell's traffic hits.  Returns the run context."""
    import adapter

    cfg = cell["cfg"]
    t0 = time.perf_counter()
    data = _module("spaces", cfg["space"]["generator"]).make(cfg["space"],
                                                             seed)
    svc, session = adapter.make_service(cfg, chips)
    ctx = SimpleNamespace(
        cfg=cfg, traffic=cell["traffic"], seed=seed, data=data,
        jobs=adapter.build_jobs(data), svc=svc, session=session,
        kind=_module("kinds", cell["traffic"]["kind"]),
    )
    t1 = time.perf_counter()
    ctx.kind.warm(ctx)
    ctx.setup_parts = {"data_and_service_s": t1 - t0,
                       "warm_up_s": time.perf_counter() - t1}
    return ctx


def window(ctx, seconds: float, trace_dir: str | None, counter) -> dict:
    """The measured window, with the profiler on around it when
    ``trace_dir`` is given."""
    import jax
    from jax.profiler import TraceAnnotation

    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    d0 = ctx.session.dispatches
    counter.on = True
    try:
        with TraceAnnotation("bench.window"):
            win = ctx.kind.run(ctx, seconds)
    finally:
        counter.on = False
        if trace_dir:
            jax.profiler.stop_trace()
    win["dispatches"] = ctx.session.dispatches - d0
    return win


def searches_of(ctx, win) -> list:
    """The window's searches as plain data for the checks."""
    import adapter

    out = []
    for s in win["searches"]:
        h = s["handle"]
        v = adapter.view(h) if h.done else {
            "status": None, "trials": [], "costs": [], "n_init": 0,
            "priority": (), "remaining": ()}
        v["job"] = s["job"]
        out.append(v)
    return out


def memory_peak(chips: int) -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:chips]]
    return int(max(peaks))


def run(name: str, seed: int, seconds: float, trace: bool,
        cell: dict | None = None) -> tuple:
    """One run of cell ``name`` (``cell`` in place of its definition in
    ``BENCHMARK.json``, where given).  Returns (result object, lines for
    standard error)."""
    import jax

    cell = cell or load_cell(name)
    chips = cell["workload"]["chips"]
    counter = _CompileCounter()
    ctx = setup(cell, seed, chips)
    trace_dir = None
    if trace:
        trace_dir = os.path.join(OUT_DIR, "trace", f"{name}-{seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    setup_s = process_age()
    win = window(ctx, seconds, trace_dir, counter)
    mem = memory_peak(chips)
    ctx.svc.shutdown(drain=False)

    searches = searches_of(ctx, win)
    checker = check.Checker(ctx.cfg, ctx.data)
    numbers = checker.rules(searches)
    states = checker.sample(searches, seed)
    numbers.update(checker.readings(states, check.service_answer))
    correct = check.verdict(numbers, checker.limits)

    published = [s for s in searches if s["status"] is not None]
    failed = sum(s["status"] != "converged" for s in searches)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    counters = {"dispatches": win["dispatches"], "searches": len(published)}
    metrics = {}
    result = {"correct": correct, "attempted": len(searches),
              "failed": failed}
    if trace:
        import trace_reduce

        reduced = trace_reduce.reduce(trace_reduce.load(
            trace_reduce.find(trace_dir)))
        traced = list(reduced["devices"].values())
        device["busy_s"] = (sum(d["busy_s"] for d in traced) / len(traced)
                            if traced else 0.0)
        device["window_s"] = reduced["window_s"]
        mctx = {"trace": reduced, "counters": counters,
                "notes": win["notes"], "cfg": ctx.cfg}
        for m in cell["per_layer"]:
            v = _module("metrics", m["name"]).read(mctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        busiest = max(traced, key=lambda d: d["busy_s"], default=None)
        if busiest is not None:
            result["breakdown"] = {"device_ops": busiest["top_ops"],
                                   "idle_gaps": busiest["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        for m in cell["end_to_end"]:
            if m["name"] == "setup_s":
                v = setup_s
            else:
                v = win["e2e"].get(m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    result["checks"] = {k: {"value": numbers[k], "limit": lim}
                        for k, lim in checker.limits.items() if k in numbers}

    notes = dict(win["notes"])
    notes.pop("queue_wait_s", None)
    trials = [len(s["trials"]) for s in searches if s["status"]]
    lines = [
        f"cell {name} seed {seed}: {len(searches)} searches, "
        f"{counters['dispatches']} dispatches, window "
        f"{win['t_end'] - win['t0']:.3f} s, set-up {setup_s:.3f} s "
        f"(of it {json.dumps(ctx.setup_parts)}; the rest is start-up: "
        "interpreter, imports, JAX and its devices)",
        f"compilations inside the window: {counter.seen}",
        f"trials per search: mean {np.mean(trials) if trials else 0:.2f}, "
        f"min {min(trials, default=0)}, max {max(trials, default=0)}",
        f"window: {json.dumps(win['e2e'])}; generator: {notes}",
        f"service groups: {json.dumps(_groups(ctx))}",
        f"checked against the reference: {len(states)} states",
    ] + [f"{k}: {v['value']!r} (limit {v['limit']!r})"
         for k, v in result["checks"].items()]
    return result, lines


def _groups(ctx) -> dict:
    return {k: {f: g[f] for f in ("iterations", "steps", "admitted")}
            for k, g in ctx.svc.metrics()["groups"].items()}

