"""From a JAX profiler trace to the numbers the per-layer metrics read.

`load` reads the ``.xplane.pb`` that `jax.profiler` writes with nothing
but `jax.profiler.ProfileData`, keeping:

  * per device plane (``/device:TPU:<i>``), the intervals of its "XLA Ops"
    line — one event per operation that ran on the device, named by
    `short_name` — and the count of its "XLA Modules" line (one event per
    program execution).  The "Async XLA Ops" line is left out: its copies
    run beside the operations and would count waiting as work;
  * the host's ``bench.*`` spans (`jax.profiler.TraceAnnotation`s of the
    harness and the adapter), on the same clock.

`reduce` then clips everything to the ``bench.window`` span and gives,
per device: busy seconds (the union of its operation intervals), idle
share (1 − busy / window), the operations that took most time, and the
idle gaps, each named by the host span it fell in (the innermost
``bench.*`` span covering most of the gap; ``host`` where none did).
"""

from __future__ import annotations

import glob
import gzip
import os

WINDOW_SPAN = "bench.window"


def find(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def short_name(event_name: str) -> str:
    """An operation's name without its HLO text: ``custom-call.8
    Cholesky`` for ``%custom-call.8 = f32[...] custom-call(...),
    custom_call_target="Cholesky", ...``."""
    name = event_name.split(" = ", 1)[0].lstrip("%")
    if 'custom_call_target="' in event_name:
        name += " " + event_name.split('custom_call_target="', 1)[1].split(
            '"', 1)[0]
    return name


def load(path: str) -> dict:
    """The trace at ``path``: an ``.xplane.pb``, or one gzipped."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    devices, modules, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [(e.start_ns, e.start_ns + e.duration_ns,
                             short_name(e.name)) for e in line.events]
                elif line.name == "XLA Modules":
                    modules[plane.name] = modules.get(plane.name, 0) + sum(
                        1 for _ in line.events)
            devices[plane.name] = sorted(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                         for e in line.events if e.name.startswith("bench.")]
    return {"devices": devices, "modules": modules, "host": sorted(host)}


def union(intervals, lo, hi) -> list:
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _label(gap, spans) -> str:
    """The host span covering most of ``gap``; the shorter one at a tie,
    so that a span wins over the window that holds it."""
    s0, e0 = gap
    best, key = "host", (0, 0)
    for s, e, name in spans:
        if s >= e0:
            break
        cover = min(e, e0) - max(s, s0)
        if name != WINDOW_SPAN and cover > 0 and (cover, -(e - s)) > key:
            best, key = name, (cover, -(e - s))
    return best


def reduce(trace: dict, top: int = 10) -> dict:
    """Per-device numbers over the ``bench.window`` span (seconds)."""
    wins = [(s, e) for s, e, n in trace["host"] if n == WINDOW_SPAN]
    if not wins:
        raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
    lo, hi = wins[0]
    spans = [sp for sp in trace["host"] if sp[1] > lo and sp[0] < hi]
    out = {"window_s": (hi - lo) * 1e-9, "devices": {}}
    for dev, ops in trace["devices"].items():
        busy = union(ops, lo, hi)
        busy_ns = sum(e - s for s, e in busy)
        by_name: dict = {}
        for s, e, name in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                by_name[name] = by_name.get(name, 0) + d
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out["devices"][dev] = {
            "busy_s": busy_ns * 1e-9,
            "idle_share": 1.0 - busy_ns / (hi - lo),
            "ops": len([1 for s, e, _ in ops if e > lo and s < hi]),
            "top_ops": [[n, d * 1e-9] for n, d in sorted(
                by_name.items(), key=lambda kv: -kv[1])[:top]],
            "idle_gaps": [[_label(g, spans), (g[1] - g[0]) * 1e-9]
                          for g in gaps[:top]],
        }
    out["host_spans"] = {}
    for s, e, name in spans:
        c = out["host_spans"].setdefault(name, [0, 0.0])
        c[0] += 1
        c[1] += (min(e, hi) - max(s, lo)) * 1e-9
    return out
