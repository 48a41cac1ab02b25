"""The arguments of the program's ``tuning.*`` spans in a traced run.

`program_trace` reads the spans' names and times; this module reads, from
the same ``.xplane.pb``, the keyword arguments a span was opened with
(`repro.fleet.telemetry.span`).  The profiler stores each as a stat of the
span's event, under a stat metadata entry named after the keyword; integer
and string values are kept, others read as None.

`for_run` finds the trace of the run whose metrics are being read, as
`program_trace.for_run` does, and gives, over the ``bench.window`` span:
``args`` {span name: [{keyword: value}] of the spans that start in the
window}, and ``ops`` {device: [(start ns, end ns, tf_op path, HLO name)]}
of the operations that overlap it, clipped to it.
"""

from __future__ import annotations

import glob
import gzip
import os

import program_trace
from program_trace import PREFIX, TRACE_ROOT, _fields, _map, _str
from trace_reduce import WINDOW_SPAN


def _names(b: bytes, plane, number: int) -> dict:
    """{metadata id: name} of a plane's event (4) or stat (5) metadata."""
    out = {}
    for key, val in _map(b, plane, number).items():
        for g, w in _fields(b, *val):
            if g == 2:
                out[key] = _str(b, w)
    return out


def load_args(path: str) -> list:
    """[(start ns, name, {keyword: value})] of the host's ``tuning.*``
    and ``bench.window`` spans in the trace at ``path``."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        b = f.read()
    out = []
    for f, v in _fields(b):
        if f != 1:
            continue
        plane = list(_fields(b, *v))
        if not next((_str(b, w) for g, w in plane if g == 2),
                    "").startswith("/host:"):
            continue
        events = {k: n for k, n in _names(b, plane, 4).items()
                  if n.startswith(PREFIX) or n == WINDOW_SPAN}
        stats = _names(b, plane, 5)
        for g, line in plane:
            if g != 3:
                continue
            ts = 0
            for h, w in _fields(b, *line):
                if h == 3:
                    ts = w
                elif h == 4:
                    name, off, args = None, 0, {}
                    for k, x in _fields(b, *w):
                        if k == 1:
                            name = events.get(x)
                        elif k == 2:
                            off = x
                        elif k == 4:
                            args.update(_stat(b, x, stats))
                    if name is not None:
                        out.append((ts + off / 1e3, name, args))
    return out


def _stat(b: bytes, span, stats: dict) -> dict:
    key = value = None
    for k, x in _fields(b, *span):
        if k == 1:
            key = stats.get(x)
        elif k in (3, 4):
            value = x - (1 << 64) if k == 4 and x >= 1 << 63 else x
        elif k == 5:
            value = _str(b, x)
    return {} if key is None else {key: value}


_loaded: dict = {}


def for_run(ctx):
    """Span arguments and device operations over the window of the trace
    behind the metric context ``ctx`` (see the module docstring), or
    None."""
    if ctx.get("trace") is None:
        return None
    want = ctx["trace"]["window_s"]
    paths = sorted(glob.glob(os.path.join(TRACE_ROOT, "*", "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime, reverse=True)
    for path in paths:
        if path not in _loaded:
            _loaded[path] = _window(path)
        run = _loaded[path]
        if run is not None and abs(run["window_s"] - want) < 1e-6:
            return run
    return None


def _window(path: str):
    trace = program_trace.load(path)
    if trace["window"] is None:
        return None
    lo, hi = trace["window"]
    args: dict = {}
    for start, name, a in load_args(path):
        if name != WINDOW_SPAN and lo <= start < hi:
            args.setdefault(name, []).append(a)
    ops = {dev: [(max(s, lo), min(e, hi), p, n) for s, e, p, n in evs
                 if min(e, hi) > max(s, lo)]
           for dev, evs in trace["devices"].items()}
    return {"window_s": (hi - lo) * 1e-9, "args": args, "ops": ops}
