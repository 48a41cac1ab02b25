"""What the program's own spans and named scopes say about a traced run.

`trace_reduce` reads the harness's ``bench.*`` spans and the device's
operations by HLO name.  This module reads, from the same ``.xplane.pb``:

  * the tuning service's ``tuning.*`` spans (`repro.fleet.telemetry`),
    each with the host thread (line) it ran on;
  * each device operation's ``tf_op``, the op-name path of the JAX
    program that emitted it, as in
    ``jit(_fleet_update)/vmap(gp_head)/vmap(jit(cholesky))/cholesky:``,
    which `jax.profiler.ProfileData` does not expose: it is a stat of the
    device plane's event metadata.

The file is decoded by hand, with no protobuf package: an XSpace's
planes, their lines and events, and their event and stat metadata are
all the fields read.  Where a field is missing the value is ``None``.

`reduce` gives, over the ``bench.window`` span:

  * per ``tuning.*`` span name: count, total seconds, and self seconds
    (each span minus its children on the same thread), over the spans
    that start inside the window;
  * per device: busy seconds per named scope (an operation counts toward
    a scope when the scope is a component of its ``tf_op`` path, under
    any ``vmap(...)`` wrapper), and idle seconds under each span name
    (the device's idle intervals intersected with the union of that
    name's spans).

`for_run` finds the trace of the run whose metrics are being read and
reduces it once; the per-layer readers in ``metrics/`` call it.
"""

from __future__ import annotations

import glob
import gzip
import os
import sys

from trace_reduce import WINDOW_SPAN, union

PREFIX = "tuning."
BENCH = os.path.dirname(os.path.abspath(__file__))
TRACE_ROOT = os.path.join(os.path.dirname(BENCH), "bench_out", "trace")


def _varint(b: bytes, i: int):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return x, i


def _fields(b: bytes, lo: int = 0, hi: int | None = None):
    """(field number, value) of one message in ``b[lo:hi]``: an int for a
    varint, a (start, end) slice for a length-delimited field."""
    i, hi = lo, len(b) if hi is None else hi
    while i < hi:
        key, i = _varint(b, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
            yield field, v
        elif wire == 2:
            n, i = _varint(b, i)
            yield field, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _str(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _map(b: bytes, plane_fields, number: int) -> dict:
    """A plane's map field ``number`` as {key: (start, end) of value}."""
    out = {}
    for f, v in plane_fields:
        if f != number:
            continue
        key = val = None
        for g, w in _fields(b, *v):
            if g == 1:
                key = w
            elif g == 2:
                val = w
        if key is not None and val is not None:
            out[key] = val
    return out


def _op_metadata(b: bytes, plane_fields) -> dict:
    """{event metadata id: (HLO text, tf_op path or None)} of a device
    plane."""
    stat_names = {}
    for key, val in _map(b, plane_fields, 5).items():
        for g, w in _fields(b, *val):
            if g == 2:
                stat_names[key] = _str(b, w)
    tf_op = {k for k, name in stat_names.items() if name == "tf_op"}
    out = {}
    for key, val in _map(b, plane_fields, 4).items():
        name = path = None
        for g, w in _fields(b, *val):
            if g == 2:
                name = _str(b, w)
            elif g == 5:
                stat = dict(_fields(b, *w))
                if stat.get(1) not in tf_op:
                    continue
                if 5 in stat:
                    path = _str(b, stat[5])
                elif 7 in stat:
                    path = stat_names.get(stat[7])
        out[key] = (name, path)
    return out


def _events(b: bytes, line):
    """(metadata id, start ns, end ns) of one line's events."""
    ts = 0
    evs = []
    for g, w in _fields(b, *line):
        if g == 3:
            ts = w
        elif g == 4:
            ev = dict(_fields(b, *w))
            evs.append((ev.get(1), ev.get(2, 0), ev.get(3, 0)))
    return [(m, ts + off / 1e3, ts + (off + dur) / 1e3)
            for m, off, dur in evs]


def load(path: str) -> dict:
    """The trace at ``path`` (an ``.xplane.pb``, or one gzipped):
    ``devices`` {plane: [(start ns, end ns, tf_op path or None, HLO
    text)]} from each TPU plane's "XLA Ops" line, ``spans`` [(start ns,
    end ns, name, thread)] of the host's ``tuning.*`` spans, and
    ``window`` (start, end) of the ``bench.window`` span or None."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        b = f.read()
    devices, spans, window = {}, [], None
    for f, v in _fields(b):
        if f != 1:
            continue
        plane = list(_fields(b, *v))
        name = next((_str(b, w) for g, w in plane if g == 2), "")
        lines = [w for g, w in plane if g == 3]
        if name.startswith("/device:TPU:"):
            meta = _op_metadata(b, plane)
            ops = []
            for line in lines:
                if _line_name(b, line) == "XLA Ops":
                    ops += [(s, e, *meta.get(m, (None, None))[::-1])
                            for m, s, e in _events(b, line)]
            devices[name] = sorted(ops, key=lambda op: op[:2])
        elif name.startswith("/host:"):
            names = {}
            for key, val in _map(b, plane, 4).items():
                for g, w in _fields(b, *val):
                    if g == 2:
                        n = _str(b, w)
                        if n.startswith(PREFIX) or n == WINDOW_SPAN:
                            names[key] = n
            for line in lines:
                tid = (name, next((w for g, w in _fields(b, *line)
                                   if g == 1), None))
                for m, s, e in _events(b, line):
                    n = names.get(m)
                    if n == WINDOW_SPAN:
                        window = window or (s, e)
                    elif n is not None:
                        spans.append((s, e, n, tid))
    return {"devices": devices, "spans": sorted(spans, key=lambda s: s[:2]),
            "window": window}


def _line_name(b: bytes, line) -> str:
    return next((_str(b, w) for g, w in _fields(b, *line) if g == 2), "")


def _overlap(a, b) -> float:
    """Total length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def scopes_of(tf_op) -> set:
    """The named scopes of an op-name path: its components with every
    ``vmap(...)`` wrapper taken off, and the trailing ``:<op type>``
    left out.  ``jit(f)/vmap(gp_head)/vmap(jit(cholesky))/cholesky:``
    gives {"jit(f)", "gp_head", "jit(cholesky)", "cholesky"}."""
    if not tf_op:
        return set()
    out = set()
    for part in tf_op.rsplit(":", 1)[0].split("/"):
        while part.startswith("vmap(") and part.endswith(")"):
            part = part[5:-1]
        if part:
            out.add(part)
    return out


def _self_times(spans) -> list:
    """Each span's duration minus its children's on the same thread."""
    order = sorted(range(len(spans)),
                   key=lambda k: (spans[k][3], spans[k][0], -spans[k][1]))
    self_ns = [e - s for s, e, *_ in spans]
    stack: list = []
    for k in order:
        s, e, _, tid = spans[k]
        while stack and (spans[stack[-1]][3] != tid
                         or spans[stack[-1]][1] <= s):
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= e - s
        stack.append(k)
    return self_ns


def reduce(trace: dict):
    """Numbers over the ``bench.window`` span (seconds); None where the
    trace holds no window."""
    if trace["window"] is None:
        return None
    lo, hi = trace["window"]
    spans = trace["spans"]
    self_ns = _self_times(spans)
    per_span: dict = {}
    for (s, e, name, _), own in zip(spans, self_ns):
        if not lo <= s < hi:
            continue
        c = per_span.setdefault(name, {"count": 0, "total_s": 0.0,
                                       "self_s": 0.0})
        c["count"] += 1
        c["total_s"] += (e - s) * 1e-9
        c["self_s"] += own * 1e-9
    covers = {name: union([sp[:2] for sp in spans if sp[2] == name],
                          lo, hi)
              for name in {sp[2] for sp in spans}}
    devices = {}
    for dev, ops in trace["devices"].items():
        busy = union([op[:2] for op in ops], lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        idle = [[edges[i], edges[i + 1]] for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        by_path: dict = {}
        for s, e, path, _ in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                by_path[path] = by_path.get(path, 0.0) + d
        scopes: dict = {}
        for path, d in by_path.items():
            for scope in scopes_of(path):
                scopes[scope] = scopes.get(scope, 0.0) + d * 1e-9
        devices[dev] = {
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "scopes": scopes,
            "idle_under": {name: _overlap(idle, cov) * 1e-9
                           for name, cov in covers.items()},
        }
    return {"window_s": (hi - lo) * 1e-9, "spans": per_span,
            "devices": devices}


_reduced: dict = {}


def for_run(ctx):
    """The reduction of the trace behind the metric context ``ctx`` (the
    newest trace under ``bench_out/trace`` whose window is as long as the
    one `trace_reduce` read), or None.  On its first reduction of a trace
    it prints, on standard error, the count of ``tuning.dispatch`` spans
    in the window beside the adapter's count of dispatches."""
    if ctx.get("trace") is None:
        return None
    want = ctx["trace"]["window_s"]
    paths = sorted(glob.glob(os.path.join(TRACE_ROOT, "*", "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime, reverse=True)
    for path in paths:
        if path not in _reduced:
            _reduced[path] = reduce(load(path))
            red = _reduced[path]
            if red is not None and abs(red["window_s"] - want) < 1e-6:
                _report(red, ctx)
        red = _reduced[path]
        if red is not None and abs(red["window_s"] - want) < 1e-6:
            return red
    return None


def _report(red: dict, ctx) -> None:
    counted = ctx["counters"]["dispatches"]
    if not red["spans"]:
        print(f"dispatches in the window: adapter {counted}; the program "
              "emits no tuning.* spans", file=sys.stderr)
        return
    spans = red["spans"].get("tuning.dispatch", {}).get("count", 0)
    verdict = "equal" if spans == counted else "MISMATCH"
    print(f"dispatches in the window: program {spans} (tuning.dispatch "
          f"spans), adapter {counted}: {verdict}", file=sys.stderr)
