"""The reduction from a profiler trace to busy time, idle share and gaps.

Checked on hand-made intervals, and on a short trace of the
`paper69-waves` cell recorded on one TPU v5e chip (``bench/testdata``).
Run by hand:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import glob
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import trace_reduce  # noqa: E402

DEV = "/device:TPU:0"


def test_union_and_gaps_on_hand_made_intervals():
    trace = {
        "devices": {DEV: [(10, 20, "a"), (15, 30, "b"), (50, 60, "a"),
                          (95, 120, "c")]},
        "modules": {},
        "host": [(0, 100, "bench.window"), (28, 52, "bench.chunk_step"),
                 (60, 100, "bench.submit"), (62, 70, "bench.admit")],
    }
    out = trace_reduce.reduce(trace)
    d = out["devices"][DEV]
    assert out["window_s"] == pytest.approx(100e-9)
    assert d["busy_s"] == pytest.approx(35e-9)  # [10,30) + [50,60) + [95,100)
    assert d["idle_share"] == pytest.approx(0.65)
    assert [n for n, _ in d["top_ops"]] == ["a", "b", "c"]
    assert d["top_ops"][0][1] == pytest.approx(20e-9)
    assert d["idle_gaps"] == [["bench.submit", pytest.approx(35e-9)],
                              ["bench.chunk_step", pytest.approx(20e-9)],
                              ["host", pytest.approx(10e-9)]]


def test_short_name():
    long = ('%custom-call.8 = f32[8,18,69,69]{3,2,1,0} custom-call(f32[8,18,'
            '69,69]{3,2,1,0} %fusion.17), custom_call_target="Cholesky", '
            'operand_layout_constraints={f32[8,18,69,69]{3,2,1,0}}')
    assert trace_reduce.short_name(long) == "custom-call.8 Cholesky"
    assert trace_reduce.short_name("%copy.86 = f32[8] copy(f32[8] %g)") == \
        "copy.86"


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"devices": {}, "modules": {}, "host": []})


def _recorded():
    paths = glob.glob(os.path.join(BENCH, "testdata", "*.xplane.pb.gz"))
    if not paths:
        pytest.fail("bench/testdata holds no recorded trace")
    return paths[0]


def test_recorded_chip_trace():
    trace = trace_reduce.load(_recorded())
    assert list(trace["devices"]) == [DEV]
    out = trace_reduce.reduce(trace)
    d = out["devices"][DEV]
    assert 0.0 < d["busy_s"] < out["window_s"]
    assert 0.0 < d["idle_share"] < 1.0
    assert d["busy_s"] == pytest.approx(
        out["window_s"] * (1.0 - d["idle_share"]))
    # Every dispatch the harness annotated ran a program on the device.
    steps = out["host_spans"]["bench.chunk_step"][0]
    assert steps > 0 and trace["modules"][DEV] >= steps
    assert sum(s for _, s in d["idle_gaps"]) <= out["window_s"]
    assert all(name.startswith("bench.") or name == "host"
               for name, _ in d["idle_gaps"])
