"""Span arguments read back from a recorded trace, and the EI tail's work
count.  ``paper69-waves-spans.xplane.pb.gz`` (one TPU v5e chip) holds a
short window of `paper69-waves` whose ``tuning.admit`` spans carry
``rows`` and ``chunks`` and whose ``tuning.retire`` spans carry ``rows``.
Run by hand:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import importlib.util
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import span_args  # noqa: E402

WITH_SPANS = os.path.join(BENCH, "testdata",
                          "paper69-waves-spans.xplane.pb.gz")


def _work():
    path = os.path.join(BENCH, "work", "ei_tail.py")
    spec = importlib.util.spec_from_file_location("ei_tail_work", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_admit_and_retire_args_read_back():
    spans = span_args.load_args(WITH_SPANS)
    admits = [a for _, name, a in spans if name == "tuning.admit"]
    retires = [a for _, name, a in spans if name == "tuning.retire"]
    assert admits and all(a == {"rows": 16, "chunks": 2} for a in admits)
    assert retires and all(a == {"rows": 8} for a in retires)
    assert all(a == {} for _, name, a in spans if name == "tuning.dispatch")
    assert [name for _, name, _ in spans].count("bench.window") == 1


def test_work_is_a_lower_bound_in_the_slots():
    work = _work()
    n, d = 1000, 6
    # Two rows at t = 3 and t = 5: Σ t² = 34 ≥ S² / R = 32.
    exact = n * (34 + (3 * d + 12) * 8 + 16 * 2)
    assert work.flops(2, 8, n, d) <= exact
    assert work.flops(2, 8, n, d) == n * (32 + (3 * d + 12) * 8 + 32)
    assert work.flops(0, 0, n, d) == 0.0
    assert work.bytes_read(2, n, d) == 2 * n * (4 * d + 5)
