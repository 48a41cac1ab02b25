"""The control comes out not correct: the reference itself, in float32 on
the chip with matrix products one step below what the deployments state
(``high``: three bfloat16 passes for float32 at ``highest``), put in the
service's place at the states a short window of the cell stepped through.

Needs a TPU (the precision setting changes nothing on the CPU).  Run by
hand on one chip:

    python -m pytest bench/tests/test_control.py -q
"""

from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import calibrate  # noqa: E402
import check  # noqa: E402
import harness  # noqa: E402


@pytest.fixture(scope="module")
def tpu():
    import jax

    if jax.devices()[0].platform != "tpu":
        pytest.skip("the control's precision exists only on a TPU")


@pytest.mark.parametrize("name", ["paper69-waves", "paper69-poisson"])
def test_control_fails_a_limit(tpu, name):
    cell = harness.load_cell(name)
    out = calibrate.one(cell, 4242, 1.0, [calibrate.CONTROL], None,
                        harness._CompileCounter())
    service, control = out["service"], out["control_" + calibrate.CONTROL]
    limits = dict(check.EXACT,
                  pick_gap=cell["cfg"]["check"]["pick_gap_limit"])
    assert check.verdict(service, limits), service
    assert not check.verdict(control, limits), control
