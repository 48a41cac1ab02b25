"""The reduction of the program's own spans and named scopes.

Checked on hand-made intervals, and on two traces recorded on one TPU
v5e chip (``bench/testdata``): ``paper69-waves.xplane.pb.gz``, from
before the program carried spans, and ``paper69-waves-spans.xplane.pb.gz``,
a short window of the `paper69-waves` cell with the ``tuning.*`` spans
and the ``gp_head`` name scope.  Run by hand:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

from __future__ import annotations

import gzip
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import program_trace  # noqa: E402
import trace_reduce  # noqa: E402

DEV = "/device:TPU:0"
DATA = os.path.join(BENCH, "testdata")
BEFORE = os.path.join(DATA, "paper69-waves.xplane.pb.gz")
WITH_SPANS = os.path.join(DATA, "paper69-waves-spans.xplane.pb.gz")
HEAD = "jit(f)/vmap(gp_head)/vmap(vmap(jit(cholesky)))/cholesky:"
TAIL = "jit(f)/vmap(jit(_solve_triangular))/triangular_solve:"


def _hand_made():
    a, b = ("/host:CPU", 1), ("/host:CPU", 2)
    return {
        "devices": {DEV: [(10, 20, HEAD, "%c"), (30, 40, TAIL, "%t"),
                          (50, 60, None, "%copy")]},
        "spans": sorted([
            (-5, 3, "tuning.submit", a),  # starts before the window
            (0, 30, "tuning.admit", a),
            (2, 8, "tuning.chunk_arrays", a),
            (8, 25, "tuning.device_put", a),
            (40, 45, "tuning.dispatch", a),
            (5, 50, "tuning.lock_wait", b),
            (60, 90, "tuning.poll", b),
        ]),
        "window": (0, 100),
    }


def test_self_time_and_idle_under_spans_on_hand_made_intervals():
    out = program_trace.reduce(_hand_made())
    assert out["window_s"] == pytest.approx(100e-9)
    spans = out["spans"]
    assert "tuning.submit" not in spans
    admit = spans["tuning.admit"]
    assert admit["count"] == 1
    assert admit["total_s"] == pytest.approx(30e-9)
    # 30 minus its children on its own thread (6 + 17); the lock wait on
    # another thread overlaps it and is no child.
    assert admit["self_s"] == pytest.approx(7e-9)
    assert spans["tuning.lock_wait"]["self_s"] == pytest.approx(45e-9)
    d = out["devices"][DEV]
    assert d["busy_s"] == pytest.approx(30e-9)
    # Idle: [0,10) [20,30) [40,50) [60,100).
    assert d["idle_under"]["tuning.admit"] == pytest.approx(20e-9)
    assert d["idle_under"]["tuning.lock_wait"] == pytest.approx(25e-9)
    assert d["idle_under"]["tuning.poll"] == pytest.approx(30e-9)
    assert d["idle_under"]["tuning.dispatch"] == pytest.approx(5e-9)
    assert d["scopes"]["gp_head"] == pytest.approx(10e-9)
    assert d["scopes"]["triangular_solve"] == pytest.approx(10e-9)
    assert d["scopes"]["jit(f)"] == pytest.approx(20e-9)


def test_scopes_of():
    assert program_trace.scopes_of(HEAD) == {
        "jit(f)", "gp_head", "jit(cholesky)", "cholesky"}
    assert "gp_head" not in program_trace.scopes_of(TAIL)
    assert program_trace.scopes_of("jit(f)/vmap()/gather:") == {
        "jit(f)", "gather"}
    assert program_trace.scopes_of(None) == set()


def test_no_window_reduces_to_none():
    assert program_trace.reduce(
        {"devices": {}, "spans": [], "window": None}) is None


def test_tf_op_of_the_recorded_trace_before_spans():
    trace = program_trace.load(BEFORE)
    ops = trace["devices"][DEV]
    chol = [op for op in ops if 'custom_call_target="Cholesky"' in op[3]]
    assert chol
    assert all(op[2] and "cholesky" in op[2] for op in chol)
    # The program had no spans and no gp_head scope yet.
    assert trace["spans"] == []
    assert all("gp_head" not in program_trace.scopes_of(op[2])
               for op in ops)


def test_trace_reduce_of_the_recorded_trace_is_unchanged():
    """`trace_reduce`'s own numbers on the first recorded trace, as the
    benchmark first computed them: the program's spans and this module
    leave the ledger's `breakdown` as it was."""
    out = trace_reduce.reduce(trace_reduce.load(BEFORE))
    d = out["devices"][DEV]
    assert out["window_s"] == pytest.approx(0.576566486, rel=1e-12)
    assert d["busy_s"] == pytest.approx(0.369230308, rel=1e-12)
    assert out["host_spans"] == {
        "bench.window": [1, pytest.approx(0.576566486)],
        "bench.submit": [96, pytest.approx(0.023784227)],
        "bench.admit": [110, pytest.approx(0.097041237)],
        "bench.chunk_step": [184, pytest.approx(0.432173486)],
        "bench.retire": [12, pytest.approx(0.03282492)],
    }
    assert d["top_ops"][0] == ["custom-call.8 Cholesky",
                               pytest.approx(0.198197397)]


def test_gp_head_scope_holds_the_factorizations():
    trace = program_trace.load(WITH_SPANS)
    ops = trace["devices"][DEV]
    chol = [op for op in ops if 'custom_call_target="Cholesky"' in op[3]]
    assert chol
    assert all("gp_head" in program_trace.scopes_of(op[2]) for op in chol)
    solves = [op for op in ops
              if op[2] and "triangular_solve" in op[2]]
    head_solves = [op for op in solves
                   if "gp_head" in program_trace.scopes_of(op[2])]
    assert head_solves
    # The EI tail's solve stays outside the head.
    assert len(head_solves) < len(solves)
    out = program_trace.reduce(trace)
    d = out["devices"][DEV]
    assert 0.0 < d["scopes"]["gp_head"] < d["busy_s"]


def test_program_spans_of_the_recorded_trace():
    trace = program_trace.load(WITH_SPANS)
    out = program_trace.reduce(trace)
    spans = out["spans"]
    assert set(spans) >= {"tuning.submit", "tuning.admit",
                          "tuning.chunk_arrays", "tuning.device_put",
                          "tuning.dispatch", "tuning.retire"}
    # One dispatch span per `_step_chunk` the harness annotated.
    steps = trace_reduce.reduce(trace_reduce.load(WITH_SPANS))
    assert spans["tuning.dispatch"]["count"] == steps["host_spans"][
        "bench.chunk_step"][0]
    d = out["devices"][DEV]
    assert 0.0 < d["idle_under"]["tuning.admit"] <= (
        out["window_s"] - d["busy_s"])
    # The ledger's breakdown still names only the harness's spans.
    gaps = steps["devices"][DEV]["idle_gaps"]
    assert all(n.startswith("bench.") or n == "host" for n, _ in gaps)
    assert all(n.startswith("bench.") for n in steps["host_spans"])


def test_for_run_finds_the_run_trace(tmp_path, monkeypatch, capsys):
    run_dir = tmp_path / "paper69-waves-1" / "plugins" / "profile" / "t"
    run_dir.mkdir(parents=True)
    with gzip.open(WITH_SPANS, "rb") as f, open(
            run_dir / "host.xplane.pb", "wb") as g:
        shutil.copyfileobj(f, g)
    monkeypatch.setattr(program_trace, "TRACE_ROOT", str(tmp_path))
    monkeypatch.setattr(program_trace, "_reduced", {})
    window = trace_reduce.reduce(trace_reduce.load(WITH_SPANS))
    dispatches = window["host_spans"]["bench.chunk_step"][0]
    ctx = {"trace": window, "counters": {"dispatches": dispatches,
                                         "searches": 64}}
    red = program_trace.for_run(ctx)
    assert red is not None and "tuning.dispatch" in red["spans"]
    assert f"adapter {dispatches}: equal" in capsys.readouterr().err
    assert program_trace.for_run(dict(ctx, trace={"window_s": 1.0})) is None
