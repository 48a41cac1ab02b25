"""The EC2 gen-6/7 c/m/r catalog (`repro.cluster.catalog`) as a deployment
of the tuning service.

  * The catalog follows the documented rules: 126 instance types, vCPUs by
    size, GiB per vCPU by class, prices linear in size, distinct encoded
    rows, the documented order.
  * On a slice (all 126 types × 1–8 nodes, n = 1008) the vectorized
    §III-D split equals the host rule, and the service in Ruya mode at
    B = 24 picks, in both the `fused` and `feature` layouts, what the
    float64 reference (`core/gp.py`'s fit and prediction, `core/acquisition`'s
    EI) ranks best, within `PICK_GAP`.  A run whose float32 matrix
    products are one bfloat16 pass fails that tolerance.
  * The spans and counters that read the EI tail: the ``ei_tail`` scope in
    the lowered chunk update, ``tuning.split`` per submit, and the
    ``rows``/``slots`` of each ``tuning.dispatch`` summing to the
    ``ei_rows``/``ei_slots`` counters.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.core.fast_bo as fast_bo
from repro.cluster import JOBS, catalog, job_cost_table
from repro.cluster.simulator import ClusterSimulator
from repro.core import gp
from repro.core.acquisition import expected_improvement
from repro.core.bayesopt import BOSettings
from repro.core.memory_model import MemoryCategory, MemoryModel
from repro.core.search_space import split_priority_mask, split_search_space
from repro.fleet import FleetJob, TuningService, TuningSession
from repro.fleet.batched_engine import _fleet_update

from test_telemetry import _named, _spans

GiB = 1024.0**3
SLICE_NODES = 8  # all 126 types × 1..8 nodes: n = 1008
SETTINGS = BOSettings(n_init=3, ei_stop_rel=0.1, min_observations=6,
                      max_iters=24)
# Widest shortfall of a pick's float64 EI below the best, as a share of
# the best (`bench/check.py`'s pick_gap).  Read on this slice with all 16
# jobs: float32 picks ≤ 1.3e-8; float32 with bfloat16_3x products (a
# TPU's "high") 1.9e-10, so no tolerance here can fail those; with one
# bfloat16 pass 0.78.
PICK_GAP = 1e-5
LML_TIE = 1e-4  # float64 grid scores this close are a tie (bench/reference)


@pytest.fixture(scope="module")
def full():
    return catalog.enumerate_catalog()


@pytest.fixture(scope="module")
def piece():
    configs = catalog.enumerate_catalog(SLICE_NODES)
    return configs, catalog.make_catalog_space(configs)


def _rule(name, configs):
    types = catalog.catalog_node_types()
    if name == "types":
        assert len(types) == 126 == len({t.name for t in types})
        assert len(configs) == 126 * catalog.MAX_NODES == 129024
    elif name == "vcpus":
        for t in types:
            assert t.cores == catalog.SIZES[t.size]
        assert sorted(set(catalog.SIZES.values())) == [2, 4, 8, 16, 32, 48,
                                                       64]
    elif name == "memory":
        for t in types:
            assert t.memory_gb == {"c": 2, "m": 4, "r": 8}[t.family] * t.cores
    elif name == "prices":
        for t in types:
            large = catalog.FAMILIES[t.name.split(".")[0]][3]
            assert t.price_per_hour == pytest.approx(large * t.cores / 2,
                                                     rel=1e-12)
    elif name == "order":
        for k in (0, 1, 777, len(configs) - 1):
            c = configs[k]
            assert c.node == types[k // catalog.MAX_NODES]
            assert c.scale_out == k % catalog.MAX_NODES + 1
    elif name == "distinct":
        enc = catalog.make_catalog_space(configs).encoded()
        assert enc.shape == (129024, 6)
        assert len(np.unique(enc, axis=0)) == len(enc)


@pytest.mark.parametrize("name", ["types", "vcpus", "memory", "prices",
                                  "order", "distinct"])
def test_catalog_follows_its_rules(full, name):
    _rule(name, full)


def _model(category, slope, intercept, r2):
    return MemoryModel(category, slope=slope, intercept=intercept, r2=r2,
                       sizes=(), readings=())


_MODELS = {
    "linear": _model(MemoryCategory.LINEAR, 3.0, 0.0, 1.0),
    "linear-none-fit": _model(MemoryCategory.LINEAR, 1e6, 0.0, 1.0),
    "flat": _model(MemoryCategory.FLAT, 0.0, 4 * GiB, 0.0),
    "unclear": _model(MemoryCategory.UNCLEAR, 0.0, 0.0, 0.2),
}


@pytest.mark.parametrize("model", sorted(_MODELS))
def test_split_mask_equals_host_split(piece, model):
    _, space = piece
    kw = dict(per_node_overhead=0.5 * GiB, leeway=0.1)
    size = 100 * GiB
    mask = split_priority_mask(space, _MODELS[model], size, **kw)
    prio, rest = split_search_space(space, _MODELS[model], size, **kw)
    assert list(np.flatnonzero(mask)) == prio
    assert list(np.flatnonzero(~mask)) == rest


def _jobs(piece):
    configs, space = piece
    out = []
    for key in JOBS:
        sim = ClusterSimulator.for_job(key)
        table = job_cost_table(JOBS[key], configs=configs)
        out.append(FleetJob(
            name=key, space=space, cost_table=table / table.min(),
            full_input_size=sim.job.input_gb * GiB,
            profile_run=sim.profile_run_fn(), per_node_overhead=0.5 * GiB,
        ))
    return out


def _run(jobs, layout, seed=11):
    svc = TuningService(TuningSession(settings=SETTINGS, mode="ruya",
                                      layout=layout))
    handles = [svc.submit(job, seed=seed) for job in jobs]
    svc.drain()
    svc.shutdown()
    return [h.outcome() for h in handles], svc.metrics()


_LS = (0.1, 0.25, 0.5, 1.0, 2.0, 4.0)
_NZ = (1e-4, 1e-2, 1e-1)


@jax.jit
def _reference(x, y, enc, cand):
    """Float64 (under `jax.enable_x64`) log marginal likelihood and EI over
    ``enc`` at every grid point, from `gp`'s fit and prediction."""
    y_mean = jnp.mean(y)
    y_std = jnp.maximum(jnp.std(y), 1e-8)
    y_n = (y - y_mean) / y_std
    d2 = gp.pairwise_sqdist(x)
    eye = jnp.eye(x.shape[0], dtype=x.dtype)

    def one(ls, nz):
        chol = jnp.linalg.cholesky(gp.matern52_from_sqdist(d2, ls)
                                   + (nz + 1e-8) * eye)
        alpha = jax.scipy.linalg.cho_solve((chol, True), y_n)
        lml = (-0.5 * y_n @ alpha - jnp.sum(jnp.log(jnp.diag(chol)))
               - 0.5 * x.shape[0] * jnp.log(2.0 * jnp.pi))
        post = gp.GPPosterior(gp.GPParams(ls, jnp.ones((), x.dtype), nz), x,
                              chol, alpha, y_mean, y_std)
        mean, std = gp.gp_predict(post, enc)
        ei = expected_improvement(mean, std, jnp.min(y))
        return lml, jnp.where(cand, ei, -jnp.inf)

    ls = jnp.repeat(jnp.asarray(_LS, x.dtype), len(_NZ))
    nz = jnp.tile(jnp.asarray(_NZ, x.dtype), len(_LS))
    return jax.vmap(one)(ls, nz)


def _pick_gap(outcomes, enc) -> float:
    """`bench/check.py`'s pick_gap over every BO pick of ``outcomes``."""
    worst = 0.0
    n = len(enc)
    with jax.enable_x64(True):
        enc64 = jnp.asarray(enc, jnp.float64)
        for o in outcomes:
            trials = [r.index for r in o.observations]
            costs = np.asarray([r.cost for r in o.observations])
            prio = np.zeros(n, bool)
            prio[list(o.priority)] = True
            n_init = sum(r.source == "init" for r in o.records)
            for k in range(n_init, len(trials)):
                seen = np.zeros(n, bool)
                seen[trials[:k]] = True
                cand = prio & ~seen
                if not cand.any():
                    cand = ~prio & ~seen
                lml, ei = (np.asarray(a) for a in _reference(
                    enc64[np.asarray(trials[:k])], jnp.asarray(costs[:k]),
                    enc64, jnp.asarray(cand)))
                floor = 1e-5 * costs[:k].min()
                worst = max(worst, min(
                    (ei[h].max() - ei[h][trials[k]]) / max(ei[h].max(), floor)
                    for h in np.flatnonzero(lml >= lml.max() - LML_TIE)))
    return worst


@pytest.fixture(scope="module")
def jobs(piece):
    """Every other Table I job: linear, flat and unclear ones, one chunk."""
    return _jobs(piece)[::2]


@pytest.mark.parametrize("layout", ["fused", "feature"])
def test_service_picks_reach_the_float64_best(piece, jobs, layout):
    outcomes, _ = _run(jobs, layout)
    assert all(o.status == "converged" for o in outcomes)
    assert all(len(o.observations) <= SETTINGS.max_iters for o in outcomes)
    assert _pick_gap(outcomes, piece[1].encoded()) <= PICK_GAP


def test_split_and_dispatch_spans_match_the_counters(jobs, tmp_path):
    with jax.profiler.trace(str(tmp_path)):
        outcomes, metrics = _run(jobs, "fused")
    spans = _spans(str(tmp_path))
    assert len(_named(spans, "tuning.split")) == len(outcomes)
    for s in _named(spans, "tuning.split"):
        assert s["parents"][0] == "tuning.submit"
    dispatch = _named(spans, "tuning.dispatch")
    (g,) = metrics["groups"].values()
    assert len(dispatch) == g["dispatches"]
    assert g["ei_rows"] == sum(s["args"]["rows"] for s in dispatch) > 0
    assert g["ei_slots"] == sum(s["args"]["slots"] for s in dispatch) > 0
    assert g["ei_rows"] < g["dispatches"] * 8


@pytest.mark.parametrize("layout", ["fused", "feature"])
def test_ei_tail_scope_in_the_lowered_update(piece, layout):
    configs, space = piece
    n, d = space.encoded().shape
    rows, b = 2, SETTINGS.max_iters
    z = lambda *shape, dt=jnp.float32: jnp.zeros((rows,) + shape, dt)
    state = fast_bo.FleetState(
        obs=z(n, dt=bool), tried=z(b, dt=jnp.int32), py=z(b),
        feats=z(b, d), t=z(dt=jnp.int32), stop=z(dt=jnp.int32),
        pb=z(dt=jnp.int32), done=z(dt=bool), last_ei=z(), last_best=z())
    text = _fleet_update.lower(
        state, z(n, d), z(n), z(n, dt=bool), z(n, dt=bool),
        z(3, dt=jnp.int32), z(dt=jnp.int32), z(dt=jnp.int32),
        jnp.int32(6), jnp.float32(0.1), jnp.asarray(False),
        xi=0.0, layout=layout,
    ).as_text(debug_info=True)
    assert "ei_tail" in text


def _one_bf16_pass(a, b):
    bf = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    return jnp.matmul(bf(a), bf(b), precision=jax.lax.Precision.HIGHEST)


def test_a_one_pass_bfloat16_run_fails_the_tolerance(piece, jobs,
                                                     monkeypatch):
    """The tolerance is not vacuous: the same service with the cross
    distance block's float32 products at one bfloat16 pass (a TPU's
    default precision) fails it."""

    def sqdist(x1, x2=None):
        x2 = x1 if x2 is None else x2
        d2 = (jnp.sum(x1**2, -1)[:, None] + jnp.sum(x2**2, -1)[None, :]
              - _one_bf16_pass(2.0 * x1, x2.T))
        return jnp.maximum(d2, 0.0)

    monkeypatch.setattr(fast_bo, "pairwise_sqdist", sqdist)
    jax.clear_caches()
    try:
        outcomes, _ = _run(jobs, "feature")
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert _pick_gap(outcomes, piece[1].encoded()) > PICK_GAP
