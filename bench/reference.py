"""The plain reference the benchmark holds the service to.

Written from the Ruya paper (arXiv:2211.04240 §III-D) and CherryPick
(NSDI'17 §4) with the surrogate the deployment's configuration states,
and sharing no code with the program:

  * `split` — the §III-D priority group of one job: LINEAR jobs need total
    cluster memory ≥ the extrapolated requirement (× (1 + leeway), plus a
    per-node overhead), or, where no configuration qualifies, the lowest
    and highest ``extreme_fraction`` by memory; FLAT jobs get the
    ``flat_fraction`` with the least memory; UNCLEAR jobs get everything.
  * `grid_fits` / `expected_improvement` — the GP surrogate over the
    trials seen so far: Matérn-5/2 kernel of unit amplitude on the
    standardized encoding, standardized targets, each (lengthscale, noise)
    of the stated grid scored by its log marginal likelihood, and Expected
    Improvement (for minimization) over the candidate pool under the
    best-scoring fit.

The GP functions take the array module as ``xp`` (NumPy in float64 for the
check; `jax.numpy` in float32 on the chip for the lower-precision control)
and ``erf`` to match it.
"""

from __future__ import annotations

import math

import numpy as np

# Grid points whose float64 log marginal likelihoods lie within this many
# nats of the best are a tie that float32 rounding may break either way
# (seen on v5e: 1.46e-5 nats between lengthscales 0.1 and 0.25 at three
# observations); the service may follow any of them.  Copied from
# `chip_smoke.py`'s LML_TIE.
LML_TIE = 1e-4


def standardize(features) -> np.ndarray:
    """Per-feature zero mean and unit variance (constant features kept at
    zero), float64."""
    f = np.asarray(features, np.float64)
    std = f.std(axis=0)
    return (f - f.mean(axis=0)) / np.where(std > 1e-12, std, 1.0)


def split(job: dict, total_memory, num_nodes, extreme_fraction=0.15):
    """Sorted priority indices of one Ruya-mode job (see module doc)."""
    mem = np.asarray(total_memory, np.float64)
    n = len(mem)
    model = job["memory_model"]
    by_memory = sorted(range(n), key=lambda i: (mem[i], i))
    if model["category"] == "unclear":
        return list(range(n))
    if model["category"] == "flat":
        k = max(1, int(round(job["flat_fraction"] * n)))
        return sorted(by_memory[:k])
    need = model["slope"] * job["full_input_size"] + model["intercept"]
    prio = [i for i in range(n)
            if mem[i] >= need * (1.0 + job["leeway"])
            + job["per_node_overhead"] * num_nodes[i]]
    if prio:
        return prio
    k = max(1, int(round(extreme_fraction * n)))
    return sorted(set(by_memory[:k]) | set(by_memory[-k:]))


def _sqdist(xp, a, b):
    """Squared distances by the usual expansion, with the cross term as
    one matrix product."""
    d2 = (xp.sum(a * a, -1)[:, None] + xp.sum(b * b, -1)[None, :]
          - 2.0 * (a @ b.T))
    return xp.maximum(d2, 0.0)


def _matern52(xp, d2, ls):
    s = xp.sqrt(d2) * (math.sqrt(5.0) / ls)
    return (1.0 + s + s * s / 3.0) * xp.exp(-s)


def grid_fits(xp, enc, trials, costs, surrogate: dict):
    """Fits of every grid point to the observed ``trials`` (indices into
    ``enc``, in trial order) and their ``costs``.

    Returns (lml (G,), grid [(lengthscale, noise)], factors): factors
    holds the standardization and, per grid point, the Cholesky factor and
    the weights the posterior needs."""
    x = enc[xp.asarray(trials)]
    y = xp.asarray(costs, dtype=enc.dtype)
    t = len(trials)
    y_mean = xp.mean(y)
    y_std = xp.maximum(xp.std(y), 1e-8)
    yt = (y - y_mean) / y_std
    d2 = _sqdist(xp, x, x)
    grid = [(ls, nz) for ls in surrogate["lengthscales"]
            for nz in surrogate["noises"]]
    eye = xp.eye(t, dtype=enc.dtype)
    a = xp.stack([_matern52(xp, d2, ls) + (nz + surrogate["jitter"]) * eye
                  for ls, nz in grid])
    chol = xp.linalg.cholesky(a)
    z = xp.linalg.solve(chol, xp.broadcast_to(yt[:, None], (len(grid), t, 1)))
    w = xp.linalg.solve(xp.swapaxes(chol, 1, 2), z)[..., 0]
    lml = (-0.5 * xp.sum(z[..., 0] ** 2, -1)
           - xp.sum(xp.log(xp.diagonal(chol, axis1=1, axis2=2)), -1)
           - 0.5 * t * math.log(2.0 * math.pi))
    return lml, grid, (x, y_mean, y_std, chol, w)


def tied(lml) -> list:
    """Grid points within LML_TIE nats of the best (float64 scores)."""
    lml = np.asarray(lml, np.float64)
    top = np.max(lml)
    return [int(h) for h in np.flatnonzero(lml >= top - LML_TIE)]


def expected_improvement(xp, erf, enc, costs, fits, h, cand, surrogate):
    """EI over every configuration under grid point ``h`` of ``fits`` (an
    index, or a traced one under `jax.jit`); configurations outside the
    boolean ``cand`` get -inf."""
    lml, grid, (x, y_mean, y_std, chol, w) = fits
    ls = xp.asarray([g[0] for g in grid], dtype=enc.dtype)[h]
    k = _matern52(xp, _sqdist(xp, x, enc), ls)  # (t, n)
    mean = (w[h] @ k) * y_std + y_mean
    v = xp.linalg.solve(chol[h], k)
    var = xp.maximum(1.0 - xp.sum(v * v, 0), 1e-12)
    std = xp.sqrt(var) * y_std
    best = xp.min(xp.asarray(costs, dtype=enc.dtype))
    imp = best - mean - surrogate["xi"]
    z = imp / xp.maximum(std, 1e-12)
    cdf = 0.5 * (1.0 + erf(z / math.sqrt(2.0)))
    pdf = xp.exp(-0.5 * z * z) / math.sqrt(2.0 * math.pi)
    ei = xp.maximum(imp * cdf + std * pdf, 0.0)
    return xp.where(xp.asarray(cand), ei, -xp.inf)
