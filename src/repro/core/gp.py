"""Gaussian-process regression in pure JAX.

This is the surrogate model used by the CherryPick-style Bayesian optimization
(Alipourfard et al., NSDI'17) that Ruya builds on.  Matérn-5/2 kernel over the
encoded configuration features, observation noise, Cholesky-based posterior.

Hyperparameters (lengthscale, amplitude, noise) are selected by maximizing the
log marginal likelihood over a small deterministic grid — robust, derivative
free, and cheap for the O(70)-point spaces this paper works with.  Everything
is jnp so the whole fit+predict path is jittable.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp

__all__ = [
    "GPParams",
    "GPPosterior",
    "matern52",
    "matern52_from_sqdist",
    "pairwise_sqdist",
    "fit_gp",
    "gp_predict",
]

_JITTER = 1e-8


@dataclasses.dataclass(frozen=True)
class GPParams:
    """Kernel hyperparameters."""

    lengthscale: jax.Array  # (n_features,) or scalar
    amplitude: jax.Array  # scalar
    noise: jax.Array  # scalar observation noise variance


@dataclasses.dataclass(frozen=True)
class GPPosterior:
    """Cached posterior factorization for prediction."""

    params: GPParams
    x_train: jax.Array  # (n, d) standardized features
    chol: jax.Array  # (n, n) lower Cholesky of K + noise*I
    alpha: jax.Array  # (n,) K^{-1} (y - mean)
    y_mean: jax.Array  # scalar — standardization mean of y
    y_std: jax.Array  # scalar — standardization scale of y


def matern52(x1: jax.Array, x2: jax.Array, params: GPParams) -> jax.Array:
    """Matérn-5/2 kernel matrix between (n,d) and (m,d).

    Handles vector (per-feature) lengthscales.  For the scalar-lengthscale
    hyperparameter grids, use `pairwise_sqdist` + `matern52_from_sqdist`
    instead: the raw distance tensor is lengthscale-independent, so the six
    grid lengthscales share one d² computation.
    """
    scaled1 = x1 / params.lengthscale
    scaled2 = x2 / params.lengthscale
    # Pairwise Euclidean distances, numerically clamped.
    d2 = (
        jnp.sum(scaled1**2, -1)[:, None]
        + jnp.sum(scaled2**2, -1)[None, :]
        - 2.0 * scaled1 @ scaled2.T
    )
    d = jnp.sqrt(jnp.maximum(d2, 1e-12))
    sqrt5_d = jnp.sqrt(5.0) * d
    return params.amplitude * (1.0 + sqrt5_d + 5.0 / 3.0 * d**2) * jnp.exp(-sqrt5_d)


def pairwise_sqdist(x1: jax.Array, x2: jax.Array = None) -> jax.Array:
    """Raw pairwise squared Euclidean distances between (n,d) and (m,d).

    Lengthscale-free: a scalar lengthscale only rescales d² (d²/ls²), so one
    tensor serves every point of a lengthscale grid.  Clamped at zero (the
    quadratic expansion can go slightly negative in float32).  The cross term runs at
    full float32 precision: a TPU's default precision would round it to
    bfloat16 (XLA:CPU ignores the setting for float32).
    """
    if x2 is None:
        x2 = x1
    d2 = (
        jnp.sum(x1**2, -1)[:, None]
        + jnp.sum(x2**2, -1)[None, :]
        - jnp.matmul(2.0 * x1, x2.T, precision=jax.lax.Precision.HIGHEST)
    )
    return jnp.maximum(d2, 0.0)


def matern52_from_sqdist(
    d2: jax.Array, lengthscale: jax.Array, amplitude: jax.Array = 1.0
) -> jax.Array:
    """Matérn-5/2 from precomputed raw squared distances, scalar lengthscale."""
    s2 = jnp.maximum(d2 / (lengthscale * lengthscale), 1e-12)
    d = jnp.sqrt(s2)
    sqrt5_d = jnp.sqrt(5.0) * d
    return amplitude * (1.0 + sqrt5_d + 5.0 / 3.0 * s2) * jnp.exp(-sqrt5_d)


def _candidate_grid(n_features: int) -> Tuple[jax.Array, jax.Array]:
    """Deterministic (lengthscale, noise) grid for hyperparameter selection."""
    lengthscales = jnp.array([0.1, 0.25, 0.5, 1.0, 2.0, 4.0])
    noises = jnp.array([1e-4, 1e-2, 1e-1])
    ls, nz = jnp.meshgrid(lengthscales, noises, indexing="ij")
    return ls.reshape(-1), nz.reshape(-1)


def fit_gp(x: jax.Array, y: jax.Array) -> GPPosterior:
    """Fit a GP to observations.

    ``x``: (n, d) raw features (already encoded); ``y``: (n,) raw costs.
    Features are assumed pre-standardized by the search-space encoder;
    targets are standardized internally so the amplitude grid is scale free.
    """
    # canonicalize_dtype maps float64 -> float32 when x64 is disabled, so this
    # picks the widest float the runtime allows without poking at jax.config
    # internals (jax.config.read is not stable across JAX versions).
    x = jnp.asarray(x, jax.dtypes.canonicalize_dtype(jnp.float64))
    y = jnp.asarray(y, x.dtype)
    y_mean = jnp.mean(y)
    y_std = jnp.maximum(jnp.std(y), 1e-8)
    y_n = (y - y_mean) / y_std

    ls_grid, nz_grid = _candidate_grid(x.shape[-1])

    # One raw d² tensor serves the whole (lengthscale, noise) grid: scalar
    # lengthscales only rescale it.
    n = x.shape[0]
    d2 = pairwise_sqdist(x)
    eye = jnp.eye(n, dtype=x.dtype)

    def lml_for(ls, nz):
        k = matern52_from_sqdist(d2, ls) + (nz + _JITTER) * eye
        chol = jnp.linalg.cholesky(k)
        alpha = jax.scipy.linalg.cho_solve((chol, True), y_n)
        return (
            -0.5 * y_n @ alpha
            - jnp.sum(jnp.log(jnp.diagonal(chol)))
            - 0.5 * n * jnp.log(2.0 * jnp.pi)
        )

    lmls = jax.vmap(lml_for)(ls_grid, nz_grid)
    lmls = jnp.where(jnp.isfinite(lmls), lmls, -jnp.inf)
    best = jnp.argmax(lmls)
    params = GPParams(
        lengthscale=ls_grid[best],
        amplitude=jnp.asarray(1.0, x.dtype),
        noise=nz_grid[best],
    )

    k = matern52_from_sqdist(d2, params.lengthscale) + (params.noise + _JITTER) * eye
    chol = jnp.linalg.cholesky(k)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y_n)
    return GPPosterior(
        params=params, x_train=x, chol=chol, alpha=alpha, y_mean=y_mean, y_std=y_std
    )


def gp_predict(post: GPPosterior, x_new: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Posterior mean and standard deviation at ``x_new`` (m, d), in raw y units."""
    x_new = jnp.asarray(x_new, post.x_train.dtype)
    k_star = matern52(post.x_train, x_new, post.params)  # (n, m)
    mean_n = k_star.T @ post.alpha
    v = jax.scipy.linalg.solve_triangular(post.chol, k_star, lower=True)
    var_n = post.params.amplitude - jnp.sum(v * v, axis=0)
    var_n = jnp.maximum(var_n, 1e-12)
    mean = mean_n * post.y_std + post.y_mean
    std = jnp.sqrt(var_n) * post.y_std
    return mean, std
