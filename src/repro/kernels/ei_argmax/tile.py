"""THE shared EI tail: posterior squared-distance block → masked EI values.

Everything downstream of a raw squared-distance block — Matérn-5/2
rescale, posterior mean/variance against the packed training factors,
de-standardization, and Expected Improvement — lives in this ONE function.
The unfused reference lane (`repro.core.fast_bo.bo_step_core`) calls it on
the full (B,n) cross block; the fused lanes (the Pallas kernel body, its
interpret-mode twin, and the `lax.scan` CPU lane in `.ops`) call it on
(B,tile) blocks.  Sharing the function — not just the formulation — is
what makes "fused ≡ feature" a structural property instead of a reviewed
convention: the op sequence cannot drift between lanes.

Float32 bit-discipline notes (XLA:CPU, pinned by `tests/
test_ei_argmax_kernel.py` and the golden fixtures):

  * Tiling the n axis of this tail is BITWISE invariant: every op is
    either elementwise in n, or contracts only over B (`k_star.T @ alpha`,
    the triangular solve, the `v*v` column sum), so a (B,tile) slice
    computes exactly the bits of the corresponding (B,n) columns.
  * The constants are PYTHON floats (`math.sqrt`), not `jnp` scalars: a
    Pallas kernel body may not capture traced constants, and
    float32(math.sqrt(2.0)) rounds to the identical bits as
    float32(jnp.sqrt(2.0)) — the XLA lanes lose nothing.
  * Three ops are injectable (`TailOps`): the posterior mean contraction,
    the triangular-solve column norm, and the normal CDF.  Every XLA lane
    (reference, scan, interpret) uses `XLA_TAIL_OPS` — LAPACK's
    `solve_triangular` is column-slice invariant, so solving for a subset
    of right-hand-side columns reproduces the full solve's bits.  The
    compiled-TPU kernel substitutes Mosaic-lowerable ops
    (`kernel.MOSAIC_TAIL_OPS`); per-backend bits may differ, exactly like
    the rest of the engine's per-backend float32 contract.
"""

from __future__ import annotations

import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.gp import matern52_from_sqdist

__all__ = ["TailOps", "XLA_TAIL_OPS", "ei_from_sqdist"]

_SQRT2 = math.sqrt(2.0)
_SQRT2PI = math.sqrt(2.0 * math.pi)


class TailOps(NamedTuple):
    """The backend-specific ops of the EI tail."""

    mean: Callable  # (k_star (B,m), alpha) → posterior mean over m columns
    sqnorm: Callable  # (chol, k_star) → column sums of (L⁻¹ k_star)²
    cdf: Callable  # z → standard normal CDF


def _xla_mean(k_star: jax.Array, alpha: jax.Array) -> jax.Array:
    return jnp.matmul(k_star.T, alpha, precision=jax.lax.Precision.HIGHEST)


def _xla_sqnorm(chol: jax.Array, k_star: jax.Array) -> jax.Array:
    v = jax.scipy.linalg.solve_triangular(chol, k_star, lower=True)
    return jnp.sum(v * v, axis=0)


def _xla_cdf(z: jax.Array) -> jax.Array:
    return 0.5 * (1.0 + jax.scipy.special.erf(z / _SQRT2))


XLA_TAIL_OPS = TailOps(mean=_xla_mean, sqnorm=_xla_sqnorm, cdf=_xla_cdf)


def ei_from_sqdist(
    d2: jax.Array,  # (B, m) raw squared distances, training rows × candidates
    pm: jax.Array,  # (B, 1) f32 packed-slot validity (1.0 for slots < t)
    alpha: jax.Array,  # K⁻¹ y_train for the selected hyperparameters
    chol: jax.Array,  # (B, B) Cholesky factor of the masked training kernel
    ls: jax.Array,  # () selected lengthscale
    y_mean: jax.Array,  # () training-target mean
    y_std: jax.Array,  # () training-target std (clamped)
    best: jax.Array,  # () best observed cost (un-standardized)
    mask: jax.Array,  # candidate mask over the m columns; False → EI = -inf
    xi: float = 0.0,
    *,
    ops: TailOps = XLA_TAIL_OPS,
) -> jax.Array:
    """Masked EI over the m candidate columns of ``d2``, float32.

    ``m`` may be the full space extent n (the reference lane) or one tile
    (the fused lanes) — the bits per column are identical either way.
    With `XLA_TAIL_OPS`, ``alpha`` is (B,) and the result (m,); the
    Mosaic ops take a (B,1) ``alpha`` and keep a leading unit axis.
    """
    k_star = matern52_from_sqdist(d2, ls) * pm
    mean_n = ops.mean(k_star, alpha)
    var_n = jnp.maximum(1.0 - ops.sqnorm(chol, k_star), 1e-12)
    std_n = jnp.sqrt(var_n)

    # De-standardize.
    mean = mean_n * y_std + y_mean
    std = std_n * y_std

    improvement = best - mean - xi
    z = improvement / jnp.maximum(std, 1e-12)
    cdf = ops.cdf(z)
    pdf = jnp.exp(-0.5 * z * z) / _SQRT2PI
    ei = jnp.maximum(improvement * cdf + std * pdf, 0.0)
    return jnp.where(mask, ei, -jnp.inf)
