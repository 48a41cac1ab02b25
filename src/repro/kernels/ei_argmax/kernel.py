"""Fused posterior+EI+argmax Pallas kernel: streaming reduction over n-tiles.

The candidate axis is the grid: tile i computes the (B,tile) distance block
of its slice of the static encoding against the (B,d) packed feature
buffer, runs the shared EI tail (`tile.ei_from_sqdist`) on it, and folds
the tile's (max EI, argmax index) into a running pair held in two (1, 1)
SMEM outputs — the flash-attention running-max idiom
(`repro.kernels.flash_attention`), with the accumulator in the revisited
output instead of VMEM scratch because the carried state is two scalars,
not a (block_q, d) tile.  The (B,n) block the unfused step materializes
never exists: peak transient memory is O(B·tile).

Tie-breaking is the load-bearing detail.  The unfused reference computes
`jnp.argmax(ei)` over all n, which returns the FIRST maximizing index.
Here each tile takes its first maximizing position (the least index whose
EI equals the tile max — `jnp.argmax` exactly whenever the max is not NaN,
and a NaN tile never wins the update below), and the cross-tile update
fires only on a STRICT `>` — a later tile that merely equals the running
max never wins — so the composition returns the first maximizing index
over all n.  `jnp.max` is exact (no rounding), so the streamed max is
bitwise the full-width max.  Both properties are pinned by
`tests/test_ei_argmax_kernel.py` (manufactured cross-tile EI ties) and the
golden fixtures.

Grid axis semantics are "arbitrary" (sequential): the running pair makes
tile i+1 depend on tile i.

Two lanes share the body and differ only in the ops and layouts Mosaic can
lower:

  * interpret (every CPU test lane): the (n,d) encoding in (tile,d) blocks,
    `gp.pairwise_sqdist`, and `tile.XLA_TAIL_OPS` — bitwise identical to
    the reference lane.
  * compiled TPU: the encoding transposed to (d,n) so a tile is lane-dense,
    the distance block in difference form (d broadcasts, no K=d matmul),
    and `MOSAIC_TAIL_OPS`: a VPU mean contraction, a statically unrolled
    forward substitution (Mosaic has no triangular solve, and the row sweep
    needs no dynamic slice), and an f32 normal CDF from the Numerical
    Recipes erfc (Mosaic has no erf; fractional error < 1.2e-7).  Its bits
    may differ from LAPACK's at the last ulp — the TPU backend is a
    different float32 context for the whole engine anyway; cross-lane
    bit-identity is only claimed per backend.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.gp import pairwise_sqdist
from repro.kernels.ei_argmax.tile import TailOps, XLA_TAIL_OPS, ei_from_sqdist

__all__ = ["MOSAIC_TAIL_OPS", "ei_argmax_kernel_call", "normal_cdf"]

_SQRT1_2 = math.sqrt(0.5)
# Numerical Recipes `erfcc` (Chebyshev fit, |fractional error| < 1.2e-7),
# highest-order coefficient first for Horner's rule.
_ERFC_COEFFS = (
    0.17087277, -0.82215223, 1.48851587, -1.13520398, 0.27886807,
    -0.18628806, 0.09678418, 0.37409196, 1.00002368, -1.26551223,
)


def normal_cdf(z: jax.Array) -> jax.Array:
    """Φ(z) = ½·erfc(−z/√2) from exp and arithmetic only.

    Relative accuracy holds in both tails (no 1 − erf cancellation)."""
    x = -z * _SQRT1_2
    a = jnp.abs(x)
    t = 1.0 / (1.0 + 0.5 * a)
    poly = _ERFC_COEFFS[0]
    for c in _ERFC_COEFFS[1:]:
        poly = poly * t + c
    erfc_a = t * jnp.exp(poly - a * a)
    return 0.5 * jnp.where(x >= 0.0, erfc_a, 2.0 - erfc_a)


def _mosaic_mean(k_star: jax.Array, alpha_col: jax.Array) -> jax.Array:
    return jnp.sum(k_star * alpha_col, axis=0, keepdims=True)


def _mosaic_sqnorm(chol: jax.Array, rhs: jax.Array) -> jax.Array:
    """Column sums of (L⁻¹ rhs)² by right-looking forward substitution,
    unrolled over the B rows: static slices and broadcasts only.  Row i of
    ``rhs`` is consumed before column i's update reaches it, so the upper
    triangle of ``chol`` is never read."""
    acc = jnp.zeros((1, rhs.shape[1]), rhs.dtype)
    for i in range(chol.shape[0]):
        x_i = rhs[i : i + 1, :] / chol[i : i + 1, i : i + 1]
        acc = acc + x_i * x_i
        rhs = rhs - chol[:, i : i + 1] * x_i
    return acc


MOSAIC_TAIL_OPS = TailOps(mean=_mosaic_mean, sqnorm=_mosaic_sqnorm, cdf=normal_cdf)


def _sqdist_t(feats: jax.Array, enc_t: jax.Array) -> jax.Array:
    """(B,m) squared distances from (B,d) features and a (d,m) encoding
    tile, summed over d in difference form."""
    d2 = jnp.zeros((feats.shape[0], enc_t.shape[1]), jnp.float32)
    for k in range(feats.shape[1]):
        diff = feats[:, k : k + 1] - enc_t[k : k + 1, :]
        d2 = d2 + diff * diff
    return d2


def _kernel(
    enc_ref,  # (tile, d) interpret | (d, tile) compiled — encoding slice
    feats_ref,  # (B, d) — packed features of observed points
    pm_ref,  # (B, 1) — packed-slot validity
    alpha_ref,  # (B,) interpret | (B, 1) compiled
    chol_ref,  # (B, B)
    scal_ref,  # (1, 4) SMEM — (lengthscale, y_mean, y_std, best)
    mask_ref,  # (tile,) interpret | (1, tile) compiled — int32 candidates
    out_val_ref,  # (1, 1) f32 SMEM — running max EI
    out_idx_ref,  # (1, 1) i32 SMEM — running argmax (global index)
    *,
    tile: int,
    xi: float,
    sqdist,
    ops: TailOps,
):
    ti = pl.program_id(0)

    @pl.when(ti == 0)
    def _init():
        out_val_ref[0, 0] = jnp.float32(-jnp.inf)
        out_idx_ref[0, 0] = jnp.int32(0)

    ei = ei_from_sqdist(
        sqdist(feats_ref[...], enc_ref[...]), pm_ref[...], alpha_ref[...],
        chol_ref[...], scal_ref[0, 0], scal_ref[0, 1], scal_ref[0, 2],
        scal_ref[0, 3],
        mask_ref[...] != 0, xi, ops=ops,
    )
    tile_max = jnp.max(ei)
    pos = jax.lax.broadcasted_iota(jnp.int32, ei.shape, ei.ndim - 1)
    tile_idx = jnp.min(jnp.where(ei == tile_max, pos, tile)) + ti * tile

    # Strict >: an equal later tile never displaces the running winner, so
    # the lowest maximizing index survives — `jnp.argmax`'s contract.
    @pl.when(tile_max > out_val_ref[0, 0])
    def _update():
        out_val_ref[0, 0] = tile_max
        out_idx_ref[0, 0] = tile_idx


def ei_argmax_kernel_call(
    enc: jax.Array,  # (n_pad, d) — encoding, zero-padded to a tile multiple
    mask: jax.Array,  # (n_pad,) bool — candidate mask, False-padded
    feats: jax.Array,  # (B, d)
    pm: jax.Array,  # (B,)
    alpha: jax.Array,  # (B,)
    chol: jax.Array,  # (B, B)
    scal: jax.Array,  # (4,) — (lengthscale, y_mean, y_std, best)
    *,
    tile: int,
    xi: float,
    interpret: bool,
):
    """((1, 1) f32 max EI, (1, 1) i32 argmax) over the masked candidates.

    Scalars live in 2-D (1, k) SMEM arrays: under the engines' chunk
    `vmap` the batch axis becomes a squeezed leading block dimension, and
    Mosaic requires the trailing two block dimensions to span the array."""
    n_pad, d = enc.shape
    b = feats.shape[0]
    if n_pad % tile:
        raise ValueError(f"n_pad={n_pad} not a multiple of tile={tile}")
    mask = mask.astype(jnp.int32)
    if interpret:
        kernel = functools.partial(
            _kernel, tile=tile, xi=xi, sqdist=pairwise_sqdist, ops=XLA_TAIL_OPS
        )
        enc_spec = pl.BlockSpec((tile, d), lambda i: (i, 0))
        alpha_spec = pl.BlockSpec((b,), lambda i: (0,))
        mask_spec = pl.BlockSpec((tile,), lambda i: (i,))
        kwargs = {}
    else:
        kernel = functools.partial(
            _kernel, tile=tile, xi=xi, sqdist=_sqdist_t, ops=MOSAIC_TAIL_OPS
        )
        enc, alpha, mask = enc.T, alpha[:, None], mask[None, :]
        enc_spec = pl.BlockSpec((d, tile), lambda i: (0, i))
        alpha_spec = pl.BlockSpec((b, 1), lambda i: (0, 0))
        mask_spec = pl.BlockSpec((1, tile), lambda i: (0, i))
        kwargs = {
            "compiler_params": pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),  # running pair is carried
            )
        }
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        grid=(n_pad // tile,),
        in_specs=[
            enc_spec,
            pl.BlockSpec((b, d), lambda i: (0, 0)),
            pl.BlockSpec((b, 1), lambda i: (0, 0)),
            alpha_spec,
            pl.BlockSpec((b, b), lambda i: (0, 0)),
            smem,
            mask_spec,
        ],
        out_specs=[smem, smem],
        out_shape=[
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.int32),
        ],
        interpret=interpret,
        **kwargs,
    )(enc, feats, pm[:, None], alpha, chol, scal[None, :], mask)
