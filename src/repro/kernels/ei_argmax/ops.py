"""Public fused EI/argmax op: tile selection, padding, backend dispatch.

Three lanes, all computing the same (argmax index, max EI) pair:

  * **TPU** — the compiled Pallas kernel (`kernel.ei_argmax_kernel_call`),
    streaming the n axis through VMEM tiles.
  * **interpret** (``interpret=True``) — the SAME kernel under the Pallas
    interpreter: every kernel-body op runs as ordinary XLA:CPU ops, which
    makes the kernel's numerics testable bit-for-bit against the unfused
    reference on the CPU test topology.  This is the kernel-identity test
    lane, not a production path (the interpreter re-enters Python per
    tile — ~5× slower than the scan lane below).
  * **CPU default** — a `lax.scan` over the same tiles running the same
    shared tail (`tile.ei_from_sqdist`) with the same strict-`>` streaming
    (max, argmax) carry.  This is the production CPU lane: one compiled
    loop, O(B·tile) transient memory, bitwise identical to both the
    interpret lane and the unfused reference (pinned by
    `tests/test_ei_argmax_kernel.py` and the golden fixtures).

Padding is exact, not approximate: n is zero-padded up to a tile multiple
and the candidate mask is padded FALSE, so padded columns reach the
reduction as EI = -inf — they can never win the strict-`>` update, and an
all-masked pool returns index 0 exactly like `jnp.argmax` over all -inf.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.gp import pairwise_sqdist
from repro.kernels.ei_argmax.kernel import ei_argmax_kernel_call
from repro.kernels.ei_argmax.tile import ei_from_sqdist

__all__ = ["ei_argmax"]

# 1024-wide tiles: B=24 tiles are ~100 KB transient, and the scan lane's
# per-step time is flat across 512–8192 on the CPU backend — small spaces
# shrink to one 128-multiple tile.
_DEFAULT_TILE = 1024
_MIN_TILE = 128


def _pick_tile(n: int, tile: Optional[int]) -> int:
    if tile is not None:
        t = int(tile)
        if t < 1:
            raise ValueError(f"tile must be positive, got {tile}")
        return t
    if n >= _DEFAULT_TILE:
        return _DEFAULT_TILE
    return -(-n // _MIN_TILE) * _MIN_TILE  # one tile, 128-aligned


def _should_use_kernel(interpret: Optional[bool]) -> bool:
    if interpret is not None:
        return True  # caller explicitly chose the kernel path
    return jax.default_backend() == "tpu"


def _ei_argmax_scan(
    enc, mask, feats, pm, alpha, chol, ls, y_mean, y_std, best, xi, tile,
) -> Tuple[jax.Array, jax.Array]:
    """The production CPU lane: compiled scan over tiles, streaming carry.

    The scan is driven by tile OFFSETS with `dynamic_slice` in the body,
    not by reshaping the encoding into scan inputs: under the engines'
    chunk `vmap` a (nt, tile, d) xs would need the whole (chunk, n, d)
    geometry transposed to put the scan axis first — a full-size transient
    copy per step, which is exactly the footprint this lane exists to
    avoid.  Slicing returns the same values bit for bit."""
    n_pad, d = enc.shape
    nt = n_pad // tile

    def body(carry, off):
        run_val, run_idx = carry
        et = jax.lax.dynamic_slice(enc, (off, 0), (tile, d))
        mt = jax.lax.dynamic_slice(mask, (off,), (tile,))
        ei = ei_from_sqdist(
            pairwise_sqdist(feats, et), pm[:, None], alpha, chol,
            ls, y_mean, y_std, best, mt, xi,
        )
        tile_max = jnp.max(ei)
        tile_idx = jnp.argmax(ei).astype(jnp.int32) + off
        upd = tile_max > run_val  # strict: lowest maximizing index survives
        return (
            jnp.where(upd, tile_max, run_val),
            jnp.where(upd, tile_idx, run_idx),
        ), None

    init = (
        jnp.asarray(-jnp.inf, jnp.float32),
        jnp.asarray(0, jnp.int32),
    )
    offsets = jnp.arange(nt, dtype=jnp.int32) * tile
    (run_val, run_idx), _ = jax.lax.scan(body, init, offsets)
    return run_idx, run_val


def ei_argmax(
    enc: jax.Array,  # (n, d) static float32 encoding of the space
    mask: jax.Array,  # (n,) bool — candidate mask (cand & ~obs)
    feats: jax.Array,  # (B, d) packed features of observed points
    pm: jax.Array,  # (B,) f32 packed-slot validity
    alpha: jax.Array,  # (B,) K⁻¹ y_train, selected hyperparameters
    chol: jax.Array,  # (B, B) Cholesky of the masked training kernel
    ls: jax.Array,  # () selected lengthscale
    y_mean: jax.Array,  # () target mean
    y_std: jax.Array,  # () target std
    best: jax.Array,  # () best observed cost
    *,
    xi: float = 0.0,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fused (argmax index, max EI) over the masked candidates, traceable.

    Bitwise equal to `argmax/max of tile.ei_from_sqdist` over the full
    (B,n) block without ever materializing it.  ``tile=None`` picks the
    default width; ``interpret`` forces the Pallas path (True: interpreter
    — the kernel-identity test lane).
    """
    n, d = enc.shape
    t = _pick_tile(n, tile)
    n_pad = -(-n // t) * t
    if n_pad != n:
        enc = jnp.pad(enc, ((0, n_pad - n), (0, 0)))
        mask = jnp.pad(mask, (0, n_pad - n))  # False → EI = -inf, inert
    pm = pm.astype(jnp.float32)
    if _should_use_kernel(interpret):
        scal = jnp.stack([
            ls.astype(jnp.float32),
            y_mean.astype(jnp.float32),
            y_std.astype(jnp.float32),
            best.astype(jnp.float32),
        ])
        val, idx = ei_argmax_kernel_call(
            enc, mask, feats, pm, alpha, chol, scal,
            tile=t, xi=float(xi),
            interpret=bool(interpret) if interpret is not None else False,
        )
        return idx[0, 0], val[0, 0]
    return _ei_argmax_scan(
        enc, mask, feats, pm, alpha, chol, ls, y_mean, y_std, best,
        float(xi), t,
    )
