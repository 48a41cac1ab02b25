"""Online-softmax flash attention for TPU (Pallas).

Grid layout (the canonical TPU flash schedule):

    grid = (batch, q_heads, T/block_q, S/block_k)

The first three axes are parallel; the KV-block axis is sequential
("arbitrary") so VMEM scratch accumulators — running max ``m``, running
denominator ``l`` and the output accumulator ``acc`` — persist across KV
iterations of one (b, h, q-block) cell.  Each step applies the standard
online-softmax rescaling.

TPU-native choices:
  * block_q = block_k = 128 by default — the MXU's native tile; both GEMMs
    in the inner loop (q·kᵀ and p·v) are 128-aligned.
  * Per-block VMEM footprint: q/k/v tiles + (block_q × D) f32 accumulator
    ≈ 128·D·(2·3 + 4) bytes ≈ 0.9 MB at D=128 — far under the ~16 MB VMEM
    budget, leaving room for double buffering.
  * GQA is folded into the k/v BlockSpec index maps (kv_head = h·KV // H):
    no KV replication in HBM, the grouping costs nothing.
  * Causal masking compares absolute positions; fully-masked KV blocks are
    skipped with ``pl.when`` (upper-triangle blocks do zero work — this is
    what makes causal flash ~2× over dense at long S).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_kernel_call"]


NEG_INF = -1e30


def _kernel(
    q_ref, k_ref, v_ref,  # VMEM tiles
    o_ref,
    m_scratch, l_scratch, acc_scratch,
    *,
    sm_scale: float,
    block_q: int,
    block_k: int,
    causal: bool,
    kv_valid_len: int,
):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scratch[...] = jnp.full(m_scratch.shape, NEG_INF, jnp.float32)
        l_scratch[...] = jnp.zeros(l_scratch.shape, jnp.float32)
        acc_scratch[...] = jnp.zeros(acc_scratch.shape, jnp.float32)

    q_start = qi * block_q
    k_start = ki * block_k

    # A KV block is live unless causality places it entirely in the future.
    live = True
    if causal:
        live = k_start <= q_start + block_q - 1

    @pl.when(live)
    def _compute():
        q = q_ref[0, :, 0, :].astype(jnp.float32)  # (bq, D)
        k = k_ref[0, :, 0, :].astype(jnp.float32)  # (bk, D)
        v = v_ref[0, :, 0, :].astype(jnp.float32)  # (bk, D)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale  # (bq, bk)

        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos < kv_valid_len  # padded keys never attend
        if causal:
            qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = mask & (qpos >= kpos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scratch[...]  # (bq, 1)
        l_prev = l_scratch[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)  # (bq, bk)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)

        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scratch[...] = acc_scratch[...] * alpha + pv
        m_scratch[...] = m_new
        l_scratch[...] = l_new

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scratch[...], 1e-30)
        o_ref[0, :, 0, :] = (acc_scratch[...] / l).astype(o_ref.dtype)


def flash_attention_kernel_call(
    q: jax.Array,  # (B, T, H, D) — T, S already padded to block multiples
    k: jax.Array,  # (B, S, KV, D)
    v: jax.Array,
    *,
    causal: bool,
    sm_scale: Optional[float] = None,
    block_q: int = 128,
    block_k: int = 128,
    kv_valid_len: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    b, t, h, d = q.shape
    s, kv = k.shape[1], k.shape[2]
    if t % block_q or s % block_k:
        raise ValueError(f"padded dims required: T={t} S={s} blocks "
                         f"({block_q},{block_k})")
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    valid = kv_valid_len if kv_valid_len is not None else s

    grid = (b, h, t // block_q, s // block_k)

    kernel = functools.partial(
        _kernel,
        sm_scale=scale,
        block_q=block_q,
        block_k=block_k,
        causal=causal,
        kv_valid_len=valid,
    )

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1, d), lambda b_, h_, qi, ki: (b_, qi, h_, 0)),
            pl.BlockSpec(
                (1, block_k, 1, d),
                lambda b_, h_, qi, ki, kv=kv, h=h: (b_, ki, h_ * kv // h, 0),
            ),
            pl.BlockSpec(
                (1, block_k, 1, d),
                lambda b_, h_, qi, ki, kv=kv, h=h: (b_, ki, h_ * kv // h, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, block_q, 1, d), lambda b_, h_, qi, ki: (b_, qi, h_, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, t, h, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
