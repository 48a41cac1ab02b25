"""Explicit expert parallelism via shard_map (the MoE hot path).

Why not GSPMD: the capacity-dispatch scatter/gather over a buffer sharded on
(experts × capacity) makes the SPMD partitioner reshard per layer — the
kimi-k2 dry-run showed ~93 TB of collectives per step.  The comm pattern we
actually want is static and tiny, so we write it explicitly:

  * tokens are sharded over the data axes and REPLICATED over "model";
  * experts are sharded over "model" — each model shard owns E/TP experts;
  * every device routes its local tokens, keeps the (token, k)-pairs that
    hit its own experts, runs the local expert GEMMs, and contributes a
    partial combine;
  * ONE ``psum`` over "model" completes the combine — the same volume as a
    single tensor-parallel all-reduce, replacing GSPMD's guesswork.

Capacity semantics: each expert's capacity applies per data shard
(C_local = ceil(local_tokens·k·cf/E)) rather than globally — with even
routing this drops the same tokens in expectation; noted in DESIGN.md.

FSDP composes: if the rules shard the experts' embed axis over data, the
weight shards are all-gathered over the data axes inside the body (that IS
ZeRO-3's gather, made explicit).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.config import ModelConfig
from repro.parallel.constraints import current_context

__all__ = ["moe_shard_map_available", "moe_apply_shard_map"]


def _axes_tuple(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    return (entry,) if isinstance(entry, str) else tuple(entry)


def moe_shard_map_available(cfg: ModelConfig, x_shape) -> bool:
    """Expert-parallel path is usable when a context with a model axis is
    active and the expert count divides over it."""
    ctx = current_context()
    if ctx is None or cfg.moe is None:
        return False
    rules, mesh = ctx
    maxis = rules.get("experts")
    if maxis is None or not isinstance(maxis, str) or maxis not in mesh.shape:
        return False
    return cfg.moe.num_experts % mesh.shape[maxis] == 0


def moe_apply_shard_map(
    p: Dict[str, Any], cfg: ModelConfig, x: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Drop-in replacement for the local moe dispatch (experts/router only —
    shared expert and dense residual are handled by the caller)."""
    rules, mesh = current_context()
    moe = cfg.moe
    assert moe is not None
    cd = cfg.cdtype
    b, t, d = x.shape
    e, k = moe.num_experts, moe.top_k

    maxis = rules.get("experts")  # "model"
    batch_axes = [
        a for a in _axes_tuple(rules.get("batch"))
        if b % mesh.shape[a] == 0 and a in mesh.shape
    ]
    # honor only a prefix whose product divides b
    keep = []
    size = 1
    for a in batch_axes:
        if b % (size * mesh.shape[a]) == 0:
            keep.append(a)
            size *= mesh.shape[a]
    batch_axes = tuple(keep)
    fsdp_axes = tuple(
        a for a in _axes_tuple(rules.get("embed"))
        if a in mesh.shape and d % mesh.shape[a] == 0
    )

    tp = mesh.shape[maxis]
    e_local = e // tp
    n_local = (b // max(size, 1)) * t
    c_local = max(int(math.ceil(n_local * k * moe.capacity_factor / e)), k)

    x_spec = P(batch_axes if len(batch_axes) > 1 else
               (batch_axes[0] if batch_axes else None), None, None)
    router_spec = P(None, maxis)
    w_in_spec = P(maxis, fsdp_axes if len(fsdp_axes) > 1 else
                  (fsdp_axes[0] if fsdp_axes else None), None)
    w_out_spec = P(maxis, None, fsdp_axes if len(fsdp_axes) > 1 else
                   (fsdp_axes[0] if fsdp_axes else None))

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(x_spec, router_spec, w_in_spec, w_in_spec, w_out_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    def body(xl, router_l, wg_l, wu_l, wo_l):
        nb, nt, _ = xl.shape
        n = nb * nt
        xf = xl.reshape(n, d)

        # Router needs all E columns: gather the model-sharded router weight.
        if tp > 1:
            router = jax.lax.all_gather(router_l, maxis, axis=1, tiled=True)
        else:
            router = router_l
        # FSDP: gather the embed shards of the local experts' weights.
        if fsdp_axes:
            for ax in fsdp_axes:
                wg_l = jax.lax.all_gather(wg_l, ax, axis=1, tiled=True)
                wu_l = jax.lax.all_gather(wu_l, ax, axis=1, tiled=True)
                wo_l = jax.lax.all_gather(wo_l, ax, axis=2, tiled=True)

        probs = jax.nn.softmax(
            (xf.astype(jnp.float32) @ router.astype(jnp.float32)), axis=-1
        )
        gate_vals, expert_ids = jax.lax.top_k(probs, k)  # (n, k)
        gate_vals = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9
        )

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], e, dtype=jnp.float32), 0)
        aux = moe.router_aux_weight * e * jnp.sum(me * ce)
        for ax in batch_axes:
            aux = jax.lax.pmean(aux, ax)

        first = jax.lax.axis_index(maxis) * e_local
        flat_ids = expert_ids.T.reshape(-1)  # (k*n,) k-major
        flat_gates = gate_vals.T.reshape(-1)
        local = (flat_ids >= first) & (flat_ids < first + e_local)
        lid = jnp.where(local, flat_ids - first, e_local)
        oh = jax.nn.one_hot(lid, e_local, dtype=jnp.int32)  # (k*n, e_l)
        pos_all = jnp.cumsum(oh, axis=0) - oh
        pos = jnp.sum(pos_all * oh, axis=-1)
        kept = local & (pos < c_local)
        slot = jnp.where(kept, lid * c_local + pos, e_local * c_local)

        xk = jnp.tile(xf, (k, 1)).astype(cd)
        buf = jnp.zeros((e_local * c_local + 1, d), cd).at[slot].add(xk)
        buf = buf[: e_local * c_local].reshape(e_local, c_local, d)

        gate = jnp.einsum("ecd,edf->ecf", buf, wg_l.astype(cd))
        up = jnp.einsum("ecd,edf->ecf", buf, wu_l.astype(cd))
        h = jax.nn.silu(gate.astype(jnp.float32)).astype(cd) * up
        out_buf = jnp.einsum("ecf,efd->ecd", h, wo_l.astype(cd)).reshape(-1, d)

        gathered = jnp.where(
            kept[:, None], out_buf[jnp.minimum(slot, e_local * c_local - 1)], 0.0
        )
        combined = jnp.sum(
            (gathered * flat_gates[:, None].astype(cd)).reshape(k, n, d), 0
        )
        y = jax.lax.psum(combined, maxis)
        return y.reshape(nb, nt, d), aux

    y, aux = body(x, p["router"], p["wi_gate"], p["wi_up"], p["wo"])
    return y, aux
