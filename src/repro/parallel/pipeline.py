"""GPipe-style pipeline parallelism over the "pod" axis (shard_map).

An alternative to pure data-parallel pod composition: layers are split
into S contiguous stages (stage s on pod s), microbatches stream through
with ``ppermute`` hand-offs.  The schedule is the classic GPipe forward
wavefront — T = M + S − 1 ticks for M microbatches, bubble fraction
(S−1)/T — and, because ``shard_map`` + ``ppermute`` are differentiable,
``jax.grad`` through ``pipeline_apply`` yields the reverse wavefront
automatically.

Design notes:
  * Stage parameters are the layer stack sharded on the layer axis over
    "pod" (rules override ``layers → pod``), so FSDP/TP inside a stage
    compose unchanged on the remaining mesh axes (marked ``auto``).
  * Every stage computes every tick (bubble ticks process garbage with
    constant shapes — the standard static-schedule trick); outputs are
    masked and psum-broadcast from the last stage.
  * This is the dry-run's *optional* engine: DP over pods wins at the
    assigned batch sizes (EXPERIMENTS.md §Perf), but the plumbing is load-
    bearing for >2-pod scale-out where DP's gradient all-reduce crosses
    the slow inter-pod links every step while PP crosses them M times per
    step with activation-sized messages.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply"]


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,  # pytree; leaves stacked (num_layers, ...) — sharded over pod
    micro_inputs: jax.Array,  # (M, b, ...) microbatched activations
    *,
    mesh: Mesh,
    pod_axis: str = "pod",
) -> jax.Array:
    """Run ``stage_fn(local_params, h)`` as an S-stage GPipe.

    ``stage_fn`` receives the stage's local parameter slice (layers/S on the
    leading axis) and one microbatch of activations; returns activations of
    the same shape.  Returns (M, b, ...) outputs (replicated over pod).
    """
    n_stages = int(mesh.shape[pod_axis])
    m = micro_inputs.shape[0]
    other_axes = tuple(a for a in mesh.axis_names if a != pod_axis)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            jax.tree.map(lambda _: P(pod_axis), stage_params),
            P(),  # every stage sees the (M, b, ...) input block
        ),
        out_specs=P(),
        # Fully manual over the mesh: unmentioned axes replicate the
        # operands, which is exactly the P()-spec'd input block, and the
        # remaining axes ({other_axes}) stay available to explicit
        # collectives inside ``stage_fn``.  The carries are device-varying,
        # so the varying-manual-axes check is off.
        check_vma=False,
    )
    def run(params_local, inputs):
        stage = jax.lax.axis_index(pod_axis)
        perm = [(i, i + 1) for i in range(n_stages - 1)]
        # carries are device-varying (each stage holds different data)
        h0 = jnp.zeros_like(inputs[0])
        outputs0 = jnp.zeros_like(inputs)

        def tick(carry, t):
            received, outputs = carry
            # stage 0 injects microbatch t (while available); others consume.
            inject = jnp.where(t < m, t, 0)
            h_in = jnp.where(stage == 0, inputs[inject], received)
            h_out = stage_fn(params_local, h_in)
            # last stage emits microbatch t-S+1 once the wave arrives
            emit = t - (n_stages - 1)
            slot = jnp.clip(emit, 0, m - 1)
            should_emit = (stage == n_stages - 1) & (emit >= 0)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(should_emit, h_out, outputs[slot]),
                slot, 0,
            )
            received = jax.lax.ppermute(h_out, pod_axis, perm)
            return (received, outputs), None

        (_, outputs), _ = jax.lax.scan(
            tick, (h0, outputs0), jnp.arange(m + n_stages - 1)
        )
        # broadcast the last stage's outputs to every pod
        mask = (stage == n_stages - 1).astype(outputs.dtype)
        return jax.lax.psum(outputs * mask, pod_axis)

    return run(stage_params, micro_inputs)
