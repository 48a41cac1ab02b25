"""Device-sharded lockstep execution: chunk bundles spread over JAX devices.

The lockstep engine advances every live search one BO iteration per
`TuningSession.step()` — but each `(space shape, packed capacity)` chunk of
≤ 8 jobs is one jitted dispatch, executed serially on one device.  A 64-job
service fleet therefore pays 8 dispatches per step and uses one core no
matter how many the host has.  This module shards the JOB AXIS: up to S
lockstep chunks (same shapes, same packed capacity, same row extent) are
stacked along a leading shard axis and advanced by ONE jitted
`shard_map` call over a 1-D device mesh — each device runs the per-chunk
program on its own slice, so S chunks advance in parallel for the dispatch
cost of one.

Why `shard_map` (and not `pmap` or GSPMD-partitioned `jit`):

  * the body is traced at the PER-DEVICE extent (the chunk's row count r),
    so each device compiles exactly the program the single-device engine
    runs — the same `fast_bo.fleet_step` vmapped at an extent in [2, 8].
    Bit-identity with the unsharded reference then rests only on the
    repo's established batch-extent invariance (extents 2–8 produce
    identical float32 on XLA:CPU) plus "same program, identical CPU
    devices" — both already load-bearing for the unsharded engine, and
    re-pinned by the golden-trace harness (`tests/golden/`);
  * GSPMD-partitioned `jit` would trace the vmap at extent S·r (> 8
    diverges on XLA:CPU) and let the partitioner re-derive per-device
    code — no extent guarantee;
  * `pmap` gives the same per-device program but its dispatch path is
    5-10× slower than jit's C++ fast path on CPU — measured SLOWER than
    the serial chunk loop on the dispatch-bound service fleet, which is
    exactly the workload sharding must win.

There is NO cross-shard communication inside the update: searches are
independent, so the partitioned program is collective-free and the only
inter-device traffic is the initial placement of each chunk's buffers and
the final gather at retirement (O(S·r·(n + B·d)) bytes, once per chunk
lifetime, not per step).

Scope of the bit-identity guarantee.  On XLA:CPU every ADMITTED search's
trace is bit-identical to the unsharded engine's, for any submission
pattern — that is what the golden harness and the shard-invariance
property suite pin.  On a TPU the partitioned program compiles
differently from the plain chunk program, and a few searches end apart
(61/64 and 63/64 of an n = 32768 catalog fleet matched on v5e, `feature`
and `fused`), while a live reshard from 4 to 2 devices kept all 64
survivors identical to the undisturbed 4-device run.  One timing
caveat survives: sharded bundles retire as a unit (a
fast chunk's outcomes are published when its bundle's slowest chunk
finishes), so in a WARM-STARTING session that submits new jobs mid-flight
without draining, the class-history snapshot a submit sees — and hence
that new job's warm seeds — can differ across shard counts.  Drain
boundaries (``drain()``, or stepping a wave to completion before the next
submit, as the golden warm-session scenario does) make warm seeding
shard-count-independent; per-shard retirement is future work.

On CPU, multiple devices come from
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` — set before the
JAX backend initializes (`repro.hostdevices.force_host_device_count`,
used by the tests' ``conftest.py`` and ``tests/golden/regen.py``).  A sharded session degrades loudly, not silently: asking for more
shards than there are devices raises, while ``shard="auto"`` uses
whatever is available (1 device → the unsharded reference path).
"""

from __future__ import annotations

from functools import lru_cache
from typing import Optional, Sequence, Tuple, Union

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.core.fast_bo import fleet_step

__all__ = ["collapse_rows", "resolve_shard_devices", "sharded_update"]

# Name of the 1-D mesh axis the job/chunk axis is sharded over.
_AXIS = "jobs"


def resolve_shard_devices(
    shard: Union[None, int, str] = None,
    devices: Optional[Sequence] = None,
) -> Optional[Tuple]:
    """Resolve the ``shard=``/``devices=`` switch to a device tuple.

    Returns None for the single-device reference path (``shard`` unset, 1,
    or "auto" on a 1-device host), else a tuple of ≥ 2 devices.  An
    explicit ``devices=`` list wins; ``shard="auto"`` takes every local
    device; an integer asks for exactly that many and raises if the host
    does not expose them (forcing host devices is an env-var decision that
    must happen before backend init — failing loudly beats a silent
    single-device fallback that would fake the speedup).
    """
    if devices is not None:
        devs = tuple(devices)
        if shard not in (None, "auto") and int(shard) != len(devs):
            raise ValueError(
                f"shard={shard!r} disagrees with {len(devs)} explicit devices"
            )
        return devs if len(devs) > 1 else None
    if shard is None:
        return None
    if shard == "auto":
        devs = tuple(jax.devices())
        return devs if len(devs) > 1 else None
    s = int(shard)
    if s < 1:
        raise ValueError(f"shard={shard!r}: want a positive shard count")
    avail = tuple(jax.devices())
    if s > len(avail):
        raise ValueError(
            f"shard={s} but only {len(avail)} device(s) are visible — on "
            "CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{s} (or more) before the JAX backend initializes"
        )
    return avail[:s] if s > 1 else None


def collapse_rows(state, n_shards: int):
    """Host snapshot of a chunk's `FleetState` with any leading shard axis
    collapsed: member i lives at flat row i whether the chunk ran on one
    device or a mesh (shards slice the member list contiguously — see
    `repro.fleet.session._LiveChunk`).  This is the elastic re-bundle
    primitive: `TuningSession.reshard` snapshots every live row through it
    before regrouping survivors onto a new device set, and mid-flight
    cancellation reads the victim's partial trials from it before freezing
    the victim's row on device."""

    def flat(x):
        a = np.asarray(x)
        if n_shards > 1:
            return a.reshape((a.shape[0] * a.shape[1],) + a.shape[2:])
        return a

    return jax.tree_util.tree_map(flat, state)


@lru_cache(maxsize=None)
def sharded_update(devices: Tuple, xi: float, layout: str):
    """(jitted update, NamedSharding) for a bundle of len(devices) chunks.

    The update takes ``(state, geom, costs, prio_mask, rem_mask,
    init_picks, init_count, max_trials, min_obs, ei_stop_rel,
    to_exhaustion)`` where every array — the three scalars included — has a
    leading shard axis of extent S = len(devices), placed with the returned
    sharding.  Each device applies the vmapped `fast_bo.fleet_step` to its
    own chunk slice (the same per-device program `_fleet_update` runs), and
    the state is donated so per-step updates stay in place, per shard.

    Cached per (device tuple, xi, layout): one callable serves every
    bundle shape via jit's shape cache.
    """
    mesh = Mesh(np.asarray(devices), (_AXIS,))
    spec = PartitionSpec(_AXIS)

    def chunk_update(
        state, geom, costs, prio_mask, rem_mask, init_picks, init_count,
        max_trials, min_obs, ei_stop_rel, to_exhaustion,
    ):
        # Per-device view: every operand arrives as the (1, ...) slice this
        # device owns; drop the shard axis, run the chunk program, put the
        # axis back.  No collectives — searches are independent.
        def one(s, g, c, p, r, ip, ic, mt):
            return fleet_step(
                s, g, c, p, r, ip, ic, mt,
                min_obs[0], ei_stop_rel[0], to_exhaustion[0], xi, layout,
            )

        sq = jax.tree_util.tree_map(lambda x: x[0], state)
        out = jax.vmap(one)(
            sq, geom[0], costs[0], prio_mask[0], rem_mask[0],
            init_picks[0], init_count[0], max_trials[0],
        )
        return jax.tree_util.tree_map(lambda x: x[None], out)

    sm = jax.shard_map(
        chunk_update, mesh=mesh,
        in_specs=(spec,) * 11, out_specs=spec, check_vma=False,
    )
    return jax.jit(sm, donate_argnums=(0,)), NamedSharding(mesh, spec)
