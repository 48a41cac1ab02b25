"""Packed-observation, fully-jitted Bayesian-optimization step and fleet update.

The paper replays every search 200× over a 69-point space; the ROADMAP's
north star is production-scale spaces (real cloud catalogs span 10⁴–10⁵
instance-type × count combinations).  At most B points are ever observed
per search (B = the trial budget, 16–32 in the paper's regime), so the GP
never needs full-extent linear algebra — and, since PR 3, it never needs
full-extent *geometry* either: the engine carries a packed **(B,d) feature
buffer** of the observed points (in trial order) and computes the (B,B)
training block and the (B,n) cross block on the fly against the static
(n,d) encoding.  Nothing of extent n×n is ever materialized.

Per-step cost (n = space extent, d = features, B = trial capacity,
w = warm-start seeds):

    layout           memory      kernel blocks          factorizations  posterior
    dense            O(n²)       6·O(n²·d)              18·O(n³)        O(n²)
    d²-gather (PR 2) O(n²)       gathers + 6·O(B²)      18·O(B³)        O(B·n)
    feature (PR 3)   O(n·d)      O(B²d + B·n·d)+6·O(B²) 18·O(B³)        O(B·n)
    fused (PR 8)     O(n·d)      same flops, streamed   18·O(B³)        O(B·tile)

The packed layouts' factorization extent depends on the backend the head
is lowered for (`_packed_head`).  Elsewhere than on the TPU, XLA's
Cholesky and `cho_solve` factor all B columns: 18·O(B³) per row, the
column above.  On the TPU, where XLA's Cholesky and triangular inversion
are loops over all B columns that cost time per column rather than per
flop, a column loop runs over the t observed slots only: t trips of
18·O(B²) elementwise work (under a chunk's vmap, the chunk's largest t),
then one t-trip back-substitution for the selected grid point.  Columns
≥ t are the identity either way.

The fused row's last column is the *transient* bound: the EI/argmax tail
runs as a streaming (max, argmax) reduction over n/tile tiles
(`repro.kernels.ei_argmax`), so the (B,n) cross block — the feature
layout's one remaining extent-n per-step allocation — never exists; its
flops are unchanged.

Session-era paths ride the same step with zero new device code (PR 4):

    warm seeding     O(w·d) host prefill of the packed (B,)/(B,d) buffers
                     before the first step; a seeded search starts at t = w,
                     so it runs ≤ B − w fresh steps at unchanged extents
    vectorized split O(n log n) §III-D mask build once per admission
                     (search_space.split_priority_mask), float64 on the
                     host, bit-equal to the host rule — no O(n) Python
                     narrowing loop
    sharded step     one `shard_map` dispatch advances S chunks, one per
                     device (repro.fleet.sharding): per-device compute is
                     the unchanged extent-r chunk program, communication
                     is ZERO bytes per step (searches are independent, no
                     collectives) — only the O(S·r·(n·d + B·d + n))
                     placement at admission and the O(S·r·B) register
                     gather at retirement, once per chunk lifetime
    mid-flight       a cancelled/failed/preempted row is retired by
    retirement       latching its `done` flag (PR 7): every write in the
                     step is already gated on `live = ~done ∧ budget`, so
                     the row freezes in place as a dummy-pad — zero new
                     device code, and its vmap-independent chunk-mates'
                     traces are untouched by construction (pinned
                     bit-identical by the golden disturbed-fleet scenario)
    per-group        the async service (PR 9, repro.fleet.service) drives
    dispatch         each admission group's chunks from its own host
                     thread — the device program is the unchanged chunk
                     step; only WHO calls it and WHEN changes, plus an
                     optional committed device placement per group.
                     Because vmap rows are independent and row extents
                     stay inside the f32 batch-extent-invariant [2, 8]
                     window, chunk membership and step interleaving are
                     trace-neutral: the async schedule is pinned
                     bit-identical to the lockstep drain by the
                     golden-through-service and interleaving-fuzz lanes
    objective        O(n) host derivation once per submission (PR 10,
    routing          repro.fleet.session.objective_table): "cost" and
                     weighted runtime/cost blends rebuild the job's (n,)
                     score table from its pricing axes BEFORE packing —
                     the device step is objective-agnostic and unchanged
                     at every extent; objective="runtime" passes the
                     job's own table through untouched (pinned as_dict-
                     equal to the golden fixtures by `-m pricing`)

The d²-gather layout paid a one-off O(n²·d) `precompute_d2` per search and
held the (n,n) tensor for its whole lifetime — an O(n²) memory wall that
caps searches near n ≈ 10³.  The feature layout recomputes the two distance
blocks each step (O(B²d + Bnd), trivially cheap for B ≪ n) from O(n·d)
state, so n = 10⁴–10⁵ spaces run in megabytes.  All layouts are retained:
`bo_step_core` (feature) is the default in both engines,
`bo_step_core_fused` streams its EI/argmax tail through
`repro.kernels.ei_argmax` (layout="fused", bit-identical — the tail IS the
same function — with O(B·tile) transients), `bo_step_core_gather` +
`precompute_d2` are the PR-2 path kept for cross-checking and benchmarking,
and `bo_step_core_dense` is the original full-extent baseline.

Layout.  `FleetState` holds the trial log `tried` (B,), a packed target
buffer `py` (B,), and the packed feature buffer `feats` (B,d), all aligned
in trial order — observation k lives in slot k.  `bo_step_core` computes
the (B,B)/(B,n) raw squared-distance blocks from `feats` via
`packed_sqdist_blocks`, standardizes the packed targets, selects
(lengthscale, noise) by masked log marginal likelihood over the 18-point
grid, computes the posterior over all n points for the winner only, and
argmaxes Expected Improvement over the candidate mask.

Bit-identity across layouts.  `packed_sqdist_blocks` computes the (B,n)
cross block with *exactly* `gp.pairwise_sqdist`'s expansion — sum-of-
squares per row, one matmul for the cross terms, clamp at zero — which is
also how `precompute_d2` fills the (n,n) tensor; the contraction axis (d)
and its summation order are identical whether the left operand has extent
B or n, so cross rows are bitwise equal to rows of the precomputed tensor.
The (B,B) training block is then a column gather of the cross block by
`tried` (a second (B,d)·(d,B) self-matmul can fuse differently from the
(n,d)·(d,n) one — observed at d = 1 — while gathers are exact), so block
identity with the d²-gather layout holds by construction (XLA:CPU,
float32; property-checked in `tests/test_feature_buffer.py`).  Every op
downstream of the blocks is shared (`_packed_core`), so the two layouts
produce bit-identical (pick, max_ei, best) — and therefore bit-identical
search traces.

Padding is exact, not approximate.  Packed slots ≥ t are masked: their
kernel rows/columns are zeroed and their diagonal entries set to 1, so the
(B,B) Cholesky block-decouples — L is the factor of the observed block
direct-summed with an identity — and padded slots contribute exactly 0 to
alpha, the posterior mean, and the variance correction (their cross rows
are zeroed too).  Garbage in padded `tried`/`py`/`feats` slots is inert as
long as it is finite (the engine only ever writes -1/0 there); padded
*space* points (mask-level padding) are likewise never candidates and
never observed.  Warm-start seeding composes with this unchanged: seeds
occupy slots < t like any observation (index in `tried`, float32 cost in
`py`, the canonical encoding row in `feats`, observation mask set), so the
padding proof applies verbatim to a seeded buffer — slots ≥ t stay inert,
slots < t are ordinary training points.

Float32 discipline (unchanged from the dense engine; it concerns the CPU,
whose head is the full-extent LAPACK one, `_factor_lapack`, bit for bit
as before — the TPU's column-loop head agrees with it to rounding, not
bitwise, and computes every product elementwise in float32, never on the
matrix unit's reduced-precision passes): XLA:CPU float32
results differ between compilation contexts — batch extent 1 compiles to
different programs than extents ≥ 2 (hence everything runs at extent ≥ 2),
extents 2–8 are empirically invariant, ≥ 12 diverge, and `lax.while_loop`
bodies compute different last-ulp floats (and run 5-8× slower) than the
same ops standalone.  In the late-search regime one ulp flips argmax picks,
so BOTH engines execute the single `fleet_step` program:

  * the fleet engine (`repro.fleet.batched_engine`) vmaps it over lockstep
    chunks of 2–8 jobs, grouped by (space shape, packed capacity B) so
    every job factorizes the same static extents as a solo run would;
  * the sharded fleet engine (`repro.fleet.sharding`) runs the SAME
    vmapped program per device under `shard_map` — the body is traced at
    the per-device chunk extent (still 2–8), so sharding adds no new
    compilation context and stays bit-identical (pinned by the
    golden-trace harness in `tests/golden/`);
  * the sequential driver's `SequentialProbe` carries a batch-extent-2
    state (row 1 a discarded duplicate) on device across a whole search,
    donating it to each jitted probe call: per step one f32 scalar goes up
    (the latest observed cost, patched into the packed buffer) and three
    scalars come back — no per-iteration copies of any state buffer.

`tests/test_fleet.py` asserts sequential↔batched trace identity
seed-for-seed (both layouts, and feature↔gather cross-layout);
`tests/test_feature_buffer.py` property-checks the feature blocks against
the d²-gather blocks and `gp.pairwise_sqdist` bit-for-bit, including
padded-slot inertness; `tests/test_core_bo.py` checks the packed math
against the readable reference in `gp.py`/`acquisition.py` and the
retained dense path (`bo_step_core_dense`, the full-extent baseline for
`benchmarks/fleet_bench.py`'s scaling sweep).
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.gp import GPParams, matern52, matern52_from_sqdist, pairwise_sqdist
from repro.kernels.ei_argmax import ei_argmax, ei_from_sqdist

__all__ = [
    "FleetState",
    "SequentialProbe",
    "bo_step",
    "bo_step_core",
    "bo_step_core_dense",
    "bo_step_core_fused",
    "bo_step_core_gather",
    "encode_features",
    "fleet_step",
    "gather_sqdist_blocks",
    "packed_sqdist_blocks",
    "precompute_d2",
]

_JITTER = 1e-8
_LENGTHSCALES = (0.1, 0.25, 0.5, 1.0, 2.0, 4.0)
_NOISES = (1e-4, 1e-2, 1e-1)

_LAYOUTS = ("feature", "gather", "fused")

# The name scope of the extent-n EI tail in the feature and fused layouts:
# the cross distance block, posterior, EI and argmax over the candidates
# (in `fused`, the `ei_argmax` kernel).  Metadata only: a profile
# attributes device time by it, the numerics do not change.
EI_TAIL_SCOPE = "ei_tail"


def encode_features(encoded) -> np.ndarray:
    """Canonical float32 host view of the encoded space.

    THE single conversion both engines use for the static (n,d) geometry:
    the feature buffer is filled with rows of exactly this array, so the
    sequential and fleet engines (and host-side buffer reconstruction in
    `SequentialProbe.start`) all see bit-identical features.
    """
    return np.asarray(encoded, np.float32)


@jax.jit
def _pairwise_sqdist_f32(encoded: jax.Array) -> jax.Array:
    return pairwise_sqdist(encoded.astype(jnp.float32))


def precompute_d2(encoded) -> jax.Array:
    """(n,n) raw pairwise squared distances over the encoded space, float32.

    The PR-2 d²-gather layout: computed once per search — UNBATCHED, so
    sequential and fleet runs of the same space get bit-identical tensors —
    and threaded through every step as a constant.  O(n²) memory; retained
    for cross-checking the feature-buffer layout and for benchmarking, not
    used by the default engines.
    """
    return _pairwise_sqdist_f32(jnp.asarray(encode_features(encoded)))


def packed_sqdist_blocks(
    feats: jax.Array,  # (B, d) packed features of observed points
    encoded: jax.Array,  # (n, d) static encoding of the whole space
    tried: jax.Array,  # (B,) i32 trial log, -1 padded
) -> Tuple[jax.Array, jax.Array]:
    """((B,B), (B,n)) raw squared-distance blocks from the feature buffer.

    The (B,n) cross block is `gp.pairwise_sqdist`'s expansion verbatim —
    same sum-of-squares, same matmul contraction over d, same clamp — and
    its rows are bitwise equal to rows of `precompute_d2`'s (n,n) tensor
    (the contraction axis and its order are identical whether the left
    operand has extent B or n).  The (B,B) training block is then a COLUMN
    GATHER of the cross block by `tried`, not a second matmul: a
    (B,d)·(d,B) self-product can fuse differently from the (n,d)·(d,n)
    one (observed at d = 1 on XLA:CPU, last-ulp), while gathers are exact
    — so block identity with the d²-gather layout holds by construction.
    O(Bnd) compute and O(Bn) memory; nothing of extent n² exists.
    """
    d2_bn = pairwise_sqdist(feats, encoded)
    idx = jnp.maximum(tried, 0)  # padded slots gather column 0; masked later
    return d2_bn[:, idx], d2_bn


def gather_sqdist_blocks(
    d2: jax.Array,  # (n, n) precomputed raw squared distances
    tried: jax.Array,  # (B,) i32 trial log, -1 padded
) -> Tuple[jax.Array, jax.Array]:
    """((B,B), (B,n)) blocks gathered from the precomputed (n,n) tensor.

    The PR-2 layout; padded slots gather row 0 (finite garbage, masked
    exactly downstream).
    """
    idx = jnp.maximum(tried, 0)
    return d2[idx[:, None], idx[None, :]], d2[idx]


def _masked_posterior(
    x: jax.Array,  # (n, d)
    obs_mask: jax.Array,  # (n,) bool
    y_n: jax.Array,  # (n,) standardized targets, 0 where unobserved
    lengthscale: jax.Array,
    noise: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Reference form of the exact-masking construction: (lml, mean, var)
    over ALL n points for one (lengthscale, noise).

    This is the specification `tests/test_core_bo.py` checks against the
    readable subset-GP in `gp.py`; the packed `bo_step_core` computes the
    same math with the observed set packed into (B,) buffers instead of
    masked in place at extent n.
    """
    m = obs_mask.astype(x.dtype)
    params = GPParams(lengthscale=lengthscale, amplitude=jnp.asarray(1.0, x.dtype), noise=noise)
    k = matern52(x, x, params)
    mm = m[:, None] * m[None, :]
    k_eff = k * mm + jnp.diag(jnp.where(obs_mask, noise + _JITTER, 1.0))
    chol = jnp.linalg.cholesky(k_eff)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y_n * m)
    lml = (
        -0.5 * (y_n * m) @ alpha
        - jnp.sum(jnp.log(jnp.diagonal(chol)) * m)
        - 0.5 * jnp.sum(m) * jnp.log(2.0 * jnp.pi)
    )
    k_star = k * m[:, None]  # masked training rows
    mean_n = k_star.T @ alpha
    v = jax.scipy.linalg.solve_triangular(chol, k_star, lower=True)
    var_n = jnp.maximum(1.0 - jnp.sum(v * v, axis=0), 1e-12)
    return lml, mean_n, var_n


def _factor_lapack(ks18, nz18, pmask, y_train, t):
    """XLA's factorization of the 18 masked grid matrices at full extent
    B, whatever ``t``: `jnp.linalg.cholesky` and `cho_solve` per grid
    point (LAPACK on the CPU).  Returns ``(lmls, best_h, chol, alpha)``:
    the grid's log marginal likelihoods (-inf where not finite), the
    index of the largest, and that grid point's factor and K⁻¹ y_train."""
    b = y_train.shape[0]
    pm = pmask.astype(jnp.float32)
    diag_idx = jnp.arange(b)

    def factorize(k_masked, noise):
        """Masked-kernel Cholesky + lml for one (lengthscale, noise)."""
        diag = jnp.where(pmask, noise + _JITTER, 1.0)
        k_eff = k_masked.at[diag_idx, diag_idx].add(diag)
        chol = jnp.linalg.cholesky(k_eff)
        alpha = jax.scipy.linalg.cho_solve((chol, True), y_train)
        lml = (
            jnp.matmul(
                -0.5 * y_train, alpha, precision=jax.lax.Precision.HIGHEST
            )
            - jnp.sum(jnp.log(jnp.diagonal(chol)) * pm)
            - 0.5 * jnp.sum(pm) * jnp.log(2.0 * jnp.pi)
        )
        return lml, chol, alpha

    lmls, chols, alphas = jax.vmap(factorize)(ks18, nz18)
    lmls = jnp.where(jnp.isfinite(lmls), lmls, -jnp.inf)
    best_h = jnp.argmax(lmls)
    return lmls, best_h, chols[best_h], alphas[best_h]


def _factor_loop(ks18, nz18, pmask, y_train, t):
    """The same selection as `_factor_lapack`, with a column loop that
    runs over the t observed slots only.

    Right-looking Cholesky of all 18 grid matrices at once, in place: step
    j writes column j of L over column j of the matrix and subtracts its
    outer product from the trailing block; the same step carries the
    forward substitution z = L⁻¹ y_train.  Padded columns (≥ t) are e_j in
    the masked matrix, so they would come out as the identity; the loop
    stops at t and leaves them so.  Each grid point's LML is then
    −½ z·z − Σ log diag(L)·pm − ½ t log 2π, and the winner's alpha comes
    from one back-substitution Lᵀα = z, again over t columns.  Columns are
    picked by one-hot masks and every product is elementwise float32, so
    nothing runs on the matrix unit's reduced-precision passes; a batch of
    rows (vmap) runs to the largest t among them, the others idle.
    """
    b = y_train.shape[0]
    pm = pmask.astype(jnp.float32)
    idx = jnp.arange(b)
    diag = jnp.where(pmask[None], nz18[:, None] + _JITTER, 1.0)  # (18, B)
    a0 = jnp.where(idx[:, None] == idx[None, :], ks18 + diag[:, :, None], ks18)
    z0 = jnp.broadcast_to(y_train, diag.shape)

    # `lax.select` and 0/1 masks in the loop bodies, not `jnp.where`: each
    # `jnp.where` is a jit of its own, which vmap batches anew in the trace
    # of every chunk extent, and that trace is part of the service's set-up.
    def column(j, carry):
        a, z = carry  # (18, B, B) in-place factor, (18, B) L⁻¹ y so far
        at = idx == j
        e = at.astype(jnp.float32)
        col = jnp.sum(a * e, axis=-1)  # (18, B) a[:, :, j]
        d = jnp.sqrt(jnp.sum(col * e, axis=-1, keepdims=True))
        l = col / d * (idx > j)  # L[j+1:, j]
        zj = jnp.sum(z * e, axis=-1, keepdims=True) / d
        new_col = at[None, None, :] & (idx >= j)[None, :, None]
        a = jax.lax.select(
            jnp.broadcast_to(new_col, a.shape),
            jnp.broadcast_to((l + d * e)[:, :, None], a.shape),
            a - l[:, :, None] * l[:, None, :],
        )
        z = jax.lax.select(jnp.broadcast_to(at, z.shape),
                           jnp.broadcast_to(zj, z.shape), z - l * zj)
        return a, z

    a, z = jax.lax.fori_loop(0, t, column, (a0, z0))
    lmls = (
        -0.5 * jnp.sum(z * z, axis=-1)
        - jnp.sum(jnp.log(jnp.diagonal(a, axis1=-2, axis2=-1)) * pm, axis=-1)
        - 0.5 * jnp.sum(pm) * jnp.log(2.0 * jnp.pi)
    )
    lmls = jnp.where(jnp.isfinite(lmls), lmls, -jnp.inf)
    best_h = jnp.argmax(lmls)
    chol = jnp.where(idx[:, None] >= idx[None, :], a[best_h], 0.0)

    def back(k, v):
        # Lᵀα = z column by column, from j = t − 1 down; v holds z above
        # the current column and α from it on.
        j = t - 1 - k
        at = idx == j
        e = at.astype(jnp.float32)
        row = jnp.sum(chol * e[:, None], axis=0)  # L[j, :]
        aj = jnp.sum(v * e) / jnp.sum(row * e)
        return jax.lax.select(at, jnp.broadcast_to(aj, v.shape),
                              v - row * (idx < j) * aj)

    alpha = jax.lax.fori_loop(0, t, back, z[best_h])
    return lmls, best_h, chol, alpha


def _factor(ks18, nz18, pmask, y_train, t):
    """The head's factorization for the platform it is lowered for: the
    trip-bounded loop on the TPU, where XLA's Cholesky and triangular
    inversion are loops over all B columns; XLA's (LAPACK) elsewhere."""
    return jax.lax.platform_dependent(
        ks18, nz18, pmask, y_train, t,
        tpu=_factor_loop, default=_factor_lapack,
    )


@jax.named_scope("gp_head")
def _packed_head(
    d2_bb: jax.Array,  # (B, B) raw squared distances, training block
    py: jax.Array,  # (B,) f32 packed observed costs, trial order
    t: jax.Array,  # () i32 observations made (valid packed slots)
    lengthscales: Tuple[float, ...] = _LENGTHSCALES,
    noises: Tuple[float, ...] = _NOISES,
    factor=_factor,
) -> Tuple[jax.Array, ...]:
    """The training-side math every packed layout shares: target
    standardization, the 18-point (lengthscale, noise) grid, masked
    Cholesky factorizations, and marginal-likelihood selection.  Everything
    here is extent-B — the space extent n never appears — so the fused
    layout runs it verbatim and streams only the tail.  A narrower grid
    (``lengthscales`` × ``noises``) serves cross-backend checks that pin
    the selection.

    The factorizations are `_factor`'s, chosen by the platform the head is
    lowered for: on the TPU a column loop over the t observed slots
    (`_factor_loop`), elsewhere XLA's full-extent Cholesky and `cho_solve`
    (`_factor_lapack`, the CPU's golden bits).  Both return the same
    identity-padded (B,B) factor, so the EI tail is the same either way.
    ``factor`` takes one of them directly, to run the TPU head on the CPU.

    Returns ``(pm, best, ls_sel, chol, alpha, y_mean, y_std)``: the
    selected posterior factors the EI tail consumes.

    Traced under the ``gp_head`` name scope, so that a profile can
    attribute the device time of its operations; the scope is metadata
    and changes no numerics.
    """
    b = py.shape[0]
    pmask = jnp.arange(b) < t
    pm = pmask.astype(jnp.float32)

    py = py.astype(jnp.float32)
    n_obs = jnp.maximum(jnp.sum(pm), 1.0)
    y_mean = jnp.sum(py * pm) / n_obs
    y_var = jnp.sum(pm * (py - y_mean) ** 2) / n_obs
    y_std = jnp.maximum(jnp.sqrt(y_var), 1e-8)
    y_train = jnp.where(pmask, (py - y_mean) / y_std, 0.0)

    # The kernel depends on the lengthscale only, and a scalar lengthscale
    # only rescales d²: 6 elementwise rescales of one (B,B) block serve all
    # 18 (lengthscale, noise) grid points.
    ls = jnp.asarray(lengthscales, jnp.float32)
    nz = jnp.asarray(noises, jnp.float32)
    ks = jax.vmap(lambda l: matern52_from_sqdist(d2_bb, l))(ls)  # (6, B, B)

    mm = pm[:, None] * pm[None, :]
    # Mask once per lengthscale (6 products), not per grid combo (18); the
    # noise only touches the diagonal, added per grid point.
    ks_masked = ks * mm[None]  # (6, B, B)
    # ls-major grid order (matches jnp.meshgrid(..., indexing="ij")):
    # combo h = (h // 3)-th lengthscale, (h % 3)-th noise.
    ks18 = jnp.repeat(ks_masked, nz.shape[0], axis=0)  # (18, B, B)
    nz18 = jnp.tile(nz, ls.shape[0])  # (18,)
    _, best_h, chol, alpha = factor(ks18, nz18, pmask, y_train, t)

    best = jnp.min(jnp.where(pmask, py, jnp.inf))
    return (
        pm, best, ls[best_h // nz.shape[0]], chol, alpha, y_mean, y_std,
    )


def _packed_core(
    d2_bb: jax.Array,  # (B, B) raw squared distances, training block
    d2_bn: jax.Array,  # (B, n) raw squared distances, cross block
    py: jax.Array,  # (B,) f32 packed observed costs, trial order
    t: jax.Array,  # () i32 observations made (valid packed slots)
    obs_mask: jax.Array,  # (n,) bool — configurations already tried
    cand_mask: jax.Array,  # (n,) bool — current candidate pool
    xi: float,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Everything downstream of the distance blocks, shared verbatim by the
    feature-buffer and d²-gather layouts — the op-for-op identity of this
    tail is what makes the two layouts' picks bit-identical.  The EI math
    itself is `ei_from_sqdist`, the SAME function the fused layout's tiled
    lanes execute per (B,tile) block (`repro.kernels.ei_argmax`), so the
    unfused reference and the fused kernel cannot drift apart.
    """
    pm, best, ls_sel, chol, alpha, y_mean, y_std = _packed_head(d2_bb, py, t)
    # Posterior + EI over all n points for the selected hyperparameters
    # only: one (B,n) rescale of the cross block, masked training rows.
    with jax.named_scope(EI_TAIL_SCOPE):
        ei = ei_from_sqdist(
            d2_bn, pm[:, None], alpha, chol, ls_sel, y_mean, y_std, best,
            cand_mask & ~obs_mask, xi,
        )
        pick = jnp.argmax(ei)
        max_ei = jnp.max(ei)
    return pick, max_ei, best


def bo_step_core(
    encoded: jax.Array,  # (n, d) static float32 encoding of the whole space
    feats: jax.Array,  # (B, d) packed features of observed points, trial order
    tried: jax.Array,  # (B,) i32 trial log in trial order, -1 padded
    py: jax.Array,  # (B,) f32 packed observed costs, aligned with feats
    t: jax.Array,  # () i32 observations made (valid packed slots)
    obs_mask: jax.Array,  # (n,) bool — configurations already tried
    cand_mask: jax.Array,  # (n,) bool — current candidate pool
    xi: float = 0.0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One feature-buffer BO iteration, traceable.  Returns
    (pick_index, max_ei, best).

    All training-side linear algebra runs at the packed capacity B; the
    space extent n only appears in the O(Bnd) cross-block matmul, the (B,n)
    rescale, and the EI argmax.  Nothing of extent n² exists anywhere.
    The extent-n work (the cross block, then `_packed_core`'s tail) runs
    under the ``ei_tail`` name scope.
    """
    with jax.named_scope(EI_TAIL_SCOPE):
        d2_bb, d2_bn = packed_sqdist_blocks(feats, encoded, tried)
    return _packed_core(d2_bb, d2_bn, py, t, obs_mask, cand_mask, xi)


def bo_step_core_fused(
    encoded: jax.Array,  # (n, d) static float32 encoding of the whole space
    feats: jax.Array,  # (B, d) packed features of observed points, trial order
    tried: jax.Array,  # (B,) i32 trial log in trial order, -1 padded
    py: jax.Array,  # (B,) f32 packed observed costs, aligned with feats
    t: jax.Array,  # () i32 observations made (valid packed slots)
    obs_mask: jax.Array,  # (n,) bool — configurations already tried
    cand_mask: jax.Array,  # (n,) bool — current candidate pool
    xi: float = 0.0,
    *,
    tile: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One fused-kernel BO iteration, traceable.  Returns
    (pick_index, max_ei, best) — bit-identical to `bo_step_core`.

    The extent-B head (`_packed_head`) runs unchanged; the n-extent tail is
    the fused streaming kernel (`repro.kernels.ei_argmax`): tiles of the
    candidate axis flow through distance → posterior rescale → EI → a
    running (max, argmax) pair, so the (B,n) cross block is NEVER
    materialized — peak transient memory drops from O(B·n) to O(B·tile).
    The training block is computed directly as `pairwise_sqdist(feats,
    encoded[tried])`: for d ≥ 2 this reproduces the feature lane's gathered
    block bit-for-bit (the (B,d)·(d,B) contraction is the same reduction,
    and XLA:CPU compiles it stably across program contexts — property- and
    golden-pinned).

    d = 1 delegates to the feature path wholesale: XLA:CPU rewrites the
    degenerate (·,1)·(1,·) matmul elementwise with CONTEXT-DEPENDENT
    fusion — any differently-shaped fused program drifts by an ulp
    (observed for the direct training block and for zero-padded d→2
    formulations alike), and one ulp flips late-search argmax picks.
    Identical program ⇒ identical bits; a single-feature space is
    degenerate for catalog-scale search anyway, which is the regime the
    kernel exists for.

    ``tile`` (None → 1024-wide tiles, single-tile for small n) and
    ``interpret`` (None → TPU: compiled Pallas, CPU: compiled `lax.scan`;
    True: Pallas interpreter, the kernel-identity test lane) are
    trace-static.
    """
    if encoded.shape[-1] < 2:
        return bo_step_core(encoded, feats, tried, py, t, obs_mask,
                            cand_mask, xi)
    idx = jnp.maximum(tried, 0)  # padded slots: column 0, masked via pm
    d2_bb = pairwise_sqdist(feats, encoded[idx])
    pm, best, ls_sel, chol, alpha, y_mean, y_std = _packed_head(d2_bb, py, t)
    with jax.named_scope(EI_TAIL_SCOPE):
        pick, max_ei = ei_argmax(
            encoded, cand_mask & ~obs_mask, feats, pm, alpha, chol,
            ls_sel, y_mean, y_std, best, xi=xi, tile=tile,
            interpret=interpret,
        )
    return pick, max_ei, best


def bo_step_core_gather(
    d2: jax.Array,  # (n, n) raw pairwise squared distances (precompute_d2)
    tried: jax.Array,  # (B,) i32 trial log in trial order, -1 padded
    py: jax.Array,  # (B,) f32 packed observed costs, aligned with tried
    t: jax.Array,  # () i32 observations made (valid packed slots)
    obs_mask: jax.Array,  # (n,) bool — configurations already tried
    cand_mask: jax.Array,  # (n,) bool — current candidate pool
    xi: float = 0.0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The retained PR-2 d²-gather BO iteration: blocks gathered from the
    once-per-search (n,n) tensor instead of recomputed from features.

    Kept as the cross-check for the feature-buffer layout (the two must be
    bit-identical — `tests/test_feature_buffer.py`) and for the scaling
    sweep in `benchmarks/fleet_bench.py`.  Not used by the default engines.
    """
    d2_bb, d2_bn = gather_sqdist_blocks(d2, tried)
    return _packed_core(d2_bb, d2_bn, py, t, obs_mask, cand_mask, xi)


def bo_step_core_dense(
    encoded: jax.Array,  # (n, d) standardized features of the whole space
    obs_mask: jax.Array,  # (n,) bool — configurations already tried
    y: jax.Array,  # (n,) observed costs (garbage where not observed)
    cand_mask: jax.Array,  # (n,) bool — current candidate pool
    xi: float = 0.0,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The pre-packed full-extent BO step: O(18n³) per call.

    Retained as the dense baseline `benchmarks/fleet_bench.py` times the
    packed layouts against, and as a second reference for the packed math
    in `tests/test_core_bo.py`.  Not used by either search engine.
    """
    x = encoded.astype(jnp.float32)
    m = obs_mask.astype(x.dtype)
    n_obs = jnp.maximum(jnp.sum(m), 1.0)
    y = y.astype(x.dtype)
    y_mean = jnp.sum(y * m) / n_obs
    y_var = jnp.sum(m * (y - y_mean) ** 2) / n_obs
    y_std = jnp.maximum(jnp.sqrt(y_var), 1e-8)
    y_n = jnp.where(obs_mask, (y - y_mean) / y_std, 0.0)

    ls = jnp.asarray(_LENGTHSCALES, x.dtype)
    nz = jnp.asarray(_NOISES, x.dtype)
    d2 = pairwise_sqdist(x)
    ks = jax.vmap(lambda l: matern52_from_sqdist(d2, l))(ls)  # (6, n, n)

    mm = m[:, None] * m[None, :]
    y_train = y_n * m
    ks_masked = ks * mm[None]  # (6, n, n)
    diag_idx = jnp.arange(ks.shape[-1])

    def factorize(k_masked, noise):
        diag = jnp.where(obs_mask, noise + _JITTER, 1.0)
        k_eff = k_masked.at[diag_idx, diag_idx].add(diag)
        chol = jnp.linalg.cholesky(k_eff)
        alpha = jax.scipy.linalg.cho_solve((chol, True), y_train)
        lml = (
            -0.5 * y_train @ alpha
            - jnp.sum(jnp.log(jnp.diagonal(chol)) * m)
            - 0.5 * jnp.sum(m) * jnp.log(2.0 * jnp.pi)
        )
        return lml, chol, alpha

    ks18 = jnp.repeat(ks_masked, nz.shape[0], axis=0)  # (18, n, n)
    nz18 = jnp.tile(nz, ls.shape[0])  # (18,)
    lmls, chols, alphas = jax.vmap(factorize)(ks18, nz18)
    lmls = jnp.where(jnp.isfinite(lmls), lmls, -jnp.inf)
    best_h = jnp.argmax(lmls)

    k_star = ks[best_h // nz.shape[0]] * m[:, None]  # masked training rows
    mean_n = k_star.T @ alphas[best_h]
    v = jax.scipy.linalg.solve_triangular(chols[best_h], k_star, lower=True)
    var_n = jnp.maximum(1.0 - jnp.sum(v * v, axis=0), 1e-12)
    std_n = jnp.sqrt(var_n)

    mean = mean_n * y_std + y_mean
    std = std_n * y_std

    best = jnp.min(jnp.where(obs_mask, y, jnp.inf))
    improvement = best - mean - xi
    z = improvement / jnp.maximum(std, 1e-12)
    cdf = 0.5 * (1.0 + jax.scipy.special.erf(z / jnp.sqrt(2.0)))
    pdf = jnp.exp(-0.5 * z * z) / jnp.sqrt(2.0 * jnp.pi)
    ei = jnp.maximum(improvement * cdf + std * pdf, 0.0)
    ei = jnp.where(cand_mask & ~obs_mask, ei, -jnp.inf)
    pick = jnp.argmax(ei)
    return pick, jnp.max(ei), best


class FleetState(NamedTuple):
    """Per-job search state, device-resident between `fleet_step` calls.

    The packed buffers (`tried`, `py`, `feats`) have static capacity B =
    the job's trial budget; slot k holds the k-th observation, in trial
    order.  `feats` carries the observed points' encoded features — the
    feature-buffer layout computes its kernel blocks from it, the d²-gather
    layout carries it untouched (zeros) so both layouts share one state
    type and one donation contract.
    """

    obs: jax.Array  # (n,) bool — observation mask over the space
    tried: jax.Array  # (B,) i32 — trial log, -1 padded
    py: jax.Array  # (B,) f32 — packed observed costs, aligned with tried
    feats: jax.Array  # (B, d) f32 — packed features of observed points
    t: jax.Array  # () i32 — trials made
    stop: jax.Array  # () i32 — stop-criterion iteration, -1 = not yet
    pb: jax.Array  # () i32 — phase boundary, -1 = still in phase 0
    done: jax.Array  # () bool
    last_ei: jax.Array  # () f32 — max EI of the latest BO step
    last_best: jax.Array  # () f32 — best observed cost at the latest step


def fleet_step(
    state: FleetState,
    geom: jax.Array,  # (n,d) encoded [feature layout] | (n,n) d2 [gather]
    costs: jax.Array,  # (n,) f32 — full observation table
    prio_mask: jax.Array,  # (n,) bool — priority pool (phase 0)
    rem_mask: jax.Array,  # (n,) bool — remaining pool (phase 1)
    init_picks: jax.Array,  # (I,) i32 — scripted random initialization
    init_count: jax.Array,  # () i32
    max_trials: jax.Array,  # () i32 — trial budget (pool size ∧ max_iters)
    min_obs: jax.Array,  # () i32 — no stopping before this many trials
    ei_stop_rel: jax.Array,  # () f32 — stop when max EI < rel·best
    to_exhaustion: jax.Array,  # () bool — record the stop but keep going
    xi: float = 0.0,
    layout: str = "feature",
) -> FleetState:
    """One search iteration: candidate pools → BO step → stop/phase
    bookkeeping → observation.  Applying it `max_trials` times executes one
    complete two-phase search; semantics mirror
    `repro.core.bayesopt._bo_loop` exactly.  A no-op once the job is done.

    ``layout`` is trace-static: "feature" (default) takes the (n,d)
    encoding as ``geom`` and maintains the packed feature buffer; "fused"
    takes the same geometry and buffer but streams the n-extent tail
    through the fused EI/argmax kernel (`bo_step_core_fused` — no (B,n)
    block); "gather" takes the precomputed (n,n) distance tensor (the
    retained PR-2 path) and leaves ``state.feats`` untouched.
    """
    if layout not in _LAYOUTS:
        raise ValueError(f"unknown layout {layout!r}; want one of {_LAYOUTS}")
    obs, tried, py, feats, t, stop, pb = (
        state.obs, state.tried, state.py, state.feats, state.t, state.stop,
        state.pb,
    )
    n_init_slots = init_picks.shape[0]

    budget_left = t < max_trials
    live = ~state.done & budget_left
    prio_left = prio_mask & ~obs
    rem_left = rem_mask & ~obs
    in_phase0 = jnp.any(prio_left)
    cand = jnp.where(in_phase0, prio_left, rem_left)
    has_cand = jnp.any(cand)
    # Entering the remaining phase with a non-empty pool records the
    # boundary (sequential: set at phase entry, before any phase-1 step).
    # Gated on ~done only, NOT on the budget: when max_iters lands exactly
    # on the phase-0/phase-1 boundary the sequential engine still records
    # the boundary before its budget check returns.
    pb = jnp.where(~state.done & (pb < 0) & ~in_phase0 & jnp.any(rem_left), t, pb)

    is_init = t < init_count
    if layout == "feature":
        bo_pick, max_ei, best = bo_step_core(
            geom, feats, tried, py, t, obs, cand, xi
        )
    elif layout == "fused":
        bo_pick, max_ei, best = bo_step_core_fused(
            geom, feats, tried, py, t, obs, cand, xi
        )
    else:
        bo_pick, max_ei, best = bo_step_core_gather(
            geom, tried, py, t, obs, cand, xi
        )
    scripted = init_picks[jnp.clip(t, 0, n_init_slots - 1)]
    pick = jnp.where(is_init, scripted, bo_pick).astype(jnp.int32)

    fire = (
        live
        & has_cand
        & ~is_init
        & (stop < 0)
        & (t >= min_obs)
        & (max_ei < ei_stop_rel * best)
    )
    stop = jnp.where(fire, t, stop)
    halt = fire & ~to_exhaustion
    observe = live & has_cand & ~halt

    slot = jnp.minimum(t, tried.shape[0] - 1)
    obs = jnp.where(observe, obs.at[pick].set(True), obs)
    tried = jnp.where(observe, tried.at[slot].set(pick), tried)
    py = jnp.where(observe, py.at[slot].set(costs[pick]), py)
    if layout in ("feature", "fused"):
        # The observed point's features enter the packed buffer — the only
        # geometry the next step's kernel blocks will read.
        feats = jnp.where(observe, feats.at[slot].set(geom[pick]), feats)
    t = t + observe.astype(jnp.int32)
    # A job is done when its candidates ran out, its stop criterion halted
    # it, or its trial budget is exhausted (the last also settles zero-budget
    # dummy pads so early-stop polling can see an all-done chunk).
    done = state.done | (live & (~has_cand | halt)) | ~budget_left
    return FleetState(
        obs=obs, tried=tried, py=py, feats=feats, t=t, stop=stop, pb=pb,
        done=done,
        last_ei=jnp.where(live, max_ei, state.last_ei),
        last_best=jnp.where(live, best, state.last_best),
    )


@partial(jax.jit, static_argnames=("xi", "layout"), donate_argnums=(0,))
def _probe_step(
    state2: FleetState,  # batch-extent-2 state (row 1: discarded duplicate)
    geom2, costs2, prio2, rem2, init_picks2, init_count2, last_cost,
    *, xi: float, layout: str,
):
    """One `fleet_step` application at batch extent 2 (extent 1 compiles to
    different float32 numerics).  The state is DONATED: XLA updates the
    packed buffers (including the (B,d) feature buffer) in place instead of
    copying them each iteration.

    The probe runs before the cost of its pick is known, so slot t-1 holds a
    placeholder 0 from the previous call's observation; `last_cost` patches
    in the real value before any math runs.  (The feature buffer needs no
    patching: the picked point's features are known at observation time.)
    """
    t_prev = state2.t[0]
    slot = jnp.maximum(t_prev - 1, 0)
    val = jnp.where(t_prev > 0, last_cost, state2.py[0, slot])
    state2 = state2._replace(py=state2.py.at[:, slot].set(val))

    def one(s, g, c, p, r, ip, ic):
        return fleet_step(
            s, g, c, p, r, ip, ic,
            s.t + 1,  # budget for exactly one more trial
            jnp.asarray(0, jnp.int32),
            jnp.asarray(0.0, jnp.float32),
            jnp.asarray(True),  # never halt inside the probe
            xi,
            layout,
        )

    out = jax.vmap(one)(state2, geom2, costs2, prio2, rem2, init_picks2,
                        init_count2)
    b = out.tried.shape[1]
    pick = out.tried[0, jnp.minimum(t_prev, b - 1)]
    return out, pick, out.last_ei[0], out.last_best[0]


class SequentialProbe:
    """Device-resident sequential BO stepper over the shared `fleet_step`.

    Carries the packed search state on device between steps at batch extent
    2, donating it back to every jitted probe call, so a sequential search
    makes no per-iteration device copies: per step, one f32 scalar goes up
    (the latest observed cost) and (pick, max_ei, best) scalars come back.

    ``capacity`` must equal the trial budget the fleet engine would compute
    for the same job — both engines then factorize (B,B) systems of the
    same static extent, which is what keeps their traces bit-identical.

    ``layout="feature"`` (default) keeps only the (n,d) encoding on device
    — O(n·d) memory, the 10⁴–10⁵-point regime; ``layout="fused"`` keeps
    the same encoding and streams the EI tail through the fused kernel
    (O(B·tile) transients, bit-identical picks); ``layout="gather"`` is
    the retained PR-2 path holding the (n,n) distance tensor.
    """

    def __init__(self, encoded, capacity: int, xi: float = 0.0,
                 layout: str = "feature"):
        if layout not in _LAYOUTS:
            raise ValueError(f"unknown layout {layout!r}; want one of {_LAYOUTS}")
        enc = encode_features(encoded)
        self._n, self._d = enc.shape
        self._b = max(int(capacity), 1)
        self._xi = float(xi)
        self._layout = layout
        self._enc = enc
        if layout in ("feature", "fused"):
            geom = jnp.asarray(enc)
        else:
            geom = precompute_d2(enc)
        self._geom2 = jnp.stack([geom, geom])
        # Observation values are irrelevant inside the probe: the real cost
        # arrives via `last_cost` on the following call.
        self._costs2 = jnp.zeros((2, self._n), jnp.float32)
        self._rem2 = jnp.zeros((2, self._n), bool)
        self._init_picks2 = jnp.zeros((2, 1), jnp.int32)
        self._init_count2 = jnp.zeros(2, jnp.int32)  # no scripted init
        self._pool2 = None
        self._state = None

    def set_pool(self, pool_mask) -> None:
        """Install the current phase's candidate pool (device copy, once)."""
        pool = jnp.asarray(np.asarray(pool_mask, bool))
        self._pool2 = jnp.stack([pool, pool])

    def start(self, obs_mask, trial_order: Sequence[int], trial_costs) -> None:
        """Build the device state from the host-side search history."""
        k = len(trial_order)
        if k > self._b:
            raise ValueError(f"{k} observations exceed packed capacity {self._b}")
        order = np.asarray(trial_order, np.int32)
        tried = np.full(self._b, -1, np.int32)
        py = np.zeros(self._b, np.float32)
        feats = np.zeros((self._b, self._d), np.float32)
        tried[:k] = order
        py[:k] = np.asarray(trial_costs, np.float32)
        # Rows of the canonical float32 encoding — bit-identical to what the
        # on-device observation writes would have accumulated.
        feats[:k] = self._enc[order]

        def two(a):
            a = jnp.asarray(a)
            return jnp.stack([a, a])

        self._state = FleetState(
            obs=two(np.asarray(obs_mask, bool)),
            tried=two(tried),
            py=two(py),
            feats=two(feats),
            t=two(np.asarray(k, np.int32)),
            stop=two(np.asarray(-1, np.int32)),
            pb=two(np.asarray(-1, np.int32)),
            done=two(np.asarray(False)),
            last_ei=two(np.asarray(0.0, np.float32)),
            last_best=two(np.asarray(np.inf, np.float32)),
        )

    def step(self, last_cost: float) -> Tuple[int, float, float]:
        """One BO iteration.  Returns (pick_index, max_ei, best_observed)."""
        if self._state is None or self._pool2 is None:
            raise RuntimeError("call start() and set_pool() before step()")
        self._state, pick, ei, best = _probe_step(
            self._state, self._geom2, self._costs2, self._pool2, self._rem2,
            self._init_picks2, self._init_count2,
            jnp.asarray(last_cost, jnp.float32), xi=self._xi,
            layout=self._layout,
        )
        return int(pick), float(ei), float(best)


def bo_step(
    encoded,
    obs_mask,
    y,
    cand_mask,
    xi: float = 0.0,
    *,
    trial_order: Optional[Sequence[int]] = None,
    capacity: Optional[int] = None,
    layout: str = "feature",
) -> Tuple[int, float, float]:
    """One standalone BO iteration.  Returns (pick_index, max_ei, best).

    Packs the observed set on the fly — in ascending index order unless
    ``trial_order`` is given (a sequential search passes its real trial
    order so the packed buffer matches the fleet engine's bit-for-bit) —
    and probes the shared `fleet_step` program once.  ``capacity`` defaults
    to the number of observations (a full buffer).
    """
    obs_mask = np.asarray(obs_mask, bool)
    y = np.asarray(y, np.float32)
    order = (
        np.asarray(trial_order, np.int64)
        if trial_order is not None
        else np.flatnonzero(obs_mask)
    )
    cap = int(capacity) if capacity is not None else max(1, len(order))
    probe = SequentialProbe(encoded, cap, xi=xi, layout=layout)
    probe.set_pool(cand_mask)
    probe.start(obs_mask, order, y[order])
    last = float(y[order][-1]) if len(order) else 0.0
    return probe.step(last)
