"""Property suite for the feature-buffer packed geometry (`fast_bo`).

The feature-buffer engine's whole correctness story is ONE claim: the
(B,B)/(B,n) raw squared-distance blocks computed on the fly from the packed
(B,d) feature buffer are **bit-identical** to (a) the same entries of the
whole-space `gp.pairwise_sqdist` tensor and (b) the readable
`gp.pairwise_sqdist` on the gathered point set — and that finite garbage
in packed slots ≥ t changes nothing.  Everything downstream of the blocks
is a fixed function of them (`fast_bo.bo_step_core`), so block identity ⇒
pick identity ⇒ trace identity.

Randomized draws run twice: as Hypothesis properties when hypothesis is
installed (`hypothesis_compat`), and as a fixed seed-parametrized lane that
always runs in tier-1 (the container ships no hypothesis).  Shapes are kept
small and clustered so each jitted helper compiles a handful of programs.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hypothesis_compat import given, settings, st
from repro.core.fast_bo import (
    FleetState,
    bo_step_core,
    encode_features,
    fleet_step,
    packed_sqdist_blocks,
)
from repro.core.gp import pairwise_sqdist

_blocks_feature = jax.jit(packed_sqdist_blocks)
_core_feature = jax.jit(bo_step_core)


def _draw_case(seed: int, n: int, d: int, capacity: int, t: int):
    """One randomized search state: space features, t observed points in a
    random trial order, finite garbage in every padded slot."""
    rng = np.random.default_rng(seed)
    x = encode_features(rng.normal(size=(n, d)))
    t = min(t, capacity, n)
    order = rng.choice(n, size=t, replace=False).astype(np.int32)
    tried = np.full(capacity, -1, np.int32)
    tried[:t] = order
    feats = np.zeros((capacity, d), np.float32)
    feats[:t] = x[order]
    # Finite garbage in padded slots — must be exactly inert.
    feats[t:] = 1e6 * rng.standard_normal((capacity - t, d)).astype(np.float32)
    tried_garbage = tried.copy()
    tried_garbage[t:] = rng.integers(0, n, size=capacity - t)
    py = np.zeros(capacity, np.float32)
    py[:t] = rng.normal(size=t).astype(np.float32) ** 2 + 1.0
    py_garbage = py.copy()
    py_garbage[t:] = 1e6 * rng.standard_normal(capacity - t)
    obs = np.zeros(n, bool)
    obs[order] = True
    return x, order, tried, tried_garbage, feats, py, py_garbage, obs, t


def _assert_blocks_identical(seed, n, d, capacity, t):
    x, order, tried, tried_g, feats, py, py_g, obs, t = _draw_case(
        seed, n, d, capacity, t
    )
    xj = jnp.asarray(x)
    full = np.asarray(jax.jit(pairwise_sqdist)(xj))  # (n, n), whole space

    bb_f, bn_f = _blocks_feature(jnp.asarray(feats), xj, jnp.asarray(tried))
    bb_f, bn_f = map(np.asarray, (bb_f, bn_f))

    # Valid slots: the feature blocks are entries of the whole-space
    # tensor, bit for bit.  (Padded rows read the garbage features and are
    # masked exactly downstream.)
    np.testing.assert_array_equal(bb_f[:t, :t], full[order][:, order])
    np.testing.assert_array_equal(bn_f[:t], full[order])

    # … and they match the readable gp.py reference: the cross block IS
    # `gp.pairwise_sqdist` on the observed subset, bit for bit, and the
    # training block is its column gather.  (A direct (B,B) self-call of
    # pairwise_sqdist can fuse differently at d = 1 — the very divergence
    # the column-gather construction removes — so it is compared with a
    # last-ulp tolerance, not bitwise.)
    ref_bn = np.asarray(jax.jit(pairwise_sqdist)(xj[order], xj))
    np.testing.assert_array_equal(bn_f[:t], ref_bn)
    np.testing.assert_array_equal(bb_f[:t, :t], ref_bn[:, order])
    ref_bb_self = np.asarray(jax.jit(pairwise_sqdist)(xj[order], xj[order]))
    np.testing.assert_allclose(bb_f[:t, :t], ref_bb_self, rtol=1e-6, atol=1e-6)


def _assert_cores_identical_and_padding_inert(seed, n, d, capacity, t):
    x, order, tried, tried_g, feats, py, py_g, obs, t = _draw_case(
        seed, n, d, capacity, t
    )
    if t == 0:
        return  # no observations: the step is init-scripted, nothing to pin
    xj = jnp.asarray(x)
    cand = jnp.asarray(~obs)
    obs_j = jnp.asarray(obs)
    tj = jnp.asarray(t, jnp.int32)

    ref = _core_feature(xj, jnp.asarray(feats), jnp.asarray(tried),
                        jnp.asarray(py), tj, obs_j, cand)

    # Padded-slot inertness: garbage features, garbage tried indices AND
    # garbage targets in slots ≥ t must not flip a single bit of
    # (pick, max_ei, best).
    got = _core_feature(xj, jnp.asarray(feats), jnp.asarray(tried_g),
                        jnp.asarray(py_g), tj, obs_j, cand)
    assert int(got[0]) == int(ref[0])
    assert float(got[1]) == float(ref[1])  # bitwise, no tolerance
    assert float(got[2]) == float(ref[2])


# Fixed shape pool — drawn cases index into it so the jitted helpers
# compile a handful of programs instead of one per example.
_SHAPES = [
    (18, 3, 12, 6),   # the mid-search shape
    (18, 3, 12, 12),  # full buffer, no padded slots
    (18, 3, 12, 1),   # single observation
    (40, 6, 24, 10),  # paper-regime capacity
    (12, 1, 6, 3),    # d = 1
    (9, 4, 1, 1),     # B = 1 edge
]


class TestBlockIdentity:
    @pytest.mark.parametrize("shape_i", range(len(_SHAPES)))
    @pytest.mark.parametrize("seed", range(4))
    def test_blocks_bitwise_identical(self, shape_i, seed):
        n, d, cap, t = _SHAPES[shape_i]
        _assert_blocks_identical(seed, n, d, cap, t)

    @given(data=st.data())
    @settings(max_examples=25, deadline=None)
    def test_blocks_bitwise_identical_hypothesis(self, data):
        shape_i = data.draw(st.integers(0, len(_SHAPES) - 1))
        seed = data.draw(st.integers(0, 2**31 - 1))
        n, d, cap, _ = _SHAPES[shape_i]
        t = data.draw(st.integers(0, min(cap, n)))
        _assert_blocks_identical(seed, n, d, cap, t)


class TestCoreIdentity:
    @pytest.mark.parametrize("shape_i", range(len(_SHAPES)))
    @pytest.mark.parametrize("seed", range(3))
    def test_cores_identical_padding_inert(self, shape_i, seed):
        n, d, cap, t = _SHAPES[shape_i]
        _assert_cores_identical_and_padding_inert(seed, n, d, cap, t)

    @given(data=st.data())
    @settings(max_examples=20, deadline=None)
    def test_cores_identical_hypothesis(self, data):
        shape_i = data.draw(st.integers(0, len(_SHAPES) - 1))
        seed = data.draw(st.integers(0, 2**31 - 1))
        n, d, cap, _ = _SHAPES[shape_i]
        t = data.draw(st.integers(1, min(cap, n)))
        _assert_cores_identical_and_padding_inert(seed, n, d, cap, t)


class TestLockstepExtents:
    """The blocks must stay bit-identical when computed inside the vmapped
    lockstep program — the fleet engine runs chunks of 2–8 jobs, and batch
    extent must not perturb the float32 distance math."""

    @pytest.mark.parametrize("extent", [2, 8])
    @pytest.mark.parametrize("shape_i", [0, 4])  # d = 3 and the d = 1 edge
    def test_blocks_invariant_under_vmap(self, extent, shape_i):
        n, d, cap, t = _SHAPES[shape_i]
        x, order, tried, _, feats, _, _, _, t = _draw_case(7, n, d, cap, t)
        ref_bb, ref_bn = map(np.asarray, _blocks_feature(
            jnp.asarray(feats), jnp.asarray(x), jnp.asarray(tried)))
        fb = jnp.stack([jnp.asarray(feats)] * extent)
        xb = jnp.stack([jnp.asarray(x)] * extent)
        tb = jnp.stack([jnp.asarray(tried)] * extent)
        bb, bn = jax.jit(jax.vmap(packed_sqdist_blocks))(fb, xb, tb)
        for e in range(extent):
            np.testing.assert_array_equal(np.asarray(bb)[e], ref_bb)
            np.testing.assert_array_equal(np.asarray(bn)[e], ref_bn)


class TestNoQuadraticIntermediates:
    """The acceptance-criterion guard: the traced lockstep program of
    either layout at n = 32768 must not contain ANY intermediate of extent
    n² — checked structurally on the jaxpr, so it costs a trace, not a
    run."""

    @pytest.mark.parametrize("layout", ["feature", "fused"])
    def test_fleet_step_jaxpr_has_no_n_squared(self, layout):
        n, b, d, j = 32768, 24, 6, 2
        state = FleetState(
            obs=jnp.zeros((j, n), bool),
            tried=jnp.full((j, b), -1, jnp.int32),
            py=jnp.zeros((j, b), jnp.float32),
            feats=jnp.zeros((j, b, d), jnp.float32),
            t=jnp.zeros(j, jnp.int32),
            stop=jnp.full(j, -1, jnp.int32),
            pb=jnp.full(j, -1, jnp.int32),
            done=jnp.zeros(j, bool),
            last_ei=jnp.zeros(j, jnp.float32),
            last_best=jnp.full(j, jnp.inf, jnp.float32),
        )

        def step(s, g, c, p, r, ip, ic, mt):
            return jax.vmap(
                lambda *a: fleet_step(
                    *a,
                    jnp.asarray(0, jnp.int32),
                    jnp.asarray(0.0, jnp.float32),
                    jnp.asarray(True),
                    0.0,
                    layout,
                )
            )(s, g, c, p, r, ip, ic, mt)

        jaxpr = jax.make_jaxpr(step)(
            state,
            jnp.zeros((j, n, d), jnp.float32),
            jnp.zeros((j, n), jnp.float32),
            jnp.ones((j, n), bool),
            jnp.zeros((j, n), bool),
            jnp.zeros((j, 1), jnp.int32),
            jnp.zeros(j, jnp.int32),
            jnp.full(j, b, jnp.int32),
        )

        largest = 0

        def scan(jx):
            nonlocal largest
            for eqn in jx.eqns:
                for v in eqn.outvars:
                    aval = getattr(v, "aval", None)
                    if aval is not None and hasattr(aval, "shape"):
                        size = int(np.prod(aval.shape)) if aval.shape else 1
                        largest = max(largest, size)
                for p in eqn.params.values():
                    if hasattr(p, "jaxpr"):
                        scan(p.jaxpr)

        scan(jaxpr.jaxpr)
        # The biggest legitimate tensor is the (j, B, n) cross block; n²
        # would be ~1400x larger.
        assert largest <= 4 * j * b * n, (
            f"{layout} program materializes a {largest:,}-element "
            f"intermediate at n={n} — the O(n²) wall is back"
        )
