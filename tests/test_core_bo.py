"""Property tests: the jitted packed-observation fast path (`fast_bo`)
against the readable reference GP (`gp.py` + `acquisition.py`).

The fast path packs the observed set into fixed-capacity (B,) buffers in
trial order and computes its kernel blocks from the packed (B,d) feature
buffer; padding must be *exact* — padded packed slots (and mask-level
padded space points) contribute nothing to the posterior, bit for bit.
These tests check that claim over randomized observation sets and buffer
capacities (including the full-buffer B = t and B = 1 edges), the EI/pick
agreement of `bo_step` with the reference pipeline in both layouts
(``feature`` and the fused EI/argmax kernel's ``fused``), the shared-d²
kernel helpers, and the dtype behavior of `fit_gp`.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import fast_bo
from repro.core.acquisition import expected_improvement
from repro.core.fast_bo import (
    _masked_posterior,
    bo_step,
    bo_step_core,
    encode_features,
)
from repro.core.gp import (
    GPParams,
    fit_gp,
    gp_predict,
    matern52,
    matern52_from_sqdist,
    pairwise_sqdist,
)

_JITTER = 1e-8
LAYOUTS = ("feature", "fused")


def random_case(seed, n=18, d=3, n_obs=6):
    # n_obs is fixed so the reference `fit_gp` compiles once across seeds.
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, d)).astype(np.float32)
    obs_idx = rng.choice(n, size=n_obs, replace=False)
    obs_mask = np.zeros(n, bool)
    obs_mask[obs_idx] = True
    # A smooth-ish cost surface with noise.
    y = (np.sum(x**2, -1) + 0.3 * rng.normal(size=n)).astype(np.float32)
    return x, obs_mask, y


def reference_posterior(x, obs_mask, y_n, lengthscale, noise):
    """Readable dense-GP math on the observed subset only (float32)."""
    x = jnp.asarray(x, jnp.float32)
    obs = np.flatnonzero(obs_mask)
    params = GPParams(
        lengthscale=jnp.asarray(lengthscale, jnp.float32),
        amplitude=jnp.asarray(1.0, jnp.float32),
        noise=jnp.asarray(noise, jnp.float32),
    )
    x_obs = x[obs]
    k = matern52(x_obs, x_obs, params) + (noise + _JITTER) * jnp.eye(len(obs))
    chol = jnp.linalg.cholesky(k)
    alpha = jax.scipy.linalg.cho_solve((chol, True), y_n[obs])
    lml = (
        -0.5 * y_n[obs] @ alpha
        - jnp.sum(jnp.log(jnp.diagonal(chol)))
        - 0.5 * len(obs) * jnp.log(2.0 * jnp.pi)
    )
    k_star = matern52(x_obs, x, params)
    mean = k_star.T @ alpha
    v = jax.scipy.linalg.solve_triangular(chol, k_star, lower=True)
    var = jnp.maximum(1.0 - jnp.sum(v * v, axis=0), 1e-12)
    return np.asarray(lml), np.asarray(mean), np.asarray(var)


class TestMaskedPosterior:
    @pytest.mark.parametrize("seed", range(8))
    def test_matches_reference_on_random_masks(self, seed):
        x, obs_mask, y = random_case(seed)
        m = obs_mask.astype(np.float32)
        y_mean = (y * m).sum() / m.sum()
        y_std = max(float(np.sqrt((m * (y - y_mean) ** 2).sum() / m.sum())), 1e-8)
        y_n = np.where(obs_mask, (y - y_mean) / y_std, 0.0).astype(np.float32)

        for ls, nz in [(0.5, 1e-2), (1.0, 1e-4), (2.0, 1e-1)]:
            lml, mean, var = jax.jit(_masked_posterior)(
                jnp.asarray(x), jnp.asarray(obs_mask), jnp.asarray(y_n),
                jnp.asarray(ls, jnp.float32), jnp.asarray(nz, jnp.float32),
            )
            ref_lml, ref_mean, ref_var = reference_posterior(x, obs_mask, y_n, ls, nz)
            assert np.asarray(lml) == pytest.approx(ref_lml, rel=1e-3, abs=1e-3)
            np.testing.assert_allclose(np.asarray(mean), ref_mean, rtol=2e-3, atol=2e-3)
            np.testing.assert_allclose(np.asarray(var), ref_var, rtol=2e-3, atol=2e-3)

    @pytest.mark.parametrize("seed", range(5))
    def test_padded_points_contribute_nothing(self, seed):
        """Appending garbage rows outside the obs mask must leave the
        posterior over the real points unchanged (padding is exact)."""
        x, obs_mask, y = random_case(seed, n=14)
        rng = np.random.default_rng(1000 + seed)
        n_pad = 7
        x_pad = np.concatenate(
            [x, 100.0 * rng.normal(size=(n_pad, x.shape[1])).astype(np.float32)]
        )
        obs_pad = np.concatenate([obs_mask, np.zeros(n_pad, bool)])

        m = obs_mask.astype(np.float32)
        y_mean = (y * m).sum() / m.sum()
        y_std = max(float(np.sqrt((m * (y - y_mean) ** 2).sum() / m.sum())), 1e-8)
        y_n = np.where(obs_mask, (y - y_mean) / y_std, 0.0).astype(np.float32)
        y_n_pad = np.concatenate([y_n, np.zeros(n_pad, np.float32)])

        lml, mean, var = jax.jit(_masked_posterior)(
            jnp.asarray(x), jnp.asarray(obs_mask), jnp.asarray(y_n),
            jnp.asarray(1.0, jnp.float32), jnp.asarray(1e-2, jnp.float32),
        )
        lml_p, mean_p, var_p = jax.jit(_masked_posterior)(
            jnp.asarray(x_pad), jnp.asarray(obs_pad), jnp.asarray(y_n_pad),
            jnp.asarray(1.0, jnp.float32), jnp.asarray(1e-2, jnp.float32),
        )
        assert np.asarray(lml_p) == pytest.approx(float(lml), rel=1e-4, abs=1e-4)
        np.testing.assert_allclose(
            np.asarray(mean_p)[: len(x)], np.asarray(mean), rtol=1e-4, atol=1e-4
        )
        np.testing.assert_allclose(
            np.asarray(var_p)[: len(x)], np.asarray(var), rtol=1e-4, atol=1e-4
        )


class TestBoStepAgainstReference:
    @pytest.mark.parametrize("layout", LAYOUTS)
    @pytest.mark.parametrize("seed", range(6))
    def test_pick_is_ei_optimal_under_reference(self, seed, layout):
        """`bo_step`'s pick must (near-)maximize the EI computed by the
        readable fit_gp → gp_predict → expected_improvement pipeline."""
        x, obs_mask, y = random_case(seed, n=16)
        cand = ~obs_mask
        pick, max_ei, best = bo_step(
            jnp.asarray(x), jnp.asarray(obs_mask), jnp.asarray(y),
            jnp.asarray(cand), layout=layout,
        )
        pick = int(pick)
        assert cand[pick]
        obs_idx = np.flatnonzero(obs_mask)
        assert float(best) == pytest.approx(float(y[obs_idx].min()))

        post = fit_gp(jnp.asarray(x[obs_idx]), jnp.asarray(y[obs_idx]))
        mean, std = gp_predict(post, jnp.asarray(x))
        ref_ei = np.array(
            expected_improvement(mean, std, jnp.asarray(y[obs_idx].min()))
        )
        ref_ei[~cand] = -np.inf
        # Floating tie-breaks may differ between the two programs; the pick
        # must carry (numerically) maximal reference EI either way.
        gap = ref_ei.max() - ref_ei[pick]
        assert gap <= 1e-5 * max(1.0, abs(float(ref_ei.max())))

    @pytest.mark.parametrize("layout", LAYOUTS)
    def test_max_ei_reported_consistently(self, layout):
        x, obs_mask, y = random_case(42, n=16)
        cand = ~obs_mask
        pick, max_ei, _ = bo_step(
            jnp.asarray(x), jnp.asarray(obs_mask), jnp.asarray(y),
            jnp.asarray(cand), layout=layout,
        )
        assert float(max_ei) >= 0.0
        # The returned max EI is attained at the returned pick.
        obs_idx = np.flatnonzero(obs_mask)
        post = fit_gp(jnp.asarray(x[obs_idx]), jnp.asarray(y[obs_idx]))
        mean, std = gp_predict(post, jnp.asarray(x))
        ref_ei = np.asarray(
            expected_improvement(mean, std, jnp.asarray(y[obs_idx].min()))
        )
        assert float(max_ei) == pytest.approx(float(ref_ei[int(pick)]), rel=5e-2, abs=1e-5)


def _reference_ei(x, obs_mask, y, cand):
    """EI over all points via the readable fit_gp → gp_predict pipeline."""
    obs_idx = np.flatnonzero(obs_mask)
    post = fit_gp(jnp.asarray(x[obs_idx]), jnp.asarray(y[obs_idx]))
    mean, std = gp_predict(post, jnp.asarray(x))
    ei = np.array(expected_improvement(mean, std, jnp.asarray(y[obs_idx].min())))
    ei[~cand] = -np.inf
    return ei


def _assert_pick_near_optimal(ei_ref, pick, tol=1e-5):
    gap = ei_ref.max() - ei_ref[pick]
    assert gap <= tol * max(1.0, abs(float(ei_ref.max())))


class TestPackedEngine:
    """The packed (B,B)/(B,n) layout: gp.py-reference agreement on random
    observed subsets, exact (bitwise-inert) slot padding, and the
    full-buffer / B=1 edge cases."""

    def _packed_inputs(self, x, obs_mask, y, capacity):
        order = np.flatnonzero(obs_mask)
        k = len(order)
        tried = np.full(capacity, -1, np.int32)
        tried[:k] = order
        py = np.zeros(capacity, np.float32)
        py[:k] = y[order]
        return tried, py, k

    @pytest.mark.parametrize("seed", range(4))
    def test_padded_slots_are_bitwise_inert(self, seed):
        """Finite garbage in packed slots ≥ t must not change a single bit
        of (pick, max_ei, best) — the padding is exact, not approximate.
        """
        x, obs_mask, y = random_case(seed)
        cand = ~obs_mask
        capacity = 12
        tried, py, k = self._packed_inputs(x, obs_mask, y, capacity)
        enc = encode_features(x)
        feats = np.zeros((capacity, enc.shape[1]), np.float32)
        feats[:k] = enc[tried[:k]]
        core_f = jax.jit(bo_step_core)
        args_tail = (jnp.asarray(k, jnp.int32), jnp.asarray(obs_mask),
                     jnp.asarray(cand))

        ref = core_f(jnp.asarray(enc), jnp.asarray(feats),
                     jnp.asarray(tried), jnp.asarray(py), *args_tail)
        rng = np.random.default_rng(100 + seed)
        tried_g = tried.copy()
        py_g = py.copy()
        feats_g = feats.copy()
        tried_g[k:] = rng.integers(0, len(x), size=capacity - k)
        py_g[k:] = 1e6 * rng.standard_normal(capacity - k)
        feats_g[k:] = 1e6 * rng.standard_normal((capacity - k, enc.shape[1]))
        got = core_f(jnp.asarray(enc), jnp.asarray(feats_g),
                     jnp.asarray(tried_g), jnp.asarray(py_g), *args_tail)
        assert int(got[0]) == int(ref[0])
        assert float(got[1]) == float(ref[1])  # bitwise, no tolerance
        assert float(got[2]) == float(ref[2])

    @pytest.mark.parametrize("layout", LAYOUTS)
    @pytest.mark.parametrize("seed", range(4))
    def test_full_buffer_matches_reference(self, seed, layout):
        """capacity == n_obs (no padded slots at all) against the readable
        reference pipeline."""
        x, obs_mask, y = random_case(seed, n=16)
        cand = ~obs_mask
        n_obs = int(obs_mask.sum())
        pick, max_ei, best = bo_step(x, obs_mask, y, cand, capacity=n_obs,
                                     layout=layout)
        assert cand[pick]
        assert best == pytest.approx(float(y[obs_mask].min()))
        _assert_pick_near_optimal(_reference_ei(x, obs_mask, y, cand), pick)

    @pytest.mark.parametrize("layout", LAYOUTS)
    @pytest.mark.parametrize("seed", range(4))
    def test_oversized_buffer_matches_reference(self, seed, layout):
        """capacity > n_obs (the mid-search shape) against the reference."""
        x, obs_mask, y = random_case(seed, n=16)
        cand = ~obs_mask
        pick, max_ei, best = bo_step(x, obs_mask, y, cand, capacity=14,
                                     layout=layout)
        assert cand[pick]
        _assert_pick_near_optimal(_reference_ei(x, obs_mask, y, cand), pick)

    def test_single_observation_capacity_one(self):
        """B = 1: a (1,1) system, the smallest the packed engine can run."""
        x, _, y = random_case(5, n=12)
        obs_mask = np.zeros(12, bool)
        obs_mask[4] = True
        cand = ~obs_mask
        pick, max_ei, best = bo_step(x, obs_mask, y, cand, capacity=1)
        assert cand[pick]
        assert best == pytest.approx(float(y[4]))
        assert max_ei >= 0.0

    @pytest.mark.parametrize("seed", range(3))
    def test_trial_order_is_immaterial_to_the_pick_quality(self, seed):
        """The packed buffer is ordered by trial; any order must yield a
        (near-)EI-optimal pick and the identical best cost."""
        x, obs_mask, y = random_case(seed, n=16)
        cand = ~obs_mask
        order = np.flatnonzero(obs_mask)
        shuffled = np.random.default_rng(seed).permutation(order)
        ei_ref = _reference_ei(x, obs_mask, y, cand)
        p1, e1, b1 = bo_step(x, obs_mask, y, cand, trial_order=order)
        p2, e2, b2 = bo_step(x, obs_mask, y, cand, trial_order=shuffled)
        assert b1 == b2  # min is order-independent even in float32
        assert e2 == pytest.approx(e1, rel=1e-3, abs=1e-6)
        _assert_pick_near_optimal(ei_ref, p1)
        _assert_pick_near_optimal(ei_ref, p2)

    @pytest.mark.parametrize("seed", range(4))
    def test_packed_agrees_with_reference_pipeline(self, seed):
        """The packed step against the readable fit_gp → gp_predict →
        expected_improvement pipeline on the same state: the same best,
        the max EI equal to the reference EI at the pick, and a
        near-optimal pick."""
        x, obs_mask, y = random_case(seed, n=16)
        cand = ~obs_mask
        pick, max_ei, best = bo_step(x, obs_mask, y, cand)
        assert best == pytest.approx(float(y[obs_mask].min()))
        ei_ref = _reference_ei(x, obs_mask, y, cand)
        assert max_ei == pytest.approx(float(ei_ref[pick]), rel=5e-2, abs=1e-5)
        _assert_pick_near_optimal(ei_ref, pick)


# `bench/reference.py`'s tie: grid points whose log marginal likelihoods
# lie within this many nats may be picked either way.
LML_TIE = 1e-4


@jax.jit
def _both_heads(d2_bb, py, t):
    """The head through XLA's factorization and through the TPU's column
    loop, with each factorization's own outputs (the LML grid among them)."""
    got = {}

    def keep(name, factor):
        def run(*args):
            got[name] = factor(*args)
            return got[name]

        return run

    got["lapack_head"] = fast_bo._packed_head(
        d2_bb, py, t, factor=keep("lapack", fast_bo._factor_lapack)
    )
    got["loop_head"] = fast_bo._packed_head(
        d2_bb, py, t, factor=keep("loop", fast_bo._factor_loop)
    )
    return got


def _head_case(seed, b, t):
    rng = np.random.default_rng(seed)
    feats = rng.normal(size=(b, 4)).astype(np.float32)
    py = (np.sum(feats**2, -1) + 0.3 * rng.normal(size=b)).astype(np.float32)
    return pairwise_sqdist(jnp.asarray(feats)), jnp.asarray(py)


def _assert_loop_matches_lapack(got, t):
    b = got["loop_head"][3].shape[-1]
    lml_lapack, h_lapack = np.asarray(got["lapack"][0]), int(got["lapack"][1])
    lml_loop, h_loop = np.asarray(got["loop"][0]), int(got["loop"][1])
    np.testing.assert_allclose(lml_loop, lml_lapack, rtol=1e-3, atol=1e-3)
    # The same grid point, unless the two are a near-tie.
    assert h_loop == h_lapack or (
        lml_lapack[h_lapack] - lml_lapack[h_loop] <= LML_TIE
    )
    if h_loop != h_lapack:
        return
    chol_lapack, alpha_lapack = got["lapack_head"][3:5]
    chol, alpha = got["loop_head"][3:5]
    np.testing.assert_allclose(chol, chol_lapack, rtol=0, atol=1e-5)
    scale = 1.0 + float(jnp.max(jnp.abs(alpha_lapack)))
    np.testing.assert_allclose(alpha, alpha_lapack, rtol=0, atol=1e-4 * scale)
    # Columns past the observed slots are exactly the identity's.
    np.testing.assert_array_equal(np.asarray(chol)[:, t:], np.eye(b)[:, t:])
    # pm, best, the selected lengthscale, y_mean and y_std: bit for bit.
    for k in (0, 1, 2, 5, 6):
        np.testing.assert_array_equal(got["loop_head"][k],
                                      got["lapack_head"][k])


class TestLoopHead:
    """The TPU's GP head (`fast_bo._factor_loop`: a column loop over the t
    observed slots) run on the CPU against XLA's full-extent factorization
    (`_factor_lapack`, the CPU's head)."""

    @pytest.mark.parametrize("b", [8, 24, 69])
    @pytest.mark.parametrize("fill", ["none", "random", "full"])
    def test_matches_lapack_head(self, b, fill):
        for seed in range(4):
            rng = np.random.default_rng(1000 * b + seed)
            t = {"none": 0, "full": b}.get(fill, int(rng.integers(1, b)))
            d2, py = _head_case(seed, b, t)
            got = _both_heads(d2, py, jnp.asarray(t, jnp.int32))
            _assert_loop_matches_lapack(got, t)

    @pytest.mark.parametrize("b", [8, 24, 69])
    def test_batch_of_rows_with_different_t(self, b):
        """Under the chunk's vmap the loop runs to the largest t of the
        batch; every row still matches its own full-extent factorization."""
        rows = 6
        ts = np.random.default_rng(b).integers(0, b + 1, size=rows)
        ts[:2] = (0, b)
        cases = [_head_case(100 + i, b, int(ts[i])) for i in range(rows)]
        d2 = jnp.stack([c[0] for c in cases])
        py = jnp.stack([c[1] for c in cases])
        got = jax.vmap(_both_heads)(d2, py, jnp.asarray(ts, jnp.int32))
        for i, t in enumerate(ts):
            row = jax.tree_util.tree_map(lambda x, i=i: x[i], got)
            _assert_loop_matches_lapack(row, int(t))

    @pytest.mark.parametrize("b", [8, 24, 69])
    def test_padded_slots_are_bitwise_inert(self, b):
        """Finite garbage in the distances and costs of slots ≥ t changes
        no bit of the loop head's outputs."""
        loop_head = jax.jit(
            lambda d2, py, t: fast_bo._packed_head(
                d2, py, t, factor=fast_bo._factor_loop
            )
        )
        rng = np.random.default_rng(7 * b)
        for t in (0, 1, b // 2, b - 1):
            d2, py = _head_case(b + t, b, t)
            d2_g = np.array(d2)
            py_g = np.array(py)
            junk = np.abs(1e3 * rng.standard_normal((b, b))).astype(np.float32)
            d2_g[t:, :] = junk[t:, :]
            d2_g[:, t:] = junk[:, t:]
            py_g[t:] = 1e6 * rng.standard_normal(b - t)
            tt = jnp.asarray(t, jnp.int32)
            ref = loop_head(d2, py, tt)
            got = loop_head(jnp.asarray(d2_g), jnp.asarray(py_g), tt)
            for r, g in zip(ref, got):
                np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


class TestSqdistKernelHelpers:
    def test_matern_from_sqdist_matches_matern52_scalar_ls(self):
        """One raw d² rescaled per lengthscale must reproduce matern52 for
        every scalar lengthscale of the hyperparameter grid."""
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(9, 3)), jnp.float32)
        d2 = pairwise_sqdist(x)
        for ls in (0.1, 0.25, 0.5, 1.0, 2.0, 4.0):
            params = GPParams(
                lengthscale=jnp.asarray(ls, jnp.float32),
                amplitude=jnp.asarray(1.0, jnp.float32),
                noise=jnp.asarray(0.0, jnp.float32),
            )
            ref = np.asarray(matern52(x, x, params))
            got = np.asarray(matern52_from_sqdist(d2, jnp.asarray(ls, jnp.float32)))
            # Small lengthscales put far pairs deep into the exponential
            # tail, where the two float32 evaluation orders diverge
            # relatively (but not absolutely) — hence the atol floor.
            np.testing.assert_allclose(got, ref, rtol=2e-3, atol=1e-6)

    def test_pairwise_sqdist_nonnegative_and_symmetric(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.normal(size=(7, 4)), jnp.float32)
        d2 = np.asarray(pairwise_sqdist(x))
        assert (d2 >= 0.0).all()
        np.testing.assert_allclose(d2, d2.T, rtol=0, atol=0)
        ref = ((np.asarray(x)[:, None] - np.asarray(x)[None]) ** 2).sum(-1)
        np.testing.assert_allclose(d2, ref, rtol=1e-4, atol=1e-5)


class TestFitGpDtype:
    def test_respects_default_float32(self):
        """`fit_gp` must follow the runtime's canonical float width instead
        of poking at jax.config internals (fragile across JAX versions)."""
        x = jnp.asarray(np.random.default_rng(0).normal(size=(6, 2)))
        y = jnp.asarray(np.arange(6.0))
        post = fit_gp(x, y)
        expected = jax.dtypes.canonicalize_dtype(jnp.float64)
        assert post.x_train.dtype == expected
        assert post.chol.dtype == expected
        mean, std = gp_predict(post, x)
        assert mean.dtype == expected
        # And the posterior interpolates the training targets reasonably.
        np.testing.assert_allclose(np.asarray(mean), np.arange(6.0), atol=0.3)
