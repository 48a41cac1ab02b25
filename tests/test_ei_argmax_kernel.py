"""Kernel-identity lane: the fused posterior+EI+argmax kernel vs the
unfused reference, bit for bit.

`repro.kernels.ei_argmax` streams the candidate axis in tiles with a
running (max EI, argmax) reduction so the (B,n) cross block never
materializes.  The claim these tests pin is IDENTITY, not closeness:
every byte of (pick, max_ei, best) from `bo_step_core_fused` — on the
production `lax.scan` lane AND under the Pallas interpreter — must equal
`bo_step_core`'s, across tile widths, buffer fill levels, manufactured
EI ties that span tile boundaries, garbage in padded packed slots, and
the d=1 / B=2 shape edges.  The final class proves the structural point
by inspection: the fused jaxpr contains no (B,n)-sized intermediate and
XLA's compiled-memory report shows the transient footprint collapsing,
while the reference lane demonstrably has both.

Everything here runs on the CPU test topology (interpret mode executes
the kernel body as ordinary XLA:CPU ops); the compiled-TPU lane shares
the same body with Mosaic-lowerable tail ops, which `TestMosaicTailOps`
holds to the XLA ops within float32 tolerance, and is covered by the same
calls when a TPU backend is present.
"""

import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from hypothesis_compat import HAVE_HYPOTHESIS, given, settings, st
from repro.core.fast_bo import bo_step_core, bo_step_core_fused
from repro.core.gp import matern52_from_sqdist, pairwise_sqdist
from repro.kernels.ei_argmax import ei_argmax
from repro.kernels.ei_argmax.kernel import MOSAIC_TAIL_OPS, _sqdist_t
from repro.kernels.ei_argmax.ops import _pick_tile
from repro.kernels.ei_argmax.tile import XLA_TAIL_OPS

pytestmark = pytest.mark.kernel

_REF = jax.jit(bo_step_core)
_FUSED = jax.jit(
    bo_step_core_fused, static_argnames=("tile", "interpret")
)


def _case(seed, n, d, k, capacity):
    """A packed BO-step instance: k observed points in a capacity-B buffer
    over an (n,d) standard-normal encoding with a smooth noisy cost."""
    rng = np.random.default_rng(seed)
    enc = rng.normal(size=(n, d)).astype(np.float32)
    y = (np.sum(enc**2, -1) + 0.3 * rng.normal(size=n)).astype(np.float32)
    picks = rng.choice(n, size=k, replace=False)
    tried = np.full(capacity, -1, np.int32)
    tried[:k] = picks
    py = np.zeros(capacity, np.float32)
    py[:k] = y[picks]
    obs = np.zeros(n, bool)
    obs[picks] = True
    cand = np.ones(n, bool)
    enc = jnp.asarray(enc)
    feats = enc[jnp.maximum(jnp.asarray(tried), 0)]
    return (
        enc, feats, jnp.asarray(tried), jnp.asarray(py),
        jnp.asarray(k, jnp.int32), jnp.asarray(obs), jnp.asarray(cand),
    )


def _assert_bitwise(ref, got, ctx=""):
    for name, a, b in zip(("pick", "max_ei", "best"), ref, got):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype, f"{ctx}{name}: dtype {a.dtype} != {b.dtype}"
        assert np.array_equal(a, b), f"{ctx}{name}: {a!r} != {b!r}"


class TestFusedIdentity:
    """fused == reference, byte for byte, across shapes and fill levels."""

    @pytest.mark.parametrize(
        "n,d,capacity",
        [(69, 5, 24), (256, 3, 16), (600, 7, 24), (1500, 4, 12)],
    )
    def test_seeded_sweep_bitwise(self, n, d, capacity):
        """The always-on lane: several fills per shape, scan + interpret."""
        for seed, k in ((0, 1), (1, capacity // 2), (2, capacity)):
            args = _case(seed, n, d, k, capacity)
            ref = _REF(*args)
            _assert_bitwise(ref, _FUSED(*args), f"n={n} k={k} scan: ")
            _assert_bitwise(
                ref, _FUSED(*args, interpret=True), f"n={n} k={k} interp: "
            )

    @pytest.mark.parametrize("tile", [128, 256, 512, 1024])
    def test_tile_size_invariance(self, tile):
        """The tile width is a pure performance knob: every width yields the
        reference bits, on the scan lane and under the interpreter — n=1500
        pads to a tile multiple at every width here."""
        args = _case(3, 1500, 3, 10, 16)
        ref = _REF(*args)
        _assert_bitwise(ref, _FUSED(*args, tile=tile), f"tile={tile} scan: ")
        _assert_bitwise(
            ref, _FUSED(*args, tile=tile, interpret=True),
            f"tile={tile} interp: ",
        )

    def test_padded_slots_bitwise_inert(self):
        """Finite garbage in packed slots ≥ t (features, indices, costs)
        must not change a single output bit — same exactness contract the
        unfused packed engine pins in test_core_bo.py."""
        enc, feats, tried, py, t, obs, cand = _case(4, 400, 4, 7, 20)
        k, capacity = 7, 20
        ref = _FUSED(enc, feats, tried, py, t, obs, cand)
        rng = np.random.default_rng(99)
        tried_g = np.asarray(tried).copy()
        py_g = np.asarray(py).copy()
        feats_g = np.asarray(feats).copy()
        tried_g[k:] = rng.integers(0, 400, size=capacity - k)
        py_g[k:] = 1e6 * rng.standard_normal(capacity - k)
        feats_g[k:] = 1e6 * rng.standard_normal((capacity - k, 4))
        for interpret in (None, True):
            got = _FUSED(
                enc, jnp.asarray(feats_g), jnp.asarray(tried_g),
                jnp.asarray(py_g), t, obs, cand, interpret=interpret,
            )
            _assert_bitwise(ref, got, f"garbage interpret={interpret}: ")

    def test_cross_tile_tie_takes_lowest_index(self):
        """Manufactured exact EI ties: duplicate encoding rows produce
        bitwise-equal EI columns, and when the duplicates sit in DIFFERENT
        tiles the strict-`>` streaming update must keep the first index —
        `jnp.argmax`'s contract in the reference."""
        n, d, k, capacity, tile = 1024, 3, 6, 12, 256
        enc, feats, tried, py, t, obs, cand = _case(5, n, d, k, capacity)
        enc = np.asarray(enc).copy()
        obs_np = np.asarray(obs)
        # A clone of candidate j1 placed three tiles later (both unobserved).
        j1, j2 = 40, 40 + 3 * tile
        assert not obs_np[j1] and not obs_np[j2]
        enc[j2] = enc[j1]
        enc = jnp.asarray(enc)
        feats = enc[jnp.maximum(tried, 0)]
        ref = _REF(enc, feats, tried, py, t, obs, cand)
        for kwargs in ({}, {"interpret": True}):
            got = _FUSED(enc, feats, tried, py, t, obs, cand,
                         tile=tile, **kwargs)
            _assert_bitwise(ref, got, f"tie {kwargs}: ")
        # If the winner IS the duplicated point, the tie-break was real:
        # the fused pick must be j1, never the equal-EI j2.
        if int(ref[0]) in (j1, j2):
            assert int(ref[0]) == j1

    def test_d1_delegates_to_reference(self):
        """d=1 degenerate matmuls fuse differently under XLA:CPU, so the
        fused entry point delegates wholesale — identical program,
        identical bits (and `quad_space`-based golden scenarios stay d=1)."""
        args = _case(6, 200, 1, 5, 12)
        _assert_bitwise(_REF(*args), _FUSED(*args), "d=1: ")

    def test_b2_and_d2_edges(self):
        """Smallest engine extents: B=2 buffers (the float32-discipline
        floor) and d=2 (the narrowest non-delegating width)."""
        for seed, (n, d, k, cap) in enumerate([(50, 2, 2, 2), (300, 2, 1, 2),
                                               (130, 6, 2, 2)]):
            args = _case(20 + seed, n, d, k, cap)
            ref = _REF(*args)
            _assert_bitwise(ref, _FUSED(*args), f"edge {n},{d},{cap} scan: ")
            _assert_bitwise(ref, _FUSED(*args, interpret=True),
                            f"edge {n},{d},{cap} interp: ")

    def test_all_masked_pool(self):
        """Every candidate observed or excluded: both lanes reduce over all
        -inf and must agree on (index 0, -inf) exactly."""
        enc, feats, tried, py, t, obs, cand = _case(7, 128, 3, 8, 16)
        none = jnp.zeros_like(cand)
        ref = _REF(enc, feats, tried, py, t, obs, none)
        for kwargs in ({}, {"interpret": True}):
            got = _FUSED(enc, feats, tried, py, t, obs, none, **kwargs)
            _assert_bitwise(ref, got, f"all-masked {kwargs}: ")
        assert int(ref[0]) == 0 and np.isneginf(float(ref[1]))

    @pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
    @settings(max_examples=15, deadline=None)
    @given(
        seed=st.integers(0, 2**16),
        n=st.integers(2, 300),
        d=st.integers(1, 5),
        cap=st.integers(2, 16),
    )
    def test_property_fused_equals_reference(self, seed, n, d, cap):
        """Property lane (dev-only, skipped without hypothesis): random
        shapes and fills, fused == reference bitwise — d=1 draws exercise
        the delegation path."""
        k = 1 + seed % min(n, cap)
        args = _case(seed, n, d, k, cap)
        _assert_bitwise(_REF(*args), _FUSED(*args),
                        f"prop n={n} d={d} cap={cap} k={k}: ")


def _intermediate_sizes(jaxpr):
    """Element counts of every equation output across all nested jaxprs."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)  # accept ClosedJaxpr too
    sizes = []
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            aval = getattr(var, "aval", None)
            if aval is not None and hasattr(aval, "shape"):
                sizes.append(int(np.prod(aval.shape, dtype=np.int64)))
        for val in eqn.params.values():
            for sub in jax.core.jaxprs_in_params({"_": val}):
                sizes.extend(_intermediate_sizes(sub))
    return sizes


class TestNoCrossBlock:
    """The structural claim: the fused step never builds the (B,n) block."""

    N, B, D = 32768, 16, 6

    def _args(self):
        return _case(11, self.N, self.D, self.B // 2, self.B)

    def test_jaxpr_has_no_bn_intermediate(self):
        """No intermediate in the fused jaxpr reaches even half of B·n
        elements, while the reference lane provably materializes a full
        (B,n) — the guard fails loudly if a refactor reintroduces it."""
        args = self._args()
        threshold = self.B * self.N // 2
        fused_sizes = _intermediate_sizes(
            jax.make_jaxpr(bo_step_core_fused)(*args).jaxpr
        )
        assert fused_sizes and max(fused_sizes) < threshold, (
            f"fused lane materializes {max(fused_sizes)} elements "
            f"(threshold {threshold})"
        )
        ref_sizes = _intermediate_sizes(
            jax.make_jaxpr(bo_step_core)(*args).jaxpr
        )
        assert max(ref_sizes) >= self.B * self.N, (
            "positive control broke: reference lane no longer has a (B,n) "
            "intermediate — the guard above is not testing anything"
        )

    def test_compiled_transient_memory_collapses(self):
        """XLA's own compiled-memory report: the fused step's transient
        footprint is at least 8x below the reference at n=32768 (the
        measured gap is ~32x; 8x leaves slack for backend layout churn)."""
        args = self._args()
        def temp_bytes(fn):
            stats = jax.jit(fn).lower(*args).compile().memory_analysis()
            return int(stats.temp_size_in_bytes)
        ref, fused = temp_bytes(bo_step_core), temp_bytes(bo_step_core_fused)
        assert fused * 8 <= ref, (
            f"fused transients {fused}B vs reference {ref}B — "
            f"expected >=8x reduction"
        )


class TestMosaicTailOps:
    """The compiled-TPU kernel's stand-ins (`MOSAIC_TAIL_OPS`, the
    difference-form distance block) against the XLA ops they replace, run
    here as ordinary XLA:CPU ops: interpret mode never executes them, and
    the TPU compile tests only show that they lower."""

    B, M, D = 24, 512, 6

    def _gp(self):
        rng = np.random.default_rng(5)
        feats = rng.normal(size=(self.B, self.D)).astype(np.float32)
        enc = rng.normal(size=(self.M, self.D)).astype(np.float32)
        d2 = np.asarray(pairwise_sqdist(jnp.asarray(feats), jnp.asarray(feats)))
        k = np.asarray(matern52_from_sqdist(jnp.asarray(d2), 2.0))
        chol = np.linalg.cholesky(k + 1e-2 * np.eye(self.B)).astype(np.float32)
        k_star = np.asarray(matern52_from_sqdist(
            pairwise_sqdist(jnp.asarray(feats), jnp.asarray(enc)), 2.0))
        return feats, enc, chol, k_star

    @pytest.mark.parametrize("op", ["cdf", "sqnorm", "mean", "sqdist"])
    def test_matches_xla_op(self, op):
        feats, enc, chol, k_star = self._gp()
        if op == "cdf":
            z = np.linspace(-9.0, 6.0, 4001).astype(np.float32)
            want = np.asarray([0.5 * math.erfc(-float(v) / math.sqrt(2.0))
                               for v in z])
            got = np.asarray(MOSAIC_TAIL_OPS.cdf(jnp.asarray(z)), np.float64)
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)
            return
        if op == "sqnorm":
            # The upper triangle is never read: garbage there is inert.
            garbage = chol + np.triu(np.full_like(chol, np.nan), 1)
            got = MOSAIC_TAIL_OPS.sqnorm(jnp.asarray(garbage),
                                         jnp.asarray(k_star))
            want = XLA_TAIL_OPS.sqnorm(jnp.asarray(chol), jnp.asarray(k_star))
            np.testing.assert_allclose(np.asarray(got)[0], want, rtol=1e-5)
            return
        if op == "mean":
            alpha = np.random.default_rng(6).normal(size=self.B).astype(
                np.float32)
            got = MOSAIC_TAIL_OPS.mean(jnp.asarray(k_star),
                                       jnp.asarray(alpha[:, None]))
            want = XLA_TAIL_OPS.mean(jnp.asarray(k_star), jnp.asarray(alpha))
            np.testing.assert_allclose(np.asarray(got)[0], want, rtol=1e-5,
                                       atol=1e-6)
            return
        got = _sqdist_t(jnp.asarray(feats), jnp.asarray(enc.T))
        want = pairwise_sqdist(jnp.asarray(feats), jnp.asarray(enc))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
