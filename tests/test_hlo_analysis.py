"""HLO cost analyzer: ground-truth flop counting with loop scaling, and
collective-byte accounting on explicitly-collective programs."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow  # excluded from the tier-1 fast lane

from repro.launch.hlo_analysis import analyze_hlo


class TestFlops:
    def test_plain_matmul(self):
        m, k, n = 64, 128, 32
        f = jax.jit(lambda a, b: a @ b)
        c = f.lower(
            jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((k, n), jnp.float32),
        ).compile()
        cost = analyze_hlo(c.as_text())
        assert cost.flops == pytest.approx(2 * m * k * n, rel=0.01)

    def test_scan_multiplies_by_trip_count(self):
        def f(x):
            def body(c, _):
                return jnp.tanh(c @ c), None

            y, _ = jax.lax.scan(body, x, None, length=17)
            return y

        c = jax.jit(f).lower(jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
        cost = analyze_hlo(c.as_text())
        assert cost.flops == pytest.approx(17 * 2 * 64**3, rel=0.01)

    def test_nested_scan(self):
        def f(x):
            def outer(c, _):
                def inner(ci, _):
                    return ci @ ci, None

                ci, _ = jax.lax.scan(inner, c, None, length=3)
                return ci, None

            y, _ = jax.lax.scan(outer, x, None, length=5)
            return y

        c = jax.jit(f).lower(jax.ShapeDtypeStruct((32, 32), jnp.float32)).compile()
        cost = analyze_hlo(c.as_text())
        assert cost.flops == pytest.approx(15 * 2 * 32**3, rel=0.02)

    def test_batched_dot(self):
        f = jax.jit(lambda a, b: jnp.einsum("bij,bjk->bik", a, b))
        c = f.lower(
            jax.ShapeDtypeStruct((4, 16, 32), jnp.float32),
            jax.ShapeDtypeStruct((4, 32, 8), jnp.float32),
        ).compile()
        cost = analyze_hlo(c.as_text())
        assert cost.flops == pytest.approx(2 * 4 * 16 * 32 * 8, rel=0.01)


class TestCollectives:
    def test_psum_bytes_counted(self, devices_runner):
        devices_runner(
            """
            import jax, jax.numpy as jnp
            from functools import partial
            from jax.sharding import PartitionSpec as P
            from repro.launch.hlo_analysis import analyze_hlo

            mesh = jax.make_mesh((8,), ("x",))

            @partial(jax.shard_map, mesh=mesh, in_specs=P("x"), out_specs=P())
            def f(v):
                return jax.lax.psum(v, "x")

            c = jax.jit(f).lower(
                jax.ShapeDtypeStruct((8, 1024), jnp.float32)).compile()
            cost = analyze_hlo(c.as_text())
            # all-reduce of the per-device (1, 1024) f32 → ≥ 4 KiB counted
            assert cost.collective_bytes >= 1024 * 4, cost.collective_bytes
            assert "all-reduce" in cost.collective_breakdown
            print("PSUM OK", cost.collective_bytes)
            """
        )

    def test_collectives_inside_scan_are_loop_scaled(self, devices_runner):
        devices_runner(
            """
            import jax, jax.numpy as jnp
            from functools import partial
            # The psum result is carried directly; the varying-manual-axes
            # checker would reject the replicated carry, so it is off (the
            # HLO under test is identical).
            from jax.sharding import PartitionSpec as P
            from repro.launch.hlo_analysis import analyze_hlo

            mesh = jax.make_mesh((8,), ("x",))

            @partial(jax.shard_map, mesh=mesh, in_specs=P("x"),
                     out_specs=P(), check_vma=False)
            def step(v):
                def body(c, _):
                    y = jax.lax.psum(c, "x") * (1.0 / 8.0)
                    return y, None
                y, _ = jax.lax.scan(body, v.sum(0), None, length=10)
                return jax.lax.psum(y, "x") * (1.0 / 8.0)

            c = jax.jit(step).lower(
                jax.ShapeDtypeStruct((8, 256), jnp.float32)).compile()
            cost = analyze_hlo(c.as_text())
            one = 256 * 4
            assert cost.collective_bytes >= 9 * one, cost.collective_bytes
            print("LOOPED PSUM OK", cost.collective_bytes)
            """
        )


class TestTrafficModel:
    def test_hbm_bytes_scale_with_tensor_size(self):
        small = jax.jit(lambda a: jnp.tanh(a) * 2.0).lower(
            jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
        big = jax.jit(lambda a: jnp.tanh(a) * 2.0).lower(
            jax.ShapeDtypeStruct((1024, 1024), jnp.float32)).compile()
        cs = analyze_hlo(small.as_text())
        cb = analyze_hlo(big.as_text())
        assert cb.hbm_bytes > 30 * cs.hbm_bytes
