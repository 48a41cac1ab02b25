"""Sharding-rule resolution (unit) + multi-device equivalence (subprocess):
the sharded train step must produce the same numbers as single-device."""

import jax
import jax.numpy as jnp
import pytest

from repro.models.spec import TensorSpec
from repro.parallel.sharding import ShardingRules, default_rules


class TestRules:
    def test_override_and_get(self):
        r = default_rules(data_axes=("data",), model_axis="model")
        assert r.get("heads") == "model"
        r2 = r.override(seq="model")
        assert r2.get("seq") == "model"
        assert r.get("seq") is None  # original untouched

    def test_multi_pod_batch_axes(self):
        r = default_rules(data_axes=("pod", "data"), model_axis="model")
        assert r.get("batch") == ("pod", "data")


class TestResolvePspec:
    def test_divisibility_drops_axis(self, devices_runner):
        devices_runner(
            """
            import jax, jax.numpy as jnp
            from jax.sharding import PartitionSpec as P
            from repro.models.spec import TensorSpec
            from repro.parallel.sharding import default_rules, resolve_pspec
            mesh = jax.make_mesh((2, 4), ("data", "model"))
            rules = default_rules(data_axes=("data",), model_axis="model")
            # heads=8 divides model=4 → sharded
            s = TensorSpec((16, 8, 4), None, ("embed", "heads", "head_dim"))
            assert resolve_pspec(s, rules, mesh) == P("data", "model"), resolve_pspec(s, rules, mesh)
            # heads=6 does NOT divide model=4 → dropped (whisper case)
            s2 = TensorSpec((16, 6, 4), None, ("embed", "heads", "head_dim"))
            assert resolve_pspec(s2, rules, mesh) == P("data"), resolve_pspec(s2, rules, mesh)
            # tuple axes degrade to the longest dividing prefix
            rules2 = default_rules(data_axes=("data", "model"))
            s3 = TensorSpec((2, 10), None, ("batch", None))
            ps = resolve_pspec(s3, rules2, mesh)
            assert ps == P("data"), ps
            # axis never reused across dims
            s4 = TensorSpec((8, 8), None, ("heads", "kv_heads"))
            ps4 = resolve_pspec(s4, rules, mesh)
            assert ps4 == P("model"), ps4
            print("RESOLVE OK")
            """
        )

    def test_sharded_train_step_matches_single_device(self, devices_runner):
        out = devices_runner(
            """
            import jax, jax.numpy as jnp, numpy as np
            import repro.configs as C
            from repro.launch.mesh import make_mesh
            from repro.launch.build import build_cell
            from repro.configs.shapes import ShapeCell
            from repro.models import Model
            from repro.runtime.steps import init_train_state, make_train_step
            from repro.data import SyntheticDataset

            spec = C.smoke("granite-8b")
            spec = spec.replace_model(compute_dtype="float32")
            model = Model(spec.model)
            ex = spec.exec.replace(num_microbatches=2)
            cell = ShapeCell("t", seq_len=16, global_batch=8, kind="train")
            ds = SyntheticDataset(spec.model, 8, 16, seed=0)
            batch = {k: jnp.asarray(v) for k, v in ds.batch_at(0).items()}

            # single device
            state = init_train_state(model, ex, jax.random.key(0))
            step = jax.jit(make_train_step(model, ex))
            _, m1 = step(state, batch)

            # 8-device mesh through the launcher path
            mesh = make_mesh((2, 4), ("data", "model"))
            built = build_cell(spec, cell, mesh, exec_override=ex)
            state2 = init_train_state(model, ex, jax.random.key(0))
            jitted = jax.jit(built.step_fn, in_shardings=built.in_shardings,
                             out_shardings=built.out_shardings)
            with jax.set_mesh(mesh):
                _, m2 = jitted(state2, batch)
            l1, l2 = float(m1["loss"]), float(m2["loss"])
            print("LOSSES", l1, l2)
            assert abs(l1 - l2) < 1e-4, (l1, l2)
            g1, g2 = float(m1["grad_norm"]), float(m2["grad_norm"])
            assert abs(g1 - g2) / max(g1, 1e-9) < 1e-3, (g1, g2)
            print("SHARDED == SINGLE OK")
            """
        )
        assert "SHARDED == SINGLE OK" in out

    def test_moe_expert_parallel_matches_single_device(self, devices_runner):
        out = devices_runner(
            """
            import dataclasses
            import jax, jax.numpy as jnp
            import repro.configs as C
            from repro.launch.mesh import make_mesh
            from repro.launch.build import rules_for
            from repro.configs.shapes import ShapeCell
            from repro.parallel.constraints import activation_sharding
            from repro.models import Model, init_tree

            spec = C.smoke("kimi-k2-1t-a32b")
            cfg = spec.model.replace(
                compute_dtype="float32",
                moe=dataclasses.replace(spec.model.moe, capacity_factor=8.0),
            )
            model = Model(cfg)
            params = init_tree(jax.random.key(0), model.param_specs())
            batch = {"tokens": jax.random.randint(jax.random.key(1), (8, 16),
                                                  0, cfg.vocab_size)}
            loss1, _ = model.loss_fn(params, batch)
            mesh = make_mesh((2, 4), ("data", "model"))
            cell = ShapeCell("t", 16, 8, "train")
            rules = rules_for(spec, cell, mesh)
            with activation_sharding(rules, mesh):
                loss2, _ = model.loss_fn(params, batch)
            l1, l2 = float(loss1), float(loss2)
            print("LOSSES", l1, l2)
            assert abs(l1 - l2) < 5e-3, (l1, l2)
            print("MOE EP OK")
            """
        )
        assert "MOE EP OK" in out

    @pytest.mark.slow  # ~80s: compiles 6 archs × 3 step kinds
    def test_tiny_mesh_dryrun_all_step_kinds(self, devices_runner):
        """lower+compile every step kind on an 8-device mesh using smoke
        configs — the dry-run machinery end to end, in miniature."""
        out = devices_runner(
            """
            import jax
            import repro.configs as C
            from repro.launch.mesh import make_mesh
            from repro.launch.build import build_cell
            from repro.configs.shapes import ShapeCell

            mesh = make_mesh((2, 4), ("data", "model"))
            cells = [ShapeCell("t", 16, 8, "train"),
                     ShapeCell("p", 32, 8, "prefill"),
                     ShapeCell("d", 32, 8, "decode")]
            for arch in ["granite-8b", "kimi-k2-1t-a32b", "mamba2-370m",
                         "zamba2-1.2b", "whisper-tiny",
                         "llava-next-mistral-7b"]:
                spec = C.smoke(arch)
                if spec.model.family == "vlm":
                    cells_a = [ShapeCell("t", 24, 8, "train"),
                               ShapeCell("p", 24, 8, "prefill"),
                               ShapeCell("d", 32, 8, "decode")]
                else:
                    cells_a = cells
                for cell in cells_a:
                    built = build_cell(spec, cell, mesh)
                    compiled = built.lower(mesh).compile()
                    assert compiled.memory_analysis() is not None
                    print("OK", arch, cell.kind)
            print("TINY DRYRUN OK")
            """
        )
        assert "TINY DRYRUN OK" in out
