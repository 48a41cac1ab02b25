"""Assemble (step_fn, abstract inputs, shardings) for any (arch × cell × mesh).

This is the single place where model specs, shape cells, sharding rules and
step factories meet; the dry-run, the roofline benchmark, the tuner and the
real train/serve drivers all call ``build_cell``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro.configs import ArchSpec, ShapeCell, input_specs
from repro.configs.base import ExecConfig
from repro.models.model import Model
from repro.models.spec import abstract_tree
from repro.parallel.constraints import activation_sharding
from repro.parallel.sharding import ShardingRules, default_rules, named_sharding_tree
from repro.launch.mesh import data_axes, model_axis
from repro.runtime.steps import make_serve_steps, make_train_step, train_state_specs

__all__ = ["BuiltCell", "build_cell", "rules_for"]


@dataclasses.dataclass
class BuiltCell:
    """Everything needed to lower/compile/run one (arch × cell × mesh)."""

    step_fn: Callable
    abstract_args: Tuple[Any, ...]  # ShapeDtypeStruct pytrees, step_fn(*args)
    in_shardings: Tuple[Any, ...]
    out_shardings: Any
    donate_argnums: Tuple[int, ...]
    kind: str

    def lower(self, mesh: Mesh):
        jitted = jax.jit(
            self.step_fn,
            in_shardings=self.in_shardings,
            out_shardings=self.out_shardings,
            donate_argnums=self.donate_argnums,
        )
        with jax.set_mesh(mesh):
            return jitted.lower(*self.abstract_args)


def rules_for(
    spec: ArchSpec, cell: ShapeCell, mesh: Mesh, *, overrides: Optional[Dict] = None
) -> ShardingRules:
    """Default rules for a cell: FSDP per exec config; long-context decode
    (batch smaller than the data axes) shards the KV-cache length instead."""
    da = data_axes(mesh)
    rules = default_rules(
        data_axes=da,
        model_axis=model_axis(mesh) or "model",
        fsdp=spec.exec.fsdp,
    )
    if spec.exec.seq_shard:
        rules = rules.override(seq=model_axis(mesh) or "model")
    if spec.model.family == "hybrid":
        # The shared-attention site caches ride the layer scan's carry; a
        # model-axis-sharded carry makes GSPMD reshard it every iteration
        # (measured: zamba2 long_500k collectives 0.002→22.9 s).  Keep the
        # hybrid cache on the data axes only.
        rules = rules.override(cache_seq=da if len(da) > 1 else da[0])
    if overrides:
        rules = rules.override(**overrides)
    return rules


def _batch_pspec_tree(batch_specs: Dict[str, Any], rules: ShardingRules, mesh: Mesh):
    """Activation inputs shard on the batch dim only."""
    batch_axes = rules.get("batch")

    def pspec(leaf: jax.ShapeDtypeStruct) -> PartitionSpec:
        entry = batch_axes
        if entry is None:
            return PartitionSpec()
        axes = (entry,) if isinstance(entry, str) else tuple(entry)
        size = 1
        kept = []
        for a in axes:
            asize = int(mesh.shape[a])
            if leaf.shape and leaf.shape[0] % (size * asize) == 0:
                kept.append(a)
                size *= asize
            else:
                break
        if not kept:
            return PartitionSpec()
        first = kept[0] if len(kept) == 1 else tuple(kept)
        return PartitionSpec(*([first] + [None] * (len(leaf.shape) - 1)))

    return jax.tree.map(
        lambda l: NamedSharding(mesh, pspec(l)), batch_specs
    )


def build_cell(
    spec: ArchSpec,
    cell: ShapeCell,
    mesh: Mesh,
    *,
    rules: Optional[ShardingRules] = None,
    exec_override: Optional[ExecConfig] = None,
) -> BuiltCell:
    exec_cfg = exec_override or spec.exec
    cfg = spec.model
    model = Model(cfg)
    rules = rules or rules_for(spec, cell, mesh)
    replicated = NamedSharding(mesh, PartitionSpec())
    specs = input_specs(cfg, cell)

    def constrained(fn):
        """Trace the step under the activation-sharding context."""

        def wrapped(*args):
            with activation_sharding(rules, mesh):
                return fn(*args)

        return wrapped

    if cell.kind == "train":
        step = make_train_step(model, exec_cfg)
        state_specs = train_state_specs(model, exec_cfg)
        state_sh = named_sharding_tree(state_specs, rules, mesh)
        batch_sh = _batch_pspec_tree(specs["batch"], rules, mesh)
        abstract_state = abstract_tree(state_specs)
        return BuiltCell(
            step_fn=constrained(step),
            abstract_args=(abstract_state, specs["batch"]),
            in_shardings=(state_sh, batch_sh),
            # state keeps its shardings; metrics are replicated scalars
            out_shardings=(state_sh, None),
            donate_argnums=(0,),
            kind="train",
        )

    prefill_step, decode_step = make_serve_steps(model)
    param_specs = model.param_specs()
    params_sh = named_sharding_tree(param_specs, rules, mesh)
    abstract_params = abstract_tree(param_specs)
    cache_specs = model.cache_specs(cell.global_batch, cell.seq_len)
    cache_sh = named_sharding_tree(cache_specs, rules, mesh)

    if cell.kind == "prefill":
        batch_sh = _batch_pspec_tree(specs["batch"], rules, mesh)
        return BuiltCell(
            step_fn=constrained(prefill_step),
            abstract_args=(abstract_params, specs["batch"], specs["cache"]),
            in_shardings=(params_sh, batch_sh, cache_sh),
            out_shardings=(None, cache_sh),
            donate_argnums=(2,),
            kind="prefill",
        )

    # decode
    tokens_sh = _batch_pspec_tree({"tokens": specs["tokens"]}, rules, mesh)["tokens"]
    return BuiltCell(
        step_fn=constrained(decode_step),
        abstract_args=(abstract_params, specs["cache"], specs["tokens"],
                       specs["index"]),
        in_shardings=(params_sh, cache_sh, tokens_sh, replicated),
        out_shardings=(None, cache_sh),
        donate_argnums=(1,),
        kind="decode",
    )
