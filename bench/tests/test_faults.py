"""The checks catch a broken timed path.

Drives the rest of a benchmark run of every cell, at its own size (the
harness's look for a chip is `run.py`'s, skipped here), with the chunk
update broken underneath the session, and sees ``correct`` come out
false, once for each fault a cell can have:

  * a step that returns its state unchanged;
  * half of a chunk's rows (of a sharded bundle's chunks) left out of the
    step;
  * a trial altered where the step produces it.

The fault is planted in the update each cell's chunks run: the plain
chunk update, or for a cell at ``shard`` > 1 the `shard_map` bundle
update.  (No cell has an exchange between chips: sharded searches are
independent and the sharded update holds no collective.)  Run by hand,
on the CPU or on one chip:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
    python -m pytest bench/tests/test_faults.py -q
"""

from __future__ import annotations

import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import adapter  # noqa: E402,F401  (puts the program on the path)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import repro.fleet.session as session_module  # noqa: E402


def unchanged(update, state, *args, **kwargs):
    return state


def half_left_out(update, state, *args, **kwargs):
    old = jax.tree_util.tree_map(jnp.copy, state)
    new = update(state, *args, **kwargs)
    rows = old.t.shape[0]
    keep = jnp.arange(rows) >= rows // 2

    def pick(n, o):
        return jnp.where(keep.reshape((rows,) + (1,) * (n.ndim - 1)), o, n)

    return jax.tree_util.tree_map(pick, new, old)


def altered(update, state, *args, **kwargs):
    """The first row's newest trial moves to the next configuration."""
    old_t = jnp.copy(state.t).reshape(-1)
    new = update(state, *args, **kwargs)
    n = new.obs.shape[-1]
    obs = new.obs.reshape(-1, n)
    tried = new.tried.reshape(-1, new.tried.shape[-1])
    t = new.t.reshape(-1)
    grew = t[0] > old_t[0]
    slot = jnp.maximum(t[0] - 1, 0)
    was = tried[0, slot]
    moved = (was + 1) % n
    obs = obs.at[0, was].set(jnp.where(grew, False, obs[0, was]))
    obs = obs.at[0, moved].set(jnp.where(grew, True, obs[0, moved]))
    tried = tried.at[0, slot].set(jnp.where(grew, moved, was))
    return new._replace(obs=obs.reshape(new.obs.shape),
                        tried=tried.reshape(new.tried.shape))


def plant(monkeypatch, fault) -> None:
    """Break both chunk updates the session can build."""
    real = session_module._fleet_update
    monkeypatch.setattr(session_module, "_fleet_update",
                        lambda s, *a, **k: fault(real, s, *a, **k))
    real_sharded = session_module.sharded_update

    def sharded(*a, **k):
        update, sharding = real_sharded(*a, **k)
        return (lambda s, *b: fault(update, s, *b)), sharding

    monkeypatch.setattr(session_module, "sharded_update", sharded)


CELLS = [w["name"] for w in harness._json(harness.ROOT, "BENCHMARK.json")[
    "workloads"]]


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("fault", [None, unchanged, half_left_out, altered])
def test_fault_is_caught(monkeypatch, name, fault):
    cell = harness.load_cell(name)
    if len(jax.devices()) < cell["workload"]["chips"]:
        pytest.fail(f"{name} needs {cell['workload']['chips']} devices: run "
                    "with XLA_FLAGS=--xla_force_host_platform_device_count=4")
    if fault is not None:
        plant(monkeypatch, fault)
    result, lines = harness.run(name, 12345, 1.0, False, cell=cell)
    assert result["correct"] is (fault is None), "\n".join(lines)
