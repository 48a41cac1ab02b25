"""The main path's device programs compile for a TPU v5e at real sizes.

No chip is needed: the TPU compiler compiles for a DESCRIBED v5e:2x2
topology.  Interpret-mode kernel tests cannot see what Mosaic refuses
(dynamic row slices, `erf`, scalar stores to VMEM, ...); these compiles
can.  The topology is described inside a module fixture — never at import,
so that every test worker collects the same tests and only the worker
running this file loads the TPU library — and the fixture skips where it
cannot be described.  JAX's persistent compilation cache is off around the
compiles (a TPU executable written there cannot be read back without a
chip).
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.core.fast_bo import FleetState
from repro.fleet.batched_engine import _fleet_update
from repro.fleet.sharding import sharded_update
from repro.kernels.ei_argmax import ops as ei_ops
from repro.kernels.ei_argmax.kernel import ei_argmax_kernel_call

CATALOG = dict(n=32768, d=6, b=24)  # the catalog-scale fleet
PAPER = dict(n=69, d=4, b=69)  # Table I jobs over the 69-config grid
ROWS = 8  # lockstep chunk extent
N_INIT = 3  # scripted random-init slots


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topology = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topology
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def kernel_lane(monkeypatch):
    """Take the compiled-kernel branch the TPU backend would take (this
    process's default backend is the CPU)."""
    monkeypatch.setattr(ei_ops, "_should_use_kernel", lambda interpret: True)


def chunk_args(sharding, lead, n, d, b):
    """Abstract (state, args) of one chunk update; ``lead`` prefixes every
    shape (the chunk rows, or shards × rows)."""

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(lead + shape, dtype, sharding=sharding)

    state = FleetState(
        obs=s((n,), bool), tried=s((b,), jnp.int32), py=s((b,), jnp.float32),
        feats=s((b, d), jnp.float32), t=s((), jnp.int32),
        stop=s((), jnp.int32), pb=s((), jnp.int32), done=s((), bool),
        last_ei=s((), jnp.float32), last_best=s((), jnp.float32),
    )
    args = (
        s((n, d), jnp.float32), s((n,), jnp.float32), s((n,), bool),
        s((n,), bool), s((N_INIT,), jnp.int32), s((), jnp.int32),
        s((), jnp.int32),
    )
    return state, args


def compile_chunk_update(one_chip, layout, n, d, b):
    state, args = chunk_args(one_chip, (ROWS,), n, d, b)
    tail = tuple(
        jax.ShapeDtypeStruct((), dt, sharding=one_chip)
        for dt in (jnp.int32, jnp.float32, bool)
    )
    return _fleet_update.lower(
        state, *args, *tail, xi=0.0, layout=layout
    ).compile()


def test_ei_argmax_kernel_compiles(one_chip):
    n, d, b = CATALOG["n"], CATALOG["d"], CATALOG["b"]

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    call = jax.jit(
        lambda *a: ei_argmax_kernel_call(*a, tile=1024, xi=0.0,
                                         interpret=False)
    )
    compiled = call.lower(
        s((n, d), jnp.float32), s((n,), bool), s((b, d), jnp.float32),
        s((b,), jnp.float32), s((b,), jnp.float32), s((b, b), jnp.float32),
        s((4,), jnp.float32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("layout", ["feature", "fused"])
def test_catalog_chunk_update_compiles(one_chip, kernel_lane, layout):
    compiled = compile_chunk_update(one_chip, layout, **CATALOG)
    assert ("tpu_custom_call" in compiled.as_text()) == (layout == "fused")


def test_paper_chunk_update_compiles(one_chip):
    compiled = compile_chunk_update(one_chip, "feature", **PAPER)
    assert compiled.memory_analysis() is not None


def test_sharded_chunk_update_compiles(topo, kernel_lane):
    """The job-axis-sharded update over all four chips of the host."""
    devices = tuple(topo.devices)
    update, sharding = sharded_update(devices, 0.0, "fused")
    assert isinstance(sharding, NamedSharding)
    state, args = chunk_args(sharding, (len(devices), ROWS), **CATALOG)
    tail = tuple(
        jax.ShapeDtypeStruct((len(devices),), dt, sharding=sharding)
        for dt in (jnp.int32, jnp.float32, bool)
    )
    compiled = update.lower(state, *args, *tail).compile()
    assert "tpu_custom_call" in compiled.as_text()
