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

import re
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.core.fast_bo import FleetState
from repro.fleet.batched_engine import _fleet_update
from repro.fleet.sharding import sharded_update
from repro.fleet.staging import _words, stage
from repro.kernels.ei_argmax import ops as ei_ops
from repro.kernels.ei_argmax.kernel import ei_argmax_kernel_call

CATALOG = dict(n=32768, d=6, b=24)  # the catalog-scale fleet
EC2 = dict(n=129024, d=6, b=24)  # the gen-6/7 c/m/r EC2 catalog
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


def test_ec2_catalog_fused_update_compiles(one_chip, kernel_lane):
    """The fused chunk update over the whole EC2 catalog (126 instance
    types × 1–1024 nodes) at B = 24, 8 rows: the kernel is there, under
    its own name and inside the ``ei_tail`` scope."""
    text = compile_chunk_update(one_chip, "fused", **EC2).as_text()
    assert "tpu_custom_call" in text
    kernels = re.findall(r"%(ei_argmax[.\d]*) = [^\n]*"
                         r'custom_call_target="tpu_custom_call"[^\n]*'
                         r'op_name="([^"]*)"', text)
    assert kernels and all("ei_tail" in op for _, op in kernels)


@pytest.mark.parametrize("dims", [PAPER, EC2], ids=["paper", "ec2"])
def test_chunk_staging_compiles(one_chip, dims):
    """Admission's split of one chunk's packed inputs (`staging.stage`)
    at 8 rows: it gives the update's arrays in their shapes and dtypes,
    stacks the geometry from the rows' (n, d) device copies, and needs
    no scratch near the size of what it makes."""
    state, args = chunk_args(one_chip, (ROWS,), **dims)
    scalars = [jax.ShapeDtypeStruct((), dt) for dt in
               (jnp.int32, jnp.float32, bool, jnp.int32)]
    packed = list(state) + list(args[1:]) + scalars
    spec = tuple((x.shape, np.dtype(x.dtype)) for x in packed)
    buf = jax.ShapeDtypeStruct((sum(_words(*s) for s in spec),), jnp.uint32,
                               sharding=one_chip)
    geoms = (jax.ShapeDtypeStruct(args[0].shape[1:], jnp.float32,
                                  sharding=one_chip),) * ROWS
    geom, arrays = jax.eval_shape(partial(stage, spec=spec), buf, geoms)
    assert [(x.shape, x.dtype) for x in [geom] + arrays] == [
        (x.shape, x.dtype) for x in [args[0]] + packed[:-1]]
    compiled = stage.lower(buf, geoms, spec=spec).compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < mem.output_size_in_bytes // 4


@pytest.fixture(scope="module")
def paper_update(one_chip):
    return compile_chunk_update(one_chip, "feature", **PAPER)


def test_paper_chunk_update_compiles(paper_update):
    assert paper_update.memory_analysis() is not None


def _custom_calls(text):
    return re.findall(r'custom_call_target="([^"]+)"[^\n]*?op_name="([^"]*)"',
                      text)


def test_paper_gp_head_factorizes_in_a_loop(paper_update):
    """On the TPU the GP head is a column loop over the observed slots:
    neither XLA's Cholesky nor `cho_solve`'s triangular inversion is left
    in it.  The EI tail's own triangular solve stays."""
    calls = _custom_calls(paper_update.as_text())
    assert not [c for c in calls if c[0] == "Cholesky"]
    inversions = [op for target, op in calls
                  if target == "InvertDiagBlocksLowerTriangular"]
    assert len(inversions) == 1 and "gp_head" not in inversions[0]


def test_cpu_gp_head_keeps_lapack():
    """Lowered for the CPU, the same update keeps XLA's (LAPACK) Cholesky
    and `cho_solve`, so the CPU's numbers are those of the full-extent
    factorization."""
    cpu = SingleDeviceSharding(jax.devices("cpu")[0])
    state, args = chunk_args(cpu, (ROWS,), **PAPER)
    tail = tuple(
        jax.ShapeDtypeStruct((), dt, sharding=cpu)
        for dt in (jnp.int32, jnp.float32, bool)
    )
    text = _fleet_update.lower(
        state, *args, *tail, xi=0.0, layout="feature"
    ).compile().as_text()
    calls = _custom_calls(text)
    assert [op for target, op in calls
            if target.startswith("lapack_spotrf") and "gp_head" in op]
    assert [op for target, op in calls
            if target.startswith("lapack_strsm") and "gp_head" in op]


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
