"""Admission stages a chunk's inputs in one transfer and keeps each
space's encoding on the device (`repro.fleet.staging`, `TuningSession.
_build_chunk`).

The arrays a new chunk hands to its update must equal, in dtype, shape
and bytes, a host build of the chunk: `_chunk_arrays`' state and args,
each row's space encoding (zero for dummy rows) and the settings
scalars.  Covered: both layouts, row extents 2 (one member and a dummy),
5 and 8, a chunk mixing two spaces of one shape, warm-start seed rows,
the `reshard` resume path, and committed placement on a device.  The
device copy of a space's encoding lives as long as its cache entry.
"""

import gc
import weakref

import jax
import numpy as np
import pytest

from repro.core.bayesopt import BOSettings
from repro.fleet import FleetJob, TuningSession
from repro.fleet.session import _SpaceEntry
from repro.fleet.staging import pack, stage

from golden.scenarios import flat_profile, synth_space_table

N = 24


def _record_builds(session):
    """Wrap ``session._build_chunk`` so that each chunk it builds is kept
    with a host build of the same chunk, both as NumPy arrays taken
    before the chunk's first (donating) update."""
    builds = []
    build = session._build_chunk

    def recording(members, shape, cap, n_init_slots, resume=None,
                  device=None):
        ch = build(members, shape, cap, n_init_slots, resume=resume,
                   device=device)
        rows = max(len(members), 2)
        state, args, _ = session._chunk_arrays(
            members, shape, cap, n_init_slots, rows, resume=resume)
        one = session._encoding(members[0].job.space)
        geom = np.zeros((rows,) + one.shape, one.dtype)
        for i, rec in enumerate(members):
            geom[i] = session._encoding(rec.job.space)
        st = session.settings
        want = list(state) + [geom] + list(args) + [
            np.asarray(st.min_observations, np.int32),
            np.asarray(st.ei_stop_rel, np.float32),
            np.asarray(session.to_exhaustion),
        ]
        got = [np.asarray(x) for x in list(ch.state) + list(ch.args)]
        builds.append((ch, got, want))
        return ch

    session._build_chunk = recording
    return builds


def _assert_bit_equal(got, want):
    assert len(got) == len(want)
    for k, (g, w) in enumerate(zip(got, want)):
        assert g.dtype == w.dtype, k
        assert g.shape == w.shape, k
        assert g.tobytes() == w.tobytes(), k


def _jobs(spaces, count):
    """``count`` cherrypick jobs, cycling over ``spaces``."""
    return [FleetJob(name=f"j{i}", space=spaces[i % len(spaces)][0],
                     cost_table=spaces[i % len(spaces)][1])
            for i in range(count)]


CASES = (
    [(layout, m, "one space") for layout in ("feature", "fused")
     for m in (1, 5, 8)]
    + [(layout, 8, "two spaces") for layout in ("feature", "fused")]
    + [("feature", 5, "warm seeds")]
    + [(layout, 5, "resume") for layout in ("feature", "fused")]
)


@pytest.mark.parametrize(
    "layout,members,variant", CASES,
    ids=[f"{c[0]}-{c[1]}-{c[2].replace(' ', '_')}" for c in CASES],
)
def test_staged_chunk_equals_host_build(layout, members, variant):
    settings = BOSettings(max_iters=8)
    if variant == "warm seeds":
        # A finished search of the flat class seeds the next ones.
        session = TuningSession(layout=layout, settings=settings,
                                warm_start=True)
        space, table = synth_space_table(N)
        mk = lambda name: FleetJob(
            name=name, space=space, cost_table=table,
            full_input_size=10e9, profile_result=flat_profile())
        session.submit(mk("donor"), seed=0)
        session.drain()
        builds = _record_builds(session)
        for s in range(members):
            session.submit(mk(f"j{s}"), seed=s + 1)
        session._admit()
        (ch, got, want), = builds
        assert all(m.seed_trials for m in ch.members)
        assert np.asarray(ch.state.t).max() > 0
        _assert_bit_equal(got, want)
        return

    session = TuningSession(layout=layout, settings=settings,
                            warm_start=False)
    spaces = [synth_space_table(N, seed=0)]
    if variant == "two spaces":
        spaces.append(synth_space_table(N, seed=1))
    for s, job in enumerate(_jobs(spaces, members)):
        session.submit(job, seed=s, mode="cherrypick")
    builds = _record_builds(session)
    session._admit()
    if variant == "resume":
        for _ in range(3):
            session.step()
        assert session.reshard(shard=None) == members
        assert len(builds) == 2
        ch, got, want = builds[1]
        assert np.asarray(ch.state.t).min() > 0  # rows resumed mid-search
    else:
        (ch, got, want), = builds
    if variant == "two spaces":
        assert len({id(m.job.space) for m in ch.members}) == 2
    _assert_bit_equal(got, want)


@pytest.mark.parametrize("on", ["default", "device 1"])
def test_staged_chunk_placement(on):
    """With no device the staged arrays take JAX's default placement,
    uncommitted, as `jnp.asarray` gave them; with a device they are
    committed to it, so that the update runs there."""
    device = None if on == "default" else jax.devices()[1]
    session = TuningSession(layout="feature", warm_start=False,
                            settings=BOSettings(max_iters=8))
    space, table = synth_space_table(N)
    for s in range(3):
        session.submit(FleetJob(name=f"j{s}", space=space, cost_table=table),
                       seed=s, mode="cherrypick")
    (key,) = session._pending_group_keys()
    session._admit_group(key, device=device)
    (ch,) = session._chunks
    for x in list(ch.state) + list(ch.args):
        assert x.committed == (device is not None)
        assert x.devices() == {device or jax.devices()[0]}


@pytest.mark.parametrize("dtype", [np.float32, np.int32, np.bool_])
@pytest.mark.parametrize(
    "shape", [(), (1,), (31,), (33,), (3, 69), (2, 5, 7), (5, 1000)])
def test_pack_round_trip_is_exact(shape, dtype):
    """Every segment comes back with its dtype, shape and bits: counts
    off a word and off a segment boundary, booleans over many bit
    planes, scalars, NaNs and negative zeros."""
    rng = np.random.default_rng(7)
    if dtype == np.bool_:
        x = rng.random(shape) < 0.5
    else:
        x = rng.integers(-2**31, 2**31, size=shape, dtype=np.int64)
        x = x.astype(np.int32).view(dtype)
    lead = rng.random(33) < 0.5  # the segment after it starts at 128
    buf, spec = pack([lead, x, np.int32(2)])
    geom, (back_lead, back) = stage(
        jax.device_put(buf), (jax.device_put(np.ones((2, 3), np.float32)),),
        spec=spec)
    _assert_bit_equal([np.asarray(back_lead), np.asarray(back)], [lead, x])
    assert np.asarray(geom).tobytes() == np.ones((1, 2, 3), np.float32).tobytes()


def test_pack_refuses_other_widths():
    with pytest.raises(ValueError, match="32-bit or bool"):
        pack([np.zeros(3, np.float64)])


def test_device_geometry_is_evicted_with_its_space():
    """The device copy of a space's encoding belongs to the space's cache
    entry: chunks reuse it while any search over the space is live, and
    it is dropped when the last one retires, as the host copy is."""
    session = TuningSession(layout="feature", warm_start=False,
                            settings=BOSettings(max_iters=8))
    space, table = synth_space_table(N)
    for s in range(10):
        session.submit(FleetJob(name=f"j{s}", space=space, cost_table=table),
                       seed=s, mode="cherrypick")
    session._admit()
    entry = session._spaces[id(space)]
    assert isinstance(entry, _SpaceEntry)
    (dev,) = entry.dev_geom.values()
    assert dev.shape == (N, 5)  # the space's (n, d) encoding
    alive = weakref.ref(dev)
    del dev, entry
    (g,) = session.telemetry.groups().values()
    assert (g["geom_puts"], g["geom_reuses"]) == (1, 1)  # 2 chunks

    session.drain()
    assert id(space) not in session._spaces
    gc.collect()
    assert alive() is None
