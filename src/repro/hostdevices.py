"""Force a multi-device CPU topology for the job-axis sharding lanes.

`XLA_FLAGS=--xla_force_host_platform_device_count=N` must land in the
environment BEFORE the JAX backend initializes, which makes it an
entry-point concern: `tests/conftest.py` (the in-process shard test
lanes), `tests/golden/regen.py` (fixture regeneration under the test
topology) and the `repro.launch` dry runs (placeholder production
meshes) all need the same guard.  THIS module is the one copy
of it — deliberately jax-free, so importing it can never initialize the
backend it is trying to configure.

Forcing more devices than a run will use is not free (each forced device
dilutes the host's intra-op thread pool, slowing single-device work), so
callers pass exactly the count they need and the guard appends only when
the caller's environment has not already forced one.
"""

import os

FLAG = "xla_force_host_platform_device_count"


def force_host_device_count(n: int = 4) -> None:
    """Append ``--xla_force_host_platform_device_count=n`` to XLA_FLAGS
    unless a count is already forced.  A no-op after backend init — call
    it before anything touches a jax array."""
    if FLAG in os.environ.get("XLA_FLAGS", ""):
        return
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + f" --{FLAG}={int(n)}"
    ).strip()
