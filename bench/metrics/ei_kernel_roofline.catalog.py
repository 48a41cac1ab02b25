"""The EI/argmax kernel's share of its roofline over the traced window,
in %: the least time its work could take, max(flops / the vector unit's
float32 peak, bytes / HBM bandwidth) summed over the window's dispatches,
over the busiest device's time in the ``ei_argmax`` custom call.

The work of each dispatch comes from its ``tuning.dispatch`` span's
``rows`` and ``slots`` (`span_args`) through `work/ei_tail.py`; n and d
from the cell's space; the peaks from ``peaks.json`` by the chip's
``device_kind``.  Prints its flops, bytes and times on standard error."""

import json
import os
import re
import sys

import harness
import span_args

KERNEL = re.compile(r"%ei_argmax(\.\d+)? ")


def read(ctx):
    run = span_args.for_run(ctx)
    if run is None:
        return None
    work = [a for a in run["args"].get("tuning.dispatch", [])
            if "rows" in a and "slots" in a]
    kernel = {dev: sum(e - s for s, e, _, hlo in ops
                       if hlo and KERNEL.match(hlo)) * 1e-9
              for dev, ops in run["ops"].items()}
    busiest = max(kernel.values(), default=0.0)
    if not work or busiest <= 0.0:
        return None

    import jax

    kind = jax.devices()[0].device_kind
    with open(os.path.join(harness.BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    vpu = peaks[kind]["vpu_f32_flops_per_s"]["value"]
    hbm = peaks[kind]["hbm_bytes_per_s"]["value"]

    space = ctx["cfg"]["space"]
    n, d = harness._module("spaces", space["generator"]).make(
        space, 0)["features"].shape
    tail = harness._module("work", "ei_tail")
    flops = [tail.flops(a["rows"], a["slots"], n, d) for a in work]
    nbytes = [tail.bytes_read(a["rows"], n, d) for a in work]
    least = sum(max(f / vpu, b / hbm) for f, b in zip(flops, nbytes))
    bound = "flops" if sum(flops) / vpu > sum(nbytes) / hbm else "bytes"
    print(f"ei_kernel_roofline: {len(work)} dispatches, {sum(flops):.6g} "
          f"flops, {sum(nbytes):.6g} bytes (n {n}, d {d}); least time "
          f"{least:.6g} s, {bound}-bound at {vpu:.6g} flop/s and "
          f"{hbm:.6g} B/s ({kind}); kernel {busiest:.6g} s",
          file=sys.stderr)
    return 100.0 * least / busiest
