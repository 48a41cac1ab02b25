"""Host time of the §III-D split per submitted search, in ms: the mean of
the ``tuning.split`` spans (`TuningSession._submit_locked`: the priority
mask over all n configurations and its index lists) that start in the
traced window."""

import program_trace


def read(ctx):
    red = program_trace.for_run(ctx)
    if red is None or "tuning.split" not in red["spans"]:
        return None
    s = red["spans"]["tuning.split"]
    return 1e3 * s["total_s"] / s["count"]
