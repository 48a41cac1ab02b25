"""Host time to enqueue one chunk update, in ms: the mean self time of
the ``tuning.dispatch`` spans (`TuningSession._step_chunk`, the call of
the jitted `_fleet_update`) that start in the traced window."""

import program_trace


def read(ctx):
    red = program_trace.for_run(ctx)
    if red is None or "tuning.dispatch" not in red["spans"]:
        return None
    d = red["spans"]["tuning.dispatch"]
    return 1e3 * d["self_s"] / d["count"]
