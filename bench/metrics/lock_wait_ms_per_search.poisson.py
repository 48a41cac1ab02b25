"""Time threads spent waiting for the session lock, per search published
in the traced window, in ms: the ``tuning.lock_wait`` spans (contended
acquisitions of `TuningSession._lock`) that start in the window.  Zero
where the program ran and no acquisition was contended."""

import program_trace


def read(ctx):
    red = program_trace.for_run(ctx)
    searches = ctx["counters"]["searches"]
    if red is None or not red["spans"] or not searches:
        return None
    wait = red["spans"].get("tuning.lock_wait", {}).get("total_s", 0.0)
    return 1e3 * wait / searches
