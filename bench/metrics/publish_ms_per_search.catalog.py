"""Host time of publication per published search, in ms: the mean of the
``tuning.publish`` spans (`TuningSession._publish`: a finished search's
`SearchOutcome`, its trial records, the class history and the outcome
listeners) that start in the traced window.  None where the program emits
no such span."""

import program_trace


def read(ctx):
    red = program_trace.for_run(ctx)
    if red is None or "tuning.publish" not in red["spans"]:
        return None
    s = red["spans"]["tuning.publish"]
    return 1e3 * s["total_s"] / s["count"]
