"""Share of the traced window in which the device idled while the
service admitted searches into chunks, in %, averaged over the devices
the cell uses: idle time under ``tuning.admit`` spans
(`TuningSession._admit_group`, its chunk arrays and device puts)."""

import program_trace


def read(ctx):
    red = program_trace.for_run(ctx)
    if (red is None or not red["devices"]
            or "tuning.admit" not in red["spans"]):
        return None
    devs = red["devices"].values()
    idle = sum(d["idle_under"].get("tuning.admit", 0.0) for d in devs)
    return 100.0 * idle / len(devs) / red["window_s"]
