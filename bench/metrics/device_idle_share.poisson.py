"""Share of the traced window in which no operation ran on the device,
in %, averaged over the devices the cell uses."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not trace["devices"]:
        return None
    devs = trace["devices"].values()
    return 100.0 * sum(d["idle_share"] for d in devs) / len(devs)
