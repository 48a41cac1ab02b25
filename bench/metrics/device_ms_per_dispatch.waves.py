"""Device busy time per chunk dispatch over the traced window, in ms: the
time the chunk update (`batched_engine._fleet_update`, or the sharded
`sharding.sharded_update`) keeps the busiest device busy per step."""


def read(ctx):
    trace, c = ctx["trace"], ctx["counters"]
    if trace is None or not trace["devices"] or not c["dispatches"]:
        return None
    busy = max(d["busy_s"] for d in trace["devices"].values())
    return 1e3 * busy / c["dispatches"]
