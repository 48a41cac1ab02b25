"""Device time of the EI tail per chunk dispatch over the traced window,
in ms: the busiest device's operations under the ``ei_tail`` name scope
(`fast_bo`: in the fused layout the `ei_argmax` kernel and the arrays it
is handed; in the feature layout the (B,n) cross block, the posterior,
EI and argmax) per dispatch."""

import program_trace


def read(ctx):
    red = program_trace.for_run(ctx)
    dispatches = ctx["counters"]["dispatches"]
    if red is None or not red["devices"] or not dispatches:
        return None
    busiest = max(red["devices"].values(), key=lambda d: d["busy_s"])
    tail = busiest["scopes"].get("ei_tail")
    if tail is None:
        return None
    return 1e3 * tail / dispatches
