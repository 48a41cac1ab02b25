"""Device time of the GP head per chunk dispatch over the traced window,
in ms: the busiest device's operations under the ``gp_head`` name scope
(`fast_bo._packed_head`: the 18-point hyperparameter grid's Cholesky
factorizations, solves and marginal likelihoods) per dispatch."""

import program_trace


def read(ctx):
    red = program_trace.for_run(ctx)
    dispatches = ctx["counters"]["dispatches"]
    if red is None or not red["devices"] or not dispatches:
        return None
    busiest = max(red["devices"].values(), key=lambda d: d["busy_s"])
    head = busiest["scopes"].get("gp_head")
    if head is None:
        return None
    return 1e3 * head / dispatches
