"""Chunk dispatches (`TuningSession._step_chunk` calls) per search
published in the window: how well the session batches searches into
lockstep chunks."""


def read(ctx):
    c = ctx["counters"]
    if not c["searches"]:
        return None
    return c["dispatches"] / c["searches"]
