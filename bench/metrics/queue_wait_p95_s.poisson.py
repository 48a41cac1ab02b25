"""95th percentile, over every search due in the window, of the time from
its due time to its admission into a lockstep chunk
(`TuningSession._admit_group`, run by the service's group worker)."""

import numpy as np


def read(ctx):
    wait = ctx["notes"].get("queue_wait_s")
    if not wait:
        return None
    return float(np.percentile(wait, 95))
