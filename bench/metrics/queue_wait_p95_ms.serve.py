"""95th percentile, over the requests due in the window, of the wait from
a request's due time to the start of the engine iteration that admitted it
(read from the scheduler's active set at iteration boundaries); a request
not admitted by the window's end counts at its elapsed time."""

import numpy as np


def read(res, dims, peaks):
    waits = res.counters.get("queue_waits_s")
    if not waits:
        return None
    return 1e3 * float(np.percentile(np.asarray(waits, np.float64), 95))
