"""95th percentile of the serving engine's own queue wait, enqueue to
the start of the batch's execution (``ServingEngine.recorder``, host
clock), over the requests enqueued in the window."""

import numpy as np


def read(run):
    if not run.queue_waits_s:
        return None
    return float(np.percentile(run.queue_waits_s, 95)) * 1e3
