"""95th percentile of the client latency over every request of the
window (host clock), as ``latency_p50_ms`` defines it."""

import numpy as np


def read(run):
    lat = np.sort(run.latencies_s)
    if not len(lat):
        return None
    v = float(np.percentile(lat, 95))
    return v * 1e3 if np.isfinite(v) else None
