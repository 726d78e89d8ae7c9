"""Median client latency over every request of the window (host clock):
send to answer in a closed loop, due time to answer in an open loop.  A
request never answered counts as infinitely late."""

import numpy as np


def read(run):
    lat = np.sort(run.latencies_s)
    if not len(lat):
        return None
    v = float(np.percentile(lat, 50))
    return v * 1e3 if np.isfinite(v) else None
