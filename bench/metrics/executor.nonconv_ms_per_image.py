"""Device time of every op that is not a Pallas conv kernel (pads,
pools, joins, the head, copies), per image the device ran, from the
profiler trace of the window."""


def read(run):
    rows = sum(run.steps)
    if run.trace is None or rows == 0:
        return None
    return run.trace.nonkernel_s * 1e3 / rows
