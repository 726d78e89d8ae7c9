"""Share of the window in which no op ran on the device: 1 - (union of
the device's op intervals / window), from the profiler trace, in
percent.  Batch cells."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
