"""Share of the window in which no op ran on the device, as
``device_idle.batch`` reads it.  The single-request cell."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
