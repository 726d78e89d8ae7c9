"""Host time per step spent getting the batch onto the device: the
mean over the window's replica calls of ``replica.h2d`` (the copy is
started: the runtime's host-side layout transpose and staging) plus
``replica.h2d_wait`` (until the batch has landed), from the program's
span log (``repro.core.spans``), in ms.  The jitted dispatch, which
runs between the two while the copy proceeds, is not counted.  Nothing
where the log was off."""

import progtrace


def read(run):
    pt = progtrace.for_run(run)
    if pt is None:
        return None
    calls = progtrace.mean_ms(pt.spans, "replica.h2d")
    if calls is None:
        return None
    wait = progtrace.mean_ms(pt.spans, "replica.h2d_wait") or 0.0
    return calls + wait
