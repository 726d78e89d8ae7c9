"""Device idle time while the host was in the replica's forward
(``replica.forward`` and its children ``replica.h2d``,
``replica.dispatch``, ``replica.wait`` with ``replica.h2d_wait``,
``replica.d2h``, from the program's span log, ``repro.core.spans``) per
request answered, from the profiler trace of the window, in ms.
Nothing where the log was off."""

import progtrace

LABELS = ("replica.forward", "replica.h2d", "replica.dispatch",
          "replica.wait", "replica.h2d_wait", "replica.d2h")


def read(run):
    pt = progtrace.for_run(run)
    if pt is None or not pt.spans or run.completed == 0:
        return None
    idle = sum(pt.idle_by_span.get(lab, 0.0) for lab in LABELS)
    return idle * 1e3 / run.completed
