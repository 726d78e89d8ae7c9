"""The serving engine's own host time per step: the mean over the
window's steps of the ``engine.step`` span less its ``replica.forward``
child (queue pop, recorder stamps, stacking and padding the batch,
guard events, completion stamps), from the program's span log
(``repro.core.spans``), in ms.  Nothing where the log was off."""

import progtrace


def read(run):
    pt = progtrace.for_run(run)
    if pt is None:
        return None
    return progtrace.self_ms_per_step(pt.spans, "engine.step",
                                      ["replica.forward"])
