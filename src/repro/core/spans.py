"""Host spans of the serving path, kept in memory (one log per process).

The serving engine, its replicas and the asyncio front mark where the
host spends a step: ``engine.step`` and its children ``engine.batch``,
``replica.forward`` and ``engine.finish``; under ``replica.forward`` the
replica's ``replica.h2d``, ``replica.dispatch``, ``replica.wait`` (with
``replica.h2d_wait``) and ``replica.d2h``; ``engine.submit`` for each
enqueue; ``server.step`` and
``server.resolve`` in the front.  Each record is ``(name, start_ns,
end_ns, step)`` on ``time.perf_counter_ns``; ``step`` is the engine's
step id, or ``None`` outside a step.

The log is off until :func:`enable` is called, and then keeps the
first :data:`CAP` records and counts the rest in ``dropped``.  Off,
:func:`span` returns a shared no-op context after one attribute check.
The event loop and the executor thread both record, under one lock.

A profiler's host tracer would give the same intervals, but at any
level that keeps annotations it also records every chunk of the
runtime's host-side input transpose, which slows a serving host several
times over; these spans cost two clock reads and a locked append.

    from repro.core import spans
    spans.enable()
    ...                       # serve
    records = spans.records()
    spans.disable()
"""

from __future__ import annotations

import threading
import time

__all__ = ["CAP", "span", "enable", "disable", "records", "dropped"]

#: records kept: over a minute of single-image serving on one TPU v5e
#: (about 3,000 spans a second), some 40 MB of host memory
CAP = 1 << 18


class _Log:
    def __init__(self) -> None:
        self.on = False
        self.records: list = []
        self.dropped = 0


_LOG = _Log()
_LOCK = threading.Lock()
_LOCAL = threading.local()        # .step: the open step of this thread


class _Span:
    """An open span; recorded when its ``with`` block exits."""

    __slots__ = ("name", "step", "t0", "_outer")

    def __init__(self, name: str, step) -> None:
        self.name = name
        self.step = step
        self._outer = _NO_STEP

    def set_step(self, step: int) -> None:
        """Give this span the step id ``step``; spans opened or closed
        inside it on the same thread take it too."""
        if self._outer is _NO_STEP:
            self._outer = getattr(_LOCAL, "step", None)
        self.step = step
        _LOCAL.step = step

    def __enter__(self) -> "_Span":
        if self.step is not None:
            self.set_step(self.step)
        self.t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        step = self.step
        if step is None:
            step = getattr(_LOCAL, "step", None)
        if self._outer is not _NO_STEP:
            _LOCAL.step = self._outer
        log = _LOG
        with _LOCK:
            if len(log.records) < CAP:
                log.records.append((self.name, self.t0, t1, step))
            else:
                log.dropped += 1


class _Off:
    __slots__ = ()

    def set_step(self, step: int) -> None:
        pass

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> None:
        pass


_NO_STEP = object()
_OFF = _Off()


def span(name: str, step: int | None = None):
    """A ``with`` block recorded as span ``name``.  ``step`` (or a later
    ``set_step``) makes it a step's span; without one it takes the step
    open on this thread when it ends, if any."""
    if not _LOG.on:
        return _OFF
    return _Span(name, step)


def enable() -> None:
    """Start recording into an empty log."""
    with _LOCK:
        _LOG.records = []
        _LOG.dropped = 0
    _LOG.on = True


def disable() -> None:
    """Stop recording; what was recorded stays readable."""
    _LOG.on = False


def records() -> list:
    """``[(name, start_ns, end_ns, step)]`` in the order spans ended (a
    child before its parent)."""
    with _LOCK:
        return list(_LOG.records)


def dropped() -> int:
    """Spans not kept because the log was full."""
    return _LOG.dropped
