"""The one general load generator: reads a traffic file and drives a
system with it for a window.

Traffic kinds (the ``kind`` of a file under ``bench/traffic/``):

* ``closed``: ``clients`` callers, each sending its next request as soon
  as the previous one is answered, through the program's asyncio
  serving front (``AsyncConvServer``) over the bucket grid ``buckets``.
  Latency runs from send to answer.
* ``open``: Poisson arrivals at ``rate`` requests/s through the same
  front.  The arrival times are the cell's own (drawn once from
  ``gaps_seed``), the same for every seed: near the knee the order of
  the gaps decides how batches form, so a seed that reordered them
  would change the work; the seed picks which image arrives at each
  time.  Latency runs from each request's due time, so a late
  generator shows.
* ``batch_loop``: one batch of ``batch`` images in flight, sent straight
  to the replica's batch function (numpy in, numpy out).

Every request takes an image from a pool of ``pool`` seeded images (the
system caches no results, so reuse is harmless), in an order drawn from
the seed.  The window starts when the first request is sent and ends
when the last request sent before ``seconds`` had passed is answered.
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import time

import numpy as np

clock = time.monotonic


@dataclasses.dataclass
class Request:
    item: int                  # pool index (batch_loop: first tile)
    due: float
    sent: float | None = None
    done: float | None = None
    shed: bool = False
    out: object = None         # the answer (batch_loop: a batch of rows)
    n: int = 1                 # images in the request


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    requests: list

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def arrival_times(traffic: dict, seconds: float) -> np.ndarray:
    """Offsets (s) of the open-loop arrivals of one window: the gaps of
    ``round(rate * seconds)`` requests, drawn from ``gaps_seed``."""
    rate = float(traffic["rate"])
    n = max(1, int(round(rate * seconds)))
    gaps = np.random.default_rng(int(traffic["gaps_seed"])).exponential(
        1.0 / rate, size=n)
    return np.cumsum(gaps)


def item_order(pool: int, count: int, seed: int) -> np.ndarray:
    from inputs import ORDER, rng
    return rng(seed, ORDER + 1).integers(0, pool, size=count)


async def _closed(srv, pool, clients: int, seconds: float, order):
    from repro.core.serving import QueueFull
    reqs: list[Request] = []
    t0 = clock()
    stop = t0 + seconds
    nxt = iter(order)

    async def caller():
        while clock() < stop:
            i = int(next(nxt))
            r = Request(item=i, due=clock())
            r.sent = r.due
            reqs.append(r)
            try:
                r.out = await srv.submit(pool[i])
                r.done = clock()
            except QueueFull:
                r.shed = True

    await asyncio.gather(*[caller() for _ in range(clients)])
    return Window(t0, max([t0 + seconds] + [r.done for r in reqs
                                            if r.done is not None]), reqs)


async def _open(srv, pool, times, order):
    from repro.core.serving import QueueFull
    t0 = clock()
    reqs = [Request(item=int(order[k]), due=t0 + float(t))
            for k, t in enumerate(times)]

    async def one(r):
        r.sent = clock()
        try:
            r.out = await srv.submit(pool[r.item])
            r.done = clock()
        except QueueFull:
            r.shed = True

    tasks = []
    for r in reqs:
        delay = r.due - clock()
        if delay > 0:
            await asyncio.sleep(delay)
        tasks.append(asyncio.create_task(one(r)))
    await asyncio.gather(*tasks)
    return Window(t0, max([reqs[-1].due] + [r.done for r in reqs
                                            if r.done is not None]), reqs)


def _batch_loop(fn, pool, batch: int, seconds: float, order):
    """Consecutive pool slices of ``batch`` images, chosen by ``order``."""
    n_batches = len(pool) // batch
    t0 = clock()
    stop = t0 + seconds
    reqs = []
    for k in order:
        b = int(k) % n_batches
        r = Request(item=b * batch, due=clock(), n=batch)
        r.sent = r.due
        r.out = fn(pool[b * batch:(b + 1) * batch])
        r.done = clock()
        reqs.append(r)
        if r.done >= stop:
            break
    return Window(t0, reqs[-1].done, reqs)


class TrafficRunner:
    """Runs windows of one traffic file against one system.  One asyncio
    loop and one serving front (with its batcher task and executor
    thread) serve the warm-up and the measured window alike."""

    def __init__(self, traffic: dict, engine, pool: np.ndarray,
                 seed: int) -> None:
        self.traffic = traffic
        self.kind = traffic["kind"]
        if self.kind not in ("closed", "open", "batch_loop"):
            raise ValueError(f"unknown traffic kind {self.kind!r}")
        self.engine = engine
        self.pool = pool
        self.seed = seed
        self._runs = 0
        self._loop = asyncio.new_event_loop()
        self._srv = None
        if self.kind != "batch_loop":
            from repro.launch.serve_conv import AsyncConvServer
            self._srv = AsyncConvServer(
                engine, max_wait_s=float(traffic.get("max_wait_ms", 2.0))
                / 1e3)
            self._loop.run_until_complete(self._srv.__aenter__())

    def close(self) -> None:
        """Drain and stop the serving front, then the loop's threads."""
        if self._srv is not None:
            self._loop.run_until_complete(self._srv.__aexit__(None, None,
                                                              None))
            self._srv = None
        self._loop.run_until_complete(self._loop.shutdown_default_executor())
        self._loop.close()

    def _order(self, count: int) -> np.ndarray:
        # a fresh stretch of the seeded order for every window
        self._runs += 1
        return item_order(len(self.pool), count,
                          self.seed * 7919 + self._runs)

    def run(self, seconds: float, around=None) -> Window:
        """One window of ``seconds``; ``around`` (a context manager
        factory) wraps the window itself."""
        t = self.traffic
        if self.kind == "batch_loop":
            order = self._order(100000)
            fn = self.engine.replicas[0].fn
            with (around or contextlib.nullcontext)():
                return _batch_loop(fn, self.pool, int(t["batch"]), seconds,
                                   order)
        return self._loop.run_until_complete(self._serve(seconds, around))

    async def _serve(self, seconds, around):
        t, srv = self.traffic, self._srv
        with (around or contextlib.nullcontext)():
            if self.kind == "closed":
                order = self._order(int(seconds * 20000) + 1000)
                return await _closed(srv, self.pool, int(t["clients"]),
                                     seconds, order)
            times = arrival_times(t, seconds)
            return await _open(srv, self.pool, times,
                               self._order(len(times)))

