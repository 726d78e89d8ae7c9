"""Continuous-batching serving engine over the tuned conv stack
(DESIGN.md §10).

The "millions of users" leg of the roadmap: requests enter a bounded
FIFO queue and are served in *buckets* — a fixed grid of batch sizes,
one compiled program per bucket, so the JIT cache stays finite no matter
what batch sizes the traffic produces.  Each serving step drains up to
``max_bucket`` queued requests, rounds the count up to the smallest
bucket that fits, pads the short rows, executes on the next free
replica, and returns only the real rows — padding never leaks
(per-image independence of the conv stack makes every served row
bit-identical to the single-request forward; tested in
``tests/test_serving.py``).

Three design rules keep the engine testable and production-shaped:

* **Deterministic core, async shell.**  :class:`ServingEngine` is a
  synchronous state machine — ``submit(rid, x, now)`` and
  ``step(now=...)`` take explicit timestamps, so
  :func:`replay` can drive an arrival trace on a virtual clock
  (``repro.testing.load``) with *injected* service times and reproduce a
  timeline bit-for-bit.  The asyncio front end
  (``repro.launch.serve_conv``) wraps the same engine with
  ``time.monotonic`` and real futures.

* **No cold paths after prewarm.**  ``prewarm()`` sweeps
  ``autotune.prewarm_buckets`` over the bucket grid (every layer of the
  topology tuned at every bucket's batch shape and at the rows of the
  chunks a large bucket is split into, fused groups included when
  fused execution is on) and runs one throwaway forward per
  (bucket, replica) so every compiled program exists before the first
  request.  A bucket served without prewarm is a *cold tune* — counted
  in ``stats()`` and asserted zero by the benchmark.

* **Degradation is visible, not fatal.**  Every replica's forward runs
  the guarded tier chain of ``core.guard`` (fused -> sharded -> pallas
  -> ref); the engine snapshots new demotion events after each step and
  attributes them to the serving replica, so ``stats()`` names exactly
  which replicas are degraded and why while they keep serving.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque

import numpy as np

from repro.core import guard, spans
from repro.testing.load import TraceRecorder

__all__ = ["QueueFull", "BucketGrid", "Replica", "ServingEngine",
           "replay", "pow2_buckets", "jit_replica_fn", "chunk_rows",
           "CHUNK_BYTES"]

# Input bytes a replica forward copies to the device in one piece
# (DESIGN.md §15): a larger batch is split into chunks of at most this
# size, so the program on one chunk runs while the next one lands.
CHUNK_BYTES = 5 << 20


class QueueFull(RuntimeError):
    """Raised by :meth:`ServingEngine.submit` when the bounded request
    queue is at capacity — the backpressure signal (shed or retry
    upstream; the engine never buffers unboundedly)."""


def pow2_buckets(max_batch: int) -> tuple[int, ...]:
    """The default bucket grid: powers of two up to (and including)
    ``max_batch`` — ``pow2_buckets(8) == (1, 2, 4, 8)``, and a non-power
    ``max_batch`` is appended as its own bucket (``(1, 2, 4, 6)`` for
    6) so the configured serving batch always has an exact program."""
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    buckets = []
    b = 1
    while b < max_batch:
        buckets.append(b)
        b *= 2
    buckets.append(max_batch)
    return tuple(buckets)


@dataclasses.dataclass(frozen=True)
class BucketGrid:
    """The fixed grid of serving batch sizes (ascending, unique).

    One compiled program exists per bucket; :meth:`bucket_for` is the
    entire batching policy — exact and deterministic: the smallest
    bucket that fits ``n`` requests (a request count above ``max_bucket``
    is the caller's split problem; the engine never takes more than
    ``max_bucket`` per step)."""

    buckets: tuple[int, ...]

    @classmethod
    def build(cls, buckets) -> "BucketGrid":
        bs = sorted({int(b) for b in buckets})
        if not bs:
            raise ValueError("bucket grid cannot be empty")
        if bs[0] < 1:
            raise ValueError(f"buckets must be >= 1, got {bs[0]}")
        return cls(buckets=tuple(bs))

    @property
    def max_bucket(self) -> int:
        return self.buckets[-1]

    def bucket_for(self, n: int) -> int:
        """Smallest bucket >= ``n`` (raises for n < 1 or n > max)."""
        if n < 1:
            raise ValueError(f"need at least 1 request, got {n}")
        for b in self.buckets:
            if b >= n:
                return b
        raise ValueError(
            f"{n} requests exceed the largest bucket {self.max_bucket}; "
            "the engine drains at most max_bucket per step")

    def pad_rows(self, n: int) -> int:
        """How many padding rows bucket selection adds for ``n`` real
        requests."""
        return self.bucket_for(n) - n


@dataclasses.dataclass(frozen=True)
class Replica:
    """One serving replica: a name (for stats/guard attribution) and a
    batch forward ``fn(batch) -> outputs`` (row i of the output serves
    request i).  Replicas are data-parallel copies — the engine
    dispatches whole buckets to whichever is free."""

    name: str
    fn: object     # Callable[[np.ndarray], array-like]


def chunk_rows(shape, itemsize: int, target_bytes: int | None = None) -> int:
    """Rows of each chunk a replica forward splits a batch of ``shape``
    (``itemsize`` bytes an element) into: the largest power of two that
    divides the batch and keeps a chunk within ``target_bytes``
    (:data:`CHUNK_BYTES` when ``None``), at least one row.  A batch of
    one row, or of no more than ``target_bytes``, stays whole."""
    n = int(shape[0])
    target = CHUNK_BYTES if target_bytes is None else int(target_bytes)
    row = int(itemsize) * int(np.prod(shape[1:], dtype=np.int64))
    if n <= 1 or n * row <= target:
        return n
    rows = 1
    while n % (2 * rows) == 0 and 2 * rows * row <= target:
        rows *= 2
    return rows


def jit_replica_fn(call, params, device=None):
    """A replica's batch forward from a jitted ``call(params, x)`` that
    returns one array: numpy batch in, numpy rows out, in four spans
    (``core.spans``): ``replica.h2d`` (the copy of the batch onto
    ``device``, the default device when ``None``, is started),
    ``replica.dispatch`` (the program, and the copy of its output back,
    are queued), ``replica.wait`` (until the output is ready; its child
    ``replica.h2d_wait`` until the batch has landed) and ``replica.d2h``
    (the rest of the copy back).  Both copies run while the host waits:
    the copy back is queued behind the program, not started after it.

    A batch that :func:`chunk_rows` splits runs as a pipeline
    (DESIGN.md §15): every chunk's copy is started, then the program is
    queued once per chunk, each waiting on its own chunk alone, so it
    runs while the later chunks land; ``replica.h2d_wait`` is the wait
    for the first chunk, the one copy left exposed, and the rows are
    joined in order."""
    import jax

    def fn(batch):
        batch = np.asarray(batch)
        rows = chunk_rows(batch.shape, batch.itemsize)
        if rows != len(batch):
            return _chunked(batch, rows)
        with spans.span("replica.h2d"):
            x = jax.device_put(batch, device)
        with spans.span("replica.dispatch"):
            y = call(params, x)
            y.copy_to_host_async()
        with spans.span("replica.wait"):
            with spans.span("replica.h2d_wait"):
                x.block_until_ready()
            y.block_until_ready()
        with spans.span("replica.d2h"):
            return np.asarray(y)

    def _chunked(batch, rows):
        with spans.span("replica.h2d"):
            xs = jax.device_put([batch[i:i + rows]
                                 for i in range(0, len(batch), rows)],
                                device)
        with spans.span("replica.dispatch"):
            ys = [call(params, x) for x in xs]
            for y in ys:
                y.copy_to_host_async()
        with spans.span("replica.wait"):
            with spans.span("replica.h2d_wait"):
                xs[0].block_until_ready()
            for y in ys:
                y.block_until_ready()
        with spans.span("replica.d2h"):
            return np.concatenate([np.asarray(y) for y in ys])
    return fn


class ServingEngine:
    """Continuous batching over a bucket grid with bounded queueing,
    multi-replica dispatch and guard-aware degradation reporting.

    The engine is clock-agnostic: every mutating entry point takes
    ``now`` (seconds on the caller's clock).  Thread-safe for the
    asyncio front end (one lock guards the queue and bookkeeping; the
    forward itself runs outside the lock).
    """

    def __init__(self, replicas, buckets, *, max_queue: int = 1024,
                 pad_fill: float = 0.0, topo=None, fused: bool = False,
                 tune_kwargs: dict | None = None, input_shape=None,
                 recorder: TraceRecorder | None = None) -> None:
        self.replicas = list(replicas)
        if not self.replicas:
            raise ValueError("need at least one replica")
        self.grid = buckets if isinstance(buckets, BucketGrid) \
            else BucketGrid.build(buckets)
        if max_queue < self.grid.max_bucket:
            raise ValueError(
                f"max_queue {max_queue} < max bucket "
                f"{self.grid.max_bucket}: the queue could never fill a "
                "full batch")
        self.max_queue = int(max_queue)
        self.pad_fill = float(pad_fill)
        self.topo = topo
        self.fused = fused
        self.tune_kwargs = dict(tune_kwargs or {})
        self.input_shape = tuple(input_shape) if input_shape else None
        self.recorder = recorder or TraceRecorder()

        self._lock = threading.Lock()
        self._queue: deque = deque()      # (rid, x, t_enqueue)
        self._rr = 0                      # round-robin replica cursor
        self._warm: set[int] = set()
        self.cold_tunes = 0
        self.served = 0
        self.steps_taken = 0              # next step id
        self._bucket_counts: dict[int, int] = {}
        self._replica_served = {r.name: 0 for r in self.replicas}
        self._replica_events: dict[str, list[dict]] = \
            {r.name: [] for r in self.replicas}
        self._guard_seq = max([e["seq"] for e in guard.events()],
                              default=-1)

    # -- construction -------------------------------------------------------

    @classmethod
    def for_topology(cls, topo, params, *, buckets, n_replicas: int = 1,
                     mesh=None, rules=None, fused: bool = False,
                     fuse_plan=None, jit: bool = True,
                     distribute: bool = False, **kw) -> "ServingEngine":
        """Build an engine serving a conv topology (``list[ConvLayer]``)
        through ``models.layers.cnn_apply_from_layers``.

        ``n_replicas`` data-parallel replicas share ``params`` (or, with
        ``distribute=True``, each holds a copy placed on its own local
        device — the PR 4 device-mesh leg).  ``mesh``/``rules`` route
        every conv through the sharded halo-exchange path *within* each
        replica (spatial parallelism inside a replica composes with
        data parallelism across replicas).  ``fused=True`` serves the
        residency-group megakernels (guarded: a failing group demotes
        to per-layer execution per DESIGN.md §9).  Each replica's
        forward is :func:`jit_replica_fn`; a distributed replica takes
        its batch, every chunk of it, straight onto its own device."""
        import jax
        from repro.models import layers as mlayers

        topo = list(topo)

        def fwd(p, x):
            return mlayers.cnn_apply_from_layers(
                p, topo, x, mesh=mesh, rules=rules, fused=fused,
                fuse_plan=fuse_plan)

        call = jax.jit(fwd) if jit else fwd
        devices = jax.devices() if distribute else []
        replicas = []
        for i in range(n_replicas):
            if devices:
                dev = devices[i % len(devices)]
                p_i = jax.device_put(params, dev)
            else:
                dev, p_i = None, params
            replicas.append(Replica(name=f"replica{i}",
                                    fn=jit_replica_fn(call, p_i, dev)))
        first = topo[0]
        return cls(replicas, buckets, topo=topo, fused=fused,
                   input_shape=(first.ifmap, first.ifmap,
                                first.in_channels), **kw)

    # -- request intake -----------------------------------------------------

    def pending(self) -> int:
        with self._lock:
            return len(self._queue)

    def submit(self, rid: int, x, *, now: float) -> None:
        """Enqueue one request.  Raises :class:`QueueFull` at capacity
        (backpressure: the queue depth is bounded by ``max_queue``,
        always)."""
        with spans.span("engine.submit"), self._lock:
            if len(self._queue) >= self.max_queue:
                raise QueueFull(
                    f"queue at capacity ({self.max_queue}); retry or "
                    "shed upstream")
            self.recorder.enqueue(rid, now)
            self._queue.append((rid, np.asarray(x), now))
            self.recorder.note_queue_depth(len(self._queue))

    def head_enqueue_time(self) -> float | None:
        """Enqueue timestamp of the oldest queued request (None when
        idle) — the earliest instant a batch could form."""
        with self._lock:
            return self._queue[0][2] if self._queue else None

    # -- serving ------------------------------------------------------------

    def _pad_batch(self, xs: list[np.ndarray], bucket: int) -> np.ndarray:
        batch = np.stack(xs)
        if len(xs) < bucket:
            pad = np.full((bucket - len(xs),) + batch.shape[1:],
                          self.pad_fill, batch.dtype)
            batch = np.concatenate([batch, pad])
        return batch

    def _ensure_warm(self, bucket: int) -> None:
        """First service of a non-prewarmed bucket tunes it on the spot
        — a *cold tune*, counted so the benchmark can assert prewarm
        coverage was complete."""
        if bucket in self._warm:
            return
        self.cold_tunes += 1
        if self.topo is not None:
            from repro.core import autotune
            for n in self._program_batches([bucket]):
                autotune.tune_network(self.topo, n=n, **self.tune_kwargs)
                if self.fused:
                    autotune.tune_fused_network(self.topo, n=n,
                                                **self.tune_kwargs)
        self._warm.add(bucket)

    def _chunk_rows(self, bucket: int) -> int:
        """Rows of each program a forward of ``bucket`` runs: the
        :func:`chunk_rows` rule over the f32 batch ``prewarm`` compiles,
        for the replicas of a topology (:func:`jit_replica_fn`); the
        whole bucket for any other replica."""
        if self.topo is None or self.input_shape is None:
            return bucket
        return chunk_rows((bucket,) + self.input_shape,
                          np.dtype(np.float32).itemsize)

    def _program_batches(self, buckets) -> list[int]:
        """Every batch size a program runs at for ``buckets``: each
        bucket and the rows of its chunks, ascending."""
        return sorted({m for b in buckets
                       for m in (b, self._chunk_rows(b))})

    def _collect_guard(self, replica_name: str) -> None:
        new = [e for e in guard.events() if e["seq"] > self._guard_seq]
        if new:
            self._guard_seq = new[-1]["seq"]
            self._replica_events[replica_name].extend(new)

    def step(self, *, now: float, replica: int | None = None,
             service_model=None) -> tuple[list[tuple[int, np.ndarray]],
                                          float]:
        """Serve one batch from the queue head.

        Drains up to ``max_bucket`` requests FIFO, executes the padded
        bucket on ``replica`` (or the round-robin next), and returns
        ``([(rid, result_row), ...], service_time_s)``.  With
        ``service_model`` (a ``bucket -> seconds`` callable) the
        returned/recorded service time is injected — the deterministic
        virtual-clock mode; otherwise it is the measured wall time of
        the forward.  An empty queue returns ``([], 0.0)``.

        A step that serves a batch takes the next step id
        (``steps_taken - 1`` after it); its spans (``core.spans``) are
        ``engine.step`` around ``engine.batch`` (queue pop, recorder
        stamps, stack and pad), ``replica.forward`` (the replica call)
        and ``engine.finish`` (guard events, completion stamps, rows)."""
        with spans.span("engine.step") as sp:
            with spans.span("engine.batch"):
                with self._lock:
                    if not self._queue:
                        return [], 0.0
                    take = min(len(self._queue), self.grid.max_bucket)
                    reqs = [self._queue.popleft() for _ in range(take)]
                    if replica is None:
                        replica = self._rr % len(self.replicas)
                    self._rr += 1
                    sid = self.steps_taken
                    self.steps_taken += 1
                sp.set_step(sid)
                bucket = self.grid.bucket_for(take)
                self._ensure_warm(bucket)
                rep = self.replicas[replica]
                for rid, _, _ in reqs:
                    self.recorder.batch(rid, step=sid, bucket=bucket,
                                        replica=rep.name, batch_real=take)
                    self.recorder.execute(rid, now)
                batch = self._pad_batch([x for _, x, _ in reqs], bucket)
            with spans.span("replica.forward"):
                t0 = time.perf_counter()
                out = np.asarray(rep.fn(batch))
                measured = time.perf_counter() - t0
            with spans.span("engine.finish"):
                self._collect_guard(rep.name)
                dt = float(service_model(bucket)) if service_model \
                    else measured
                done = now + dt
                results = []
                for i, (rid, _, _) in enumerate(reqs):
                    self.recorder.complete(rid, done)
                    results.append((rid, out[i]))
                with self._lock:
                    self.served += take
                    self._bucket_counts[bucket] = \
                        self._bucket_counts.get(bucket, 0) + 1
                    self._replica_served[rep.name] += take
        return results, dt

    def forward_one(self, x) -> np.ndarray:
        """The single-request tuned forward (bucket 1 on replica 0) —
        the differential oracle every served row must bit-match."""
        batch = self._pad_batch([np.asarray(x)], self.grid.bucket_for(1))
        return np.asarray(self.replicas[0].fn(batch))[0]

    # -- prewarm ------------------------------------------------------------

    def prewarm(self, *, tune: bool = True, compile: bool = True) -> dict:
        """Make every (bucket, replica) path hot before the first
        request: sweep the autotune cache over the bucket grid and the
        rows of each bucket's chunks (:func:`chunk_rows`;
        :func:`repro.core.autotune.prewarm_buckets` — skipped for
        engines without a topology) and run one throwaway forward per
        bucket per replica to populate the JIT cache (a chunked bucket
        compiles its chunk's program).  Returns the tune records by
        batch size."""
        records: dict = {}
        if tune and self.topo is not None:
            from repro.core import autotune
            records = autotune.prewarm_buckets(
                self.topo, self._program_batches(self.grid.buckets),
                fused=self.fused, **self.tune_kwargs)
        if compile and self.input_shape is not None:
            for b in self.grid.buckets:
                zeros = np.zeros((b,) + self.input_shape, np.float32)
                for rep in self.replicas:
                    rep.fn(zeros)
        self._warm.update(self.grid.buckets)
        return records

    # -- reporting ----------------------------------------------------------

    def stats(self) -> dict:
        """Serving counters + per-replica degradation report.  A replica
        with guard events kept serving on a fallback tier — degraded,
        labeled, never silent (DESIGN.md §9/§10).  ``replica_chunks``:
        {bucket: programs a forward of that bucket runs}, from the
        :func:`chunk_rows` rule every forward takes (DESIGN.md §15).
        ``tap_folded`` ({layer: K*K*Cin}) and ``depthwise`` ({layer: C})
        name the topology's convs whose route (``ops.conv_route``,
        DESIGN.md §16) folds their taps or runs the depthwise VPU
        kernel; ``fold_strided`` ({layer: stride}) the folded convs
        whose views are taken by selection, at stride > 1 (§15);
        ``mxu_strided`` ({layer: stride}) the convs whose taps the MXU
        kernel loads with a stride (``netplan.mxu_strided``, §17)."""
        from repro.core.netplan import layer_route, mxu_strided
        dtype = self.tune_kwargs.get("dtype", "float32")
        layers = {layer.name: layer for layer in self.topo or ()}
        routes = {name: layer_route(layer, dtype=dtype)
                  for name, layer in layers.items()}
        with self._lock:
            per_replica = {
                name: {"served": self._replica_served[name],
                       "degraded": bool(self._replica_events[name]),
                       "guard_events": [dict(e) for e in
                                        self._replica_events[name]]}
                for name in self._replica_served}
            return {
                "served": self.served,
                "pending": len(self._queue),
                "cold_tunes": self.cold_tunes,
                "prewarmed_buckets": sorted(self._warm),
                "bucket_batches": dict(sorted(
                    self._bucket_counts.items())),
                "max_queue_depth": self.recorder.max_queue_depth,
                "rejected": len(self.recorder.rejected),
                "replicas": per_replica,
                "replica_chunks": {b: b // self._chunk_rows(b)
                                   for b in self.grid.buckets},
                "tap_folded": {name: r.w_shape[2]
                               for name, r in routes.items()
                               if r.kind == "folded"},
                "fold_strided": {name: layers[name].stride
                                 for name, r in routes.items()
                                 if r.kind == "folded"
                                 and layers[name].stride > 1},
                "depthwise": {name: r.x_shape[3]
                              for name, r in routes.items()
                              if r.kind == "depthwise"},
                "mxu_strided": mxu_strided(list(layers.values()),
                                           dtype=dtype),
            }

# ---------------------------------------------------------------------------
# Deterministic open-loop replay
# ---------------------------------------------------------------------------

def replay(engine: ServingEngine, trace, *, service_model=None,
           start: float = 0.0):
    """Event-driven replay of an arrival trace against the engine.

    ``trace`` is an iterable of ``(t_arrival, rid, x)``; arrivals are
    open-loop (they ignore service progress, like real traffic).  The
    loop advances a virtual timeline: a batch starts at
    ``max(earliest free replica, head-of-queue arrival)``, and every
    request arriving at or before that instant joins the queue first —
    continuous batching, replicas kept busy whenever work is queued.
    Arrivals that hit a full queue are rejected (recorded, not raised:
    open-loop load sheds at the backpressure bound).

    With ``service_model`` (``bucket -> seconds``) the whole timeline is
    deterministic — same trace, same results, same timestamps; without
    it, service times are the measured wall time of each real forward
    (the benchmark mode: real kernels under a deterministic arrival
    pattern).

    Returns ``(results, rejected)``: ``{rid: output_row}`` for every
    served request and the rid list of shed ones.  Lifecycle timestamps
    land in ``engine.recorder``.
    """
    trace = sorted(trace, key=lambda e: e[0])
    free = [float(start)] * len(engine.replicas)
    results: dict[int, np.ndarray] = {}
    rejected: list[int] = []
    i, n = 0, len(trace)

    def admit(j: int) -> None:
        t, rid, x = trace[j]
        try:
            engine.submit(rid, x, now=t)
        except QueueFull:
            engine.recorder.reject(rid, t)
            rejected.append(rid)

    while i < n or engine.pending():
        if engine.pending() == 0:
            admit(i)
            i += 1
            continue
        r = int(np.argmin(free))
        t_start = max(free[r], engine.head_enqueue_time())
        # continuous batching: arrivals landing before this batch can
        # start join it (queue permitting)
        while i < n and trace[i][0] <= t_start:
            admit(i)
            i += 1
        out, dt = engine.step(now=t_start, replica=r,
                              service_model=service_model)
        free[r] = t_start + dt
        for rid, y in out:
            results[rid] = y
    return results, rejected
