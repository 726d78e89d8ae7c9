"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

The trace is taken with the profiler's host tracer off: at any level
that keeps ``jax.profiler.TraceAnnotation`` spans it also records every
chunk of the runtime's host-side input transpose, and that slowed the
host of ``vgg16-f32.saturate`` fourfold.  So the benchmark keeps its own
host spans on ``time.perf_counter_ns`` (a :class:`HostLog`, written
beside the trace as ``bench_host.json``) and puts them on the trace's
clock by markers: a tiny program (``bench_marker``) run a few times
just before the window, whose host call times and device start times
give the offset between the two clocks (to within the dispatch latency,
tens of microseconds).

Read with ``jax.profiler.ProfileData`` and nothing else.  Device planes
are those named ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run, and their ``XLA Modules`` line one event per
program run.  From these it computes, over the window:

* busy time: the union of the device's op intervals (averaged over the
  device planes), and the idle share ``1 - busy / window``;
* op time: the summed durations of all ops, and of the Pallas kernels
  among them (``tpu_custom_call``, i.e. Mosaic);
* the ops that took the most time, summed by name, with the Pallas
  kernels of each program run named after the network's convs by their
  order in the run where the counts agree;
* the idle gaps between busy intervals, each instant of them given to
  the innermost benchmark span the host was in (``no bench span`` when
  in none), summed by span name.
"""

from __future__ import annotations

import bisect
import contextlib
import dataclasses
import glob
import json
import os
import statistics
import time

HOST_LOG = "bench_host.json"
MARKER = "bench_marker"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "no bench span"


class HostLog:
    """The benchmark's host spans, kept in memory while ``on`` and
    written beside the trace.  ``list.append`` is atomic, so the
    serving front's executor thread and the event loop may both
    record."""

    def __init__(self) -> None:
        self.on = False
        self.spans: list = []          # (name, start_ns, end_ns)
        self.markers: list = []        # host ns of each marker call
        self.window = None             # (start_ns, end_ns)

    def wrap(self, name: str, fn):
        def wrapped(*a, **kw):
            if not self.on:
                return fn(*a, **kw)
            t0 = time.perf_counter_ns()
            try:
                return fn(*a, **kw)
            finally:
                self.spans.append((name, t0, time.perf_counter_ns()))
        return wrapped

    @contextlib.contextmanager
    def measure(self):
        """Marks the measured window."""
        t0 = time.perf_counter_ns()
        yield
        self.window = (t0, time.perf_counter_ns())

    def mark(self, n: int = 5) -> None:
        """Run the marker program ``n`` times, noting each call's host
        time (compile it first with :func:`warm_marker`)."""
        import jax
        fn, x = jax.jit(bench_marker), _marker_input()
        for _ in range(n):
            t = time.perf_counter_ns()
            fn(x).block_until_ready()
            self.markers.append(t)

    def dump(self, log_dir: str) -> None:
        with open(os.path.join(log_dir, HOST_LOG), "w") as f:
            json.dump({"window": self.window, "markers": self.markers,
                       "spans": self.spans}, f)


def bench_marker(x):
    """The program the two clocks are aligned by."""
    return x + 1.0


def _marker_input():
    import jax.numpy as jnp
    return jnp.zeros((8, 128), jnp.float32)


def warm_marker() -> None:
    """Compile the marker program (set-up, before the trace starts)."""
    import jax
    jax.jit(bench_marker)(_marker_input()).block_until_ready()


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:TPU:")


def is_kernel(name: str) -> bool:
    """A Pallas (Mosaic) kernel: its HLO text names the custom call."""
    return "tpu_custom_call" in name


@dataclasses.dataclass
class Reduction:
    window_s: float
    busy_s: float            # union of op intervals, mean over devices
    op_s: float              # summed op durations, all devices
    kernel_s: float          # summed Pallas kernel durations
    n_devices: int
    n_modules: int           # program runs started in the window
    top_ops: list            # [[name, seconds]], most time first
    idle_gaps: list          # [[label, seconds]], most time first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    @property
    def nonkernel_s(self) -> float:
        return self.op_s - self.kernel_s


def _union(intervals):
    """Merged, sorted ``[(start, end)]``."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _events(line):
    for ev in line.events:
        s = float(ev.start_ns)
        yield ev.name, s, s + float(ev.duration_ns)


def clock_offset(device_planes, markers) -> float | None:
    """Trace clock minus host clock (ns): the median over the marker
    runs of device start minus host call time, pairing both in order."""
    for plane in device_planes:
        for line in plane.lines:
            if line.name != MODULES_LINE:
                continue
            starts = sorted(s for name, s, _ in _events(line)
                            if name.startswith(f"jit_{MARKER}"))
            if starts and len(starts) == len(markers):
                return statistics.median(
                    d - h for d, h in zip(starts, sorted(markers)))
    return None


def reduce_profile(pd, host: dict, *, kernel_names=None, top: int = 10):
    """A :class:`Reduction` of ``pd`` (a ``ProfileData``) over the
    window of ``host`` (a :class:`HostLog`'s dump), or ``None`` when the
    trace holds no device plane or no marker to align the clocks by.
    ``kernel_names`` (the network's conv names in execution order) names
    the Pallas kernels of each program run by position."""
    device_planes = [p for p in pd.planes if is_device_plane(p.name)]
    if not device_planes or not host.get("window"):
        return None
    off = clock_offset(device_planes, host["markers"])
    if off is None:
        return None
    w0, w1 = (t + off for t in host["window"])
    spans = sorted(((n, s + off, e + off) for n, s, e in host["spans"]),
                   key=lambda sp: sp[1])
    starts = [sp[1] for sp in spans]
    longest = max((e - s for _, s, e in spans), default=0.0)
    busy_total, op_ns, kernel_ns, n_modules = 0.0, 0.0, 0.0, 0
    by_name: dict[str, float] = {}
    gaps: dict[str, float] = {}
    for plane in device_planes:
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            continue
        modules = []
        if MODULES_LINE in lines:
            modules = [(s, e) for _, s, e in _events(lines[MODULES_LINE])
                       if w0 <= s < w1]
        n_modules += len(modules)
        ops = []
        for name, s, e in _events(lines[OPS_LINE]):
            cs, ce = max(s, w0), min(e, w1)
            if ce > cs:
                ops.append((cs, ce, name, is_kernel(name)))
        ops.sort()
        names = _kernel_labels(ops, modules, kernel_names)
        for (cs, ce, name, kern), label in zip(ops, names):
            d = ce - cs
            op_ns += d
            if kern:
                kernel_ns += d
            by_name[label] = by_name.get(label, 0.0) + d
        merged = _union([(cs, ce) for cs, ce, _, _ in ops])
        busy_total += sum(e - s for s, e in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                near = spans[bisect.bisect_left(starts, g0 - longest):
                             bisect.bisect_left(starts, g1)]
                for lab, d in _attribute(g0, g1, near):
                    gaps[lab] = gaps.get(lab, 0.0) + d
    n = len(device_planes)
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    ranked_gaps = sorted(gaps.items(), key=lambda kv: -kv[1])[:top]
    return Reduction(
        window_s=(w1 - w0) / 1e9, busy_s=busy_total / n / 1e9,
        op_s=op_ns / 1e9, kernel_s=kernel_ns / 1e9, n_devices=n,
        n_modules=n_modules,
        top_ops=[[k, v / 1e9] for k, v in ranked],
        idle_gaps=[[k, v / 1e9 / n] for k, v in ranked_gaps])


def _short(name: str) -> str:
    """An op's trace name up to its layout: ``%fusion.3 = f32[8,64]``."""
    return name.split("{", 1)[0].strip()


def _kernel_labels(ops, modules, kernel_names):
    """Short op names, with the Pallas kernels of each program run
    renamed ``<conv>:<op>`` by position when their count equals the
    number of convs."""
    names = [_short(name) for _, _, name, _ in ops]
    if not kernel_names or not modules:
        return names
    kidx = [i for i, (_, _, _, kern) in enumerate(ops) if kern]
    kstart = [ops[i][0] for i in kidx]
    for m0, m1 in modules:
        idx = kidx[bisect.bisect_left(kstart, m0):
                   bisect.bisect_left(kstart, m1)]
        if len(idx) == len(kernel_names):
            for i, conv in zip(idx, kernel_names):
                names[i] = f"{conv}:{names[i]}"
    return names


def _attribute(g0, g1, spans):
    """``[(span name, ns)]``: each piece of ``[g0, g1)`` between span
    edges goes to the innermost (shortest) span covering it."""
    live = [(n, max(s, g0), min(e, g1), e - s) for n, s, e in spans
            if s < g1 and e > g0]
    cuts = sorted({g0, g1} | {x for _, s, e, _ in live for x in (s, e)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        inner = [(d, n) for n, s, e, d in live if s <= a and e >= b]
        out.append((min(inner)[1] if inner else NO_SPAN, b - a))
    return out


def newest_xplane(log_dir: str) -> str | None:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def reduce_dir(log_dir: str, **kw):
    """Reduce the newest trace under ``log_dir`` with the host log
    beside it (``None`` if either is missing)."""
    from jax.profiler import ProfileData
    path = newest_xplane(log_dir)
    host = os.path.join(log_dir, HOST_LOG)
    if path is None or not os.path.isfile(host):
        return None
    with open(host) as f:
        log = json.load(f)
    return reduce_profile(ProfileData.from_file(path), log, **kw)
