"""A traced run read by the program's own names: Pallas kernel time by
the name each kernel carries, and device idle time by the program's
host spans (``repro.core.spans``).

``devtrace`` reduces the trace by the benchmark's own names.  This reads
the same trace directory (``artifacts/bench/trace/<cell>/``: the
``.xplane.pb`` and the harness's ``bench_host.json``, whose markers put
host times on the trace's clock) and adds:

* ``kernel_s_by_layer``: the summed device time of the Pallas kernels in
  the window, grouped by the kernel's name.  The program names each
  conv kernel after its layer, and the compiled custom call carries the
  name (``%conv1.13 = f32[...] custom-call(...)`` is a kernel of
  ``conv1``), so two kernels of one conv sum under one name whatever
  their count.  A program that names no kernel gives names that match
  no layer (``trim_conv2d``).
* ``idle_by_span``: every instant of the window at which no op ran on
  the device, given to the innermost program span the host was in
  (``no program span`` when in none), summed for every label.
* ``spans``: the program's span records of the window (those of the
  steps that started in it, and the spans of no step that started in
  it), as ``(name, start_ns, end_ns, step)`` on the host clock.

The program's spans are read from its in-memory log, so they exist
only where the log was on during the window: a run whose spans are off
gives an empty list, and the readers of span metrics give nothing.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re

import devtrace

NO_SPAN = "no program span"
TRACE_ROOT = os.path.join("artifacts", "bench", "trace")
#: ``%<name>[.<n>] = ...``: an instruction's name in the trace's op text
_OP_NAME = re.compile(r"%(\S+?)(?:\.\d+)?\s*=")


@dataclasses.dataclass
class ProgramTrace:
    window_s: float
    kernel_s_by_layer: dict      # {kernel name: seconds}, all devices
    idle_by_span: dict           # {span label: seconds}, device mean
    spans: list                  # [(name, start_ns, end_ns, step)], host


def kernel_name(op_text: str) -> str | None:
    """The instruction name of an op's trace text, without XLA's
    ``.<n>`` suffix."""
    m = _OP_NAME.match(op_text)
    return m.group(1) if m else None


def in_window(records, window) -> list:
    """The window's span records (``window`` in host ns): every span of
    a step whose ``engine.step`` started in it, and the spans of no step
    that started in it."""
    w0, w1 = window
    steps = {r[3] for r in records
             if r[0] == "engine.step" and w0 <= r[1] < w1}
    return [r for r in records
            if (r[3] in steps if r[3] is not None else w0 <= r[1] < w1)]


def reduce_profile(pd, host: dict, records=()) -> ProgramTrace | None:
    """``pd`` (a ``ProfileData``) over the window of ``host`` (the
    harness's ``bench_host.json``), with the program's span
    ``records``; ``None`` where ``devtrace`` finds nothing to read."""
    planes = [p for p in pd.planes if devtrace.is_device_plane(p.name)]
    if not planes or not host.get("window"):
        return None
    off = devtrace.clock_offset(planes, host["markers"])
    if off is None:
        return None
    w0, w1 = (t + off for t in host["window"])
    spans = in_window(records, host["window"])
    on_trace = sorted(((n, s + off, e + off) for n, s, e, _ in records),
                      key=lambda sp: sp[1])
    starts = [sp[1] for sp in on_trace]
    longest = max((e - s for _, s, e in on_trace), default=0.0)
    by_layer: dict[str, float] = {}
    idle: dict[str, float] = {}
    for plane in planes:
        lines = {ln.name: ln for ln in plane.lines}
        if devtrace.OPS_LINE not in lines:
            continue
        busy = []
        for name, s, e in devtrace._events(lines[devtrace.OPS_LINE]):
            cs, ce = max(s, w0), min(e, w1)
            if ce <= cs:
                continue
            busy.append((cs, ce))
            if devtrace.is_kernel(name):
                layer = kernel_name(name) or name
                by_layer[layer] = by_layer.get(layer, 0.0) + (ce - cs)
        merged = devtrace._union(busy)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            near = on_trace[bisect.bisect_left(starts, g0 - longest):
                            bisect.bisect_left(starts, g1)]
            for lab, d in devtrace._attribute(g0, g1, near):
                lab = NO_SPAN if lab == devtrace.NO_SPAN else lab
                idle[lab] = idle.get(lab, 0.0) + d
    n = len(planes)
    return ProgramTrace(
        window_s=(w1 - w0) / 1e9,
        kernel_s_by_layer={k: v / 1e9 for k, v in by_layer.items()},
        idle_by_span={k: v / 1e9 / n for k, v in idle.items()},
        spans=spans)


def newest_trace_dir(root: str) -> str | None:
    """The directory of the newest trace under the checkout's trace
    root: the run that just ended is the one that wrote it."""
    paths = glob.glob(os.path.join(root, TRACE_ROOT, "*", "**",
                                   "*.xplane.pb"), recursive=True)
    if not paths:
        return None
    newest = max(paths, key=os.path.getmtime)
    rel = os.path.relpath(newest, os.path.join(root, TRACE_ROOT))
    return os.path.join(root, TRACE_ROOT, rel.split(os.sep)[0])


def program_records() -> list:
    """The program's span records, or none where the program has no
    span log."""
    try:
        from repro.core import spans
    except ImportError:
        return []
    return spans.records()


_CACHE: dict = {}


def read_dir(log_dir: str, records=None) -> ProgramTrace | None:
    """Reduce the newest trace under ``log_dir`` with the host log
    beside it and the program's span records (its in-memory log when
    ``records`` is ``None``)."""
    from jax.profiler import ProfileData
    path = devtrace.newest_xplane(log_dir)
    host_path = os.path.join(log_dir, devtrace.HOST_LOG)
    if path is None or not os.path.isfile(host_path):
        return None
    if records is None:
        records = program_records()
    key = (path, os.path.getmtime(path), len(records))
    if key not in _CACHE:
        with open(host_path) as f:
            host = json.load(f)
        _CACHE.clear()
        _CACHE[key] = reduce_profile(ProfileData.from_file(path), host,
                                     records)
    return _CACHE[key]


def for_run(run) -> ProgramTrace | None:
    """The program's reading of a metric reader's run (``RunData``):
    the newest trace of the checkout, if its window is the run's own
    (the trace reduction gives both from the same numbers)."""
    if run.trace is None:
        return None
    import cellspec
    log_dir = newest_trace_dir(cellspec.ROOT)
    if log_dir is None:
        return None
    pt = read_dir(log_dir)
    if pt is None or abs(pt.window_s - run.trace.window_s) > 1e-9:
        return None
    return pt


# ---------------------------------------------------------------------------
# Span arithmetic
# ---------------------------------------------------------------------------

def by_step(spans) -> dict:
    """``{step: {name: [(start_ns, end_ns)]}}`` of the spans that carry a
    step id."""
    out: dict = {}
    for name, s, e, step in spans:
        if step is not None:
            out.setdefault(step, {}).setdefault(name, []).append((s, e))
    return out


def mean_ms(spans, name: str) -> float | None:
    """Mean duration of the spans called ``name``, in ms."""
    d = [e - s for n, s, e, _ in spans if n == name]
    return sum(d) / len(d) / 1e6 if d else None


def self_ms_per_step(spans, parent: str, children) -> float | None:
    """Mean over the steps of ``parent``'s duration less that of its
    ``children`` of the same step, in ms."""
    vals = []
    for names in by_step(spans).values():
        if parent not in names:
            continue
        total = sum(e - s for s, e in names[parent])
        total -= sum(e - s for c in children for s, e in names.get(c, ()))
        vals.append(total)
    return sum(vals) / len(vals) / 1e6 if vals else None
