"""One run of one cell: set-up, warm-up, the measured window, the check
against the plain reference, and the metrics.

The order follows what each number must leave out: set-up (weights,
plan tuning, compiles, a warm-up stretch of the cell's own traffic) is
timed as ``setup_s``; the window runs with no compile, cold tune or
guard demotion allowed (each one counts as a failure); the device's
peak memory is read before the reference runs; and the reference runs
only after the program's state is freed.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import sys

import numpy as np

import cellspec
import devtrace
import inputs
import loadgen

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/backend_compile_duration")
#: float32 bytes of activations the reference may hold per block
REFERENCE_BUDGET = 4e9


class NoAccelerator(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


def configure_caches(root: str) -> None:
    """Compile cache and autotune cache at fixed paths in the checkout
    (a compile cache given from outside is kept).  Call before JAX is
    imported."""
    cache = os.path.join(root, "artifacts", "bench")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(cache, "jax_cache"))
    os.environ["REPRO_CONVTUNE_CACHE"] = os.path.join(cache,
                                                      "convtune.json")
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise FileNotFoundError(f"the program is not in this checkout "
                                f"({src}/repro is missing)")
    if src not in sys.path:
        sys.path.insert(0, src)


def devices(chips: int, rehearse: bool):
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    if rehearse:
        # the CPU's cache entries only warn when read back; a rehearsal
        # compiles afresh
        jax.config.update("jax_enable_compilation_cache", False)
        return devs
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX reports platform "
                            f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX reports "
                            f"{len(devs)}")
    from repro.kernels.runtime import on_tpu
    if not on_tpu():
        raise NoAccelerator("runtime.on_tpu() is False on a TPU: the "
                            "kernels would run in interpret mode")
    return devs


def netspec(cell, rehearse: bool) -> cellspec.NetSpec:
    if not rehearse:
        return cellspec.NetSpec(cell.config)
    r = cell.config["rehearsal"]
    return cellspec.NetSpec(cell.config, image=r["image"],
                            channel_divisor=r["channel_divisor"])


class CompileCounter:
    """Counts traces and backend compiles while ``on``."""

    def __init__(self) -> None:
        self.on = False
        self.count = 0

    def __call__(self, event: str, duration: float, **kw) -> None:
        if self.on and event in COMPILE_EVENTS:
            self.count += 1


def instrument(engine, host: devtrace.HostLog) -> None:
    """The benchmark's host spans around the calls into the program:
    enqueue, one engine step, and the replica's forward (host to device,
    the jitted network, device to host)."""
    engine.submit = host.wrap("bench.submit", engine.submit)
    engine.step = host.wrap("bench.engine_step", engine.step)
    engine.replicas = [dataclasses.replace(r, fn=host.wrap("bench.forward",
                                                           r.fn))
                       for r in engine.replicas]


@dataclasses.dataclass
class Prepared:
    spec: cellspec.NetSpec
    engine: object
    runner: loadgen.TrafficRunner
    pool: np.ndarray
    host: devtrace.HostLog


def prepare(cell, seed: int, rehearse: bool, wrap_engine=None) -> Prepared:
    """Weights, images, the system under test (tuned and compiled) and
    the runner of the cell's traffic.  ``wrap_engine`` lets a test break
    the timed path underneath."""
    spec = netspec(cell, rehearse)
    traffic = cell.traffic
    params = inputs.make_params(spec, seed)
    pool = inputs.make_pool(spec, int(traffic["pool"]), seed)
    system = cellspec.load_module("systems", cell.config["entry"])
    engine = system.build(spec, params, traffic["buckets"])
    if wrap_engine is not None:
        wrap_engine(engine)
    host = devtrace.HostLog()
    instrument(engine, host)
    return Prepared(spec, engine, loadgen.TrafficRunner(traffic, engine, pool,
                                                 seed), pool, host)


def answers_of(window) -> list:
    """``[(pool index, answer)]`` of every answered request."""
    out = []
    for r in window.requests:
        if r.done is None:
            continue
        if r.n == 1:
            out.append((r.item, np.asarray(r.out)))
        else:
            out.extend((r.item + j, np.asarray(r.out[j]))
                       for j in range(r.n))
    return out


def reference_outputs(spec, seed: int, pool, items, precision="highest"):
    """``{item: reference output}`` for the pool items, in blocks that
    fit (weights made again from the seed)."""
    ref = cellspec.load_module("reference", spec.reference)
    params = inputs.make_params(spec, seed)
    fwd = ref.make_forward(spec, precision)
    widest = max(n.size * n.size * n.channels for n in spec.nodes)
    block = int(max(1, min(16, REFERENCE_BUDGET // (6 * 4 * widest))))
    items = sorted(set(items))
    out = {}
    for i in range(0, len(items), block):
        chunk = items[i:i + block]
        idx = chunk + [chunk[-1]] * (block - len(chunk))   # one shape
        y = np.asarray(fwd(params, pool[idx]))
        out.update((it, y[j]) for j, it in enumerate(chunk))
    return out


def max_rel_err(answers, refs) -> float:
    """Max over answers of ``max|answer - ref|`` over the answer's scale:
    ``max|ref|`` of that answer, or the median of that over the compared
    answers where it is larger (an output map that the final ReLU left
    all but zero would otherwise divide by nearly nothing)."""
    peaks = {item: float(np.abs(refs[item]).max())
             for item in {i for i, _ in answers}}
    floor = float(np.median(list(peaks.values()))) if peaks else 0.0
    worst = 0.0
    for item, a in answers:
        r = refs[item]
        if a.shape != r.shape:
            return float("inf")
        scale = max(peaks[item], floor, 1e-30)
        err = float(np.abs(a.astype(np.float64) - r).max()) / scale
        if not np.isfinite(err):
            return float("inf")
        worst = max(worst, err)
    return worst


@dataclasses.dataclass
class RunData:
    """What the metric readers read (one file each under
    ``bench/metrics/``)."""

    spec: cellspec.NetSpec
    setup_s: float
    window_s: float
    completed: int               # images answered in the window
    latencies_s: np.ndarray      # every request of the window (inf: none)
    queue_waits_s: list | None   # the engine's enqueue -> execute
    steps: list                  # batch size of every forward run
    peak: dict | None
    trace: devtrace.Reduction | None


def _steps(before: dict, after: dict) -> list:
    out = []
    for b, n in after.items():
        out += [int(b)] * (n - before.get(b, 0))
    return out


def run(cell, *, seed: int, seconds: float, trace: bool, rehearse: bool,
        t_start: float, log=print, wrap_engine=None) -> dict:
    """One run; returns the result object (without printing it)."""
    import jax
    from repro.core import guard

    devs = devices(cell.chips, rehearse)
    dev = devs[0]
    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    try:
        prep = prepare(cell, seed, rehearse, wrap_engine)
        spec, engine, runner, host = (prep.spec, prep.engine, prep.runner,
                                      prep.host)
        runner.run(float(cell.traffic.get("warmup_s", 1.0)))
        if trace:
            devtrace.warm_marker()
        setup_s = loadgen.clock() - t_start
        log(f"set-up {setup_s:.3f} s; window {seconds} s")

        guard_before = len(guard.events())
        stats0 = engine.stats()
        trace_dir = os.path.join(cell.root, "artifacts", "bench", "trace",
                                 cell.name)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            # the host tracer off: see devtrace for why and for the
            # benchmark's own spans that stand in for it
            opts.python_tracer_level = 0
            opts.host_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            host.mark()
        host.on = trace
        counter.on = True
        try:
            window = runner.run(seconds, around=host.measure)
        finally:
            counter.on = host.on = False
            if trace:
                jax.profiler.stop_trace()
                host.dump(trace_dir)
        stats1 = engine.stats()
        runner.close()
    finally:
        jax.monitoring.unregister_event_duration_listener(counter)
    guard_events = len(guard.events())
    guard_in_window = guard_events - guard_before
    cold = stats1["cold_tunes"] - stats0["cold_tunes"]
    mem = [d.memory_stats() or {} for d in devs[:cell.chips]]
    # a TPU keeps a program's temporaries in memory reserved apart from
    # its buffers: the peak a program holds is the sum of the two
    memory_peak = max(int(m.get("peak_bytes_in_use", 0))
                      + int(m.get("peak_bytes_reserved", 0)) for m in mem)

    reqs = window.requests
    kind = cell.traffic["kind"]
    steps = ([int(cell.traffic["batch"])] * len(reqs)
             if kind == "batch_loop" else
             _steps(stats0["bucket_batches"], stats1["bucket_batches"]))
    t0, t1 = window.t0, window.t1
    waits = None
    if kind != "batch_loop":
        waits = [r.queue_wait for r in engine.recorder.records.values()
                 if t0 <= r.t_enqueue <= t1 and r.t_execute is not None]
    start = (lambda r: r.due) if kind == "open" else (lambda r: r.sent)
    lat = np.asarray([r.done - start(r) if r.done is not None else np.inf
                      for r in reqs for _ in range(r.n)])
    attempted = sum(r.n for r in reqs)
    shed = sum(r.n for r in reqs if r.shed)
    lost = sum(r.n for r in reqs if r.done is None and not r.shed)
    completed = attempted - shed - lost
    answers = answers_of(window)
    lateness = [r.sent - r.due for r in reqs] if kind == "open" else []
    pool = prep.pool

    # the program's state goes before the reference runs
    del prep, engine, runner, window, reqs
    gc.collect()

    limit = float(cell.config["check"]["limit"])
    refs = reference_outputs(spec, seed, pool, [a[0] for a in answers])
    err = max_rel_err(answers, refs)
    del refs, answers
    checks = {
        cell.config["check"]["number"]: {"value": err, "limit": limit},
        "unanswered": {"value": lost, "limit": 0},
        "guard_events": {"value": guard_events, "limit": 0},
        "compiles_in_window": {"value": counter.count, "limit": 0},
        "cold_tunes_in_window": {"value": cold, "limit": 0},
    }
    correct = bool(err <= limit and lost == 0 and guard_events == 0
                   and completed > 0)
    failed = shed + lost + counter.count + cold + guard_in_window

    reduction = None
    if trace:
        reduction = devtrace.reduce_dir(
            trace_dir, kernel_names=[n.name for n in spec.convs])
    peak = None
    if not rehearse:
        peak = cellspec.peak_for(dev.device_kind, spec.dtype)
    data = RunData(spec=spec, setup_s=setup_s, window_s=t1 - t0,
                   completed=completed, latencies_s=lat,
                   queue_waits_s=waits, steps=steps, peak=peak,
                   trace=reduction)
    entries = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in entries:
        v = cellspec.load_module("metrics", m["name"]).read(data)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": memory_peak}
    if reduction is not None:
        device.update(busy_s=reduction.busy_s, window_s=reduction.window_s)
    result = {"correct": correct, "attempted": attempted, "failed": failed}
    if rehearse:
        result["metrics"] = {}
        result["rehearsal_metrics"] = {f"cpu.{k}": v
                                       for k, v in metrics.items()}
    else:
        result["metrics"] = metrics
    result["device"] = device
    if reduction is not None:
        result["breakdown"] = {"device_ops": reduction.top_ops,
                               "idle_gaps": reduction.idle_gaps}
    notes = {"setup_s": setup_s, "window_s": t1 - t0,
             "completed": completed, "shed": shed, "steps": len(steps),
             "rows": int(sum(steps)), "memory_stats": mem[0]}
    if lateness:
        notes["generator_late_p95_ms"] = float(
            np.percentile(lateness, 95) * 1e3)
    if kind != "batch_loop":
        notes["bucket_batches"] = {
            str(b): n - stats0["bucket_batches"].get(b, 0)
            for b, n in stats1["bucket_batches"].items()}
    result["notes"] = notes
    result["checks"] = checks
    return result
