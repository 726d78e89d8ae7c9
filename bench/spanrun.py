"""Run one cell once with the program's span log on, and print where the
host and the device spent the window by the program's own spans.

    python3 bench/spanrun.py --workload <name> --seed <n> --seconds <s> \\
        [--trace <0|1>] [--keep DIR]

The run is ``bench/run.py``'s (same set-up, window, check and result),
with ``repro.core.spans`` switched on once the system is built.  The
result line adds ``program``: the spans kept and dropped, the window's
steps and the mean of each span per occurrence (ms).  Traced, it also
holds the span metrics (``SPAN_METRICS``, readers under
``bench/metrics/``) under ``metrics``, and under ``program`` the
device's idle time by the innermost program span (every label) and the
Pallas kernel time by kernel name (``progtrace``).  ``--keep`` copies
the trace, the harness's host log and the window's program spans
(``program_spans.json``) to ``DIR``.

Untraced, it measures what the span log costs: compare its end-to-end
metrics with those of ``bench/run.py --trace 0``.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import cellspec  # noqa: E402
import devtrace  # noqa: E402
import harness  # noqa: E402
import progtrace  # noqa: E402
import run as bench_run  # noqa: E402

#: per-layer metrics that read the program's spans: what a harness that
#: turns the span log on around the window would report
SPAN_METRICS = ["engine.host_ms_per_step", "replica.h2d_ms_per_step",
                "replica.device_idle_ms.single"]
PROGRAM_SPANS = "program_spans.json"


def window_of(records, log_dir, seconds: float):
    """The window in host ns: the harness's own where the run was traced,
    else the last ``seconds`` before the last span ended."""
    host = os.path.join(log_dir, devtrace.HOST_LOG) if log_dir else None
    if host and os.path.isfile(host):
        with open(host) as f:
            return tuple(json.load(f)["window"])
    end = max((r[2] for r in records), default=0)
    return (end - int(seconds * 1e9), end + 1)


def summary(records, window) -> dict:
    spans = progtrace.in_window(records, window)
    names = sorted({r[0] for r in spans})
    return {"steps": len(progtrace.by_step(spans)),
            "ms_mean": {n: progtrace.mean_ms(spans, n) for n in names},
            "ms_count": {n: sum(1 for r in spans if r[0] == n)
                         for n in names}}


def keep(log_dir: str, records, window, dst: str) -> None:
    os.makedirs(dst, exist_ok=True)
    shutil.copy(devtrace.newest_xplane(log_dir), dst)
    shutil.copy(os.path.join(log_dir, devtrace.HOST_LOG), dst)
    with open(os.path.join(dst, PROGRAM_SPANS), "w") as f:
        json.dump([list(r) for r in progtrace.in_window(records, window)],
                  f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--keep", default=None,
                    help="copy the trace and the window's spans here")
    ap.add_argument("--rehearse", action="store_true",
                    help="any backend, tiny size (as bench/run.py)")
    args = ap.parse_args(argv)
    root = cellspec.ROOT
    cell = cellspec.find_cell(args.workload, root)
    harness.configure_caches(root)
    from repro.core import spans

    have = {m["name"] for m in cell.per_layer}
    cell = dataclasses.replace(cell, per_layer=cell.per_layer + [
        {"name": n, "unit": "ms"} for n in SPAN_METRICS if n not in have])
    try:
        result = harness.run(cell, seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace), rehearse=args.rehearse,
                             t_start=T_START, log=bench_run.say,
                             wrap_engine=lambda engine: spans.enable())
    except harness.NoAccelerator as e:
        bench_run.say(f"FAIL: {e}")
        return 2
    finally:
        spans.disable()
    records = spans.records()
    log_dir = os.path.join(cell.root, "artifacts", "bench", "trace",
                           cell.name) if args.trace else None
    window = window_of(records, log_dir, args.seconds)
    program = {"records": len(records), "dropped": spans.dropped(),
               **summary(records, window)}
    if args.trace:
        pt = progtrace.read_dir(log_dir, records)
        if pt is not None:
            program["idle_s_by_span"] = dict(sorted(
                pt.idle_by_span.items(), key=lambda kv: -kv[1]))
            program["kernel_s_by_name"] = dict(sorted(
                pt.kernel_s_by_layer.items(), key=lambda kv: -kv[1]))
        if args.keep:
            keep(log_dir, records, window, args.keep)
    result = bench_run.finite(result)
    checks = result.pop("checks")
    result["program"] = program
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
