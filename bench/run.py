"""Run one benchmark cell once and print its result.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1> [--rehearse]

The cell (``workloads`` of ``BENCHMARK.json``) names a configuration
file, a traffic file (``bench/traffic/<traffic>.json``) and its metrics
(``bench/metrics/<metric>.py``).  The run loads, warms up, measures for
``--seconds``, checks every answer of the window against the plain
reference (``bench/reference/``), and prints one JSON object as the last
line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics; with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window), ``device``
and, traced, ``breakdown``; the numbers compared come last, under
``checks``, and again as the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  ``--rehearse`` runs the cell on any
backend at the configuration's tiny rehearsal size and reports what it
read under ``rehearsal_metrics`` with a ``cpu.`` prefix, never under a
metric's name.
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

import cellspec  # noqa: E402
import harness  # noqa: E402


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def finite(obj):
    """JSON has no infinity: a number that is not finite (an answer
    that is NaN or of the wrong shape) is written as 1e308."""
    if isinstance(obj, float) and not math.isfinite(obj):
        return 1e308
    if isinstance(obj, dict):
        return {k: finite(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [finite(v) for v in obj]
    return obj


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="any backend, tiny size, no metric under its "
                         "own name")
    args = ap.parse_args(argv)
    root = cellspec.ROOT
    try:
        cell = cellspec.find_cell(args.workload, root)
        harness.configure_caches(root)
    except (FileNotFoundError, KeyError) as e:
        say(f"FAIL: {type(e).__name__}: {e}")
        return 2
    try:
        result = harness.run(cell, seed=args.seed, seconds=args.seconds,
                             trace=bool(args.trace),
                             rehearse=args.rehearse, t_start=T_START,
                             log=say)
    except harness.NoAccelerator as e:
        say(f"FAIL: {e}")
        return 2
    result = finite(result)
    say(f"correct {result['correct']}; the numbers compared:")
    for name, c in result["checks"].items():
        say(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
