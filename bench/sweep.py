"""Sweep the offered rate of an open-loop cell to find its knee: the
highest rate served without a growing backlog.

    python3 bench/sweep.py --workload vgg16-f32.poisson --seconds 8 \\
        --rates 100 150 200 250 300

One set-up, then one window per rate on the same system.  Per rate it
prints the offered and answered rates, latency percentiles from the due
time, the engine's batch mix, and the backlog's trend: the median
latency of the last quarter of arrivals over that of the first quarter
(near 1 when sustained, growing with the window when not).  The cell's
traffic file then takes 0.8 x the knee as its rate.  The cell has to
be listed in ``BENCHMARK.json``.
"""

import argparse
import json
import os
import sys
import time

import numpy as np

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import cellspec  # noqa: E402
import harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = cellspec.find_cell(args.workload)
    if cell.traffic["kind"] != "open":
        raise SystemExit(f"{cell.name} is not an open-loop cell")
    harness.configure_caches(cellspec.ROOT)
    harness.devices(cell.chips, args.rehearse)
    prep = harness.prepare(cell, args.seed, args.rehearse)
    prep.runner.run(float(cell.traffic.get("warmup_s", 1.0)))
    for rate in args.rates:
        before = prep.engine.stats()["bucket_batches"]
        prep.runner.traffic = dict(cell.traffic, rate=rate)
        w = prep.runner.run(args.seconds)
        after = prep.engine.stats()["bucket_batches"]
        lat = np.asarray([r.done - r.due if r.done is not None else np.inf
                          for r in w.requests])
        q = max(1, len(lat) // 4)
        done = int(np.isfinite(lat).sum())
        print(json.dumps({
            "rate": rate, "requests": len(lat),
            "answered_per_s": done / w.seconds,
            "p50_ms": float(np.percentile(lat, 50)) * 1e3,
            "p95_ms": float(np.percentile(lat, 95)) * 1e3,
            "trend": float(np.median(lat[-q:]) / np.median(lat[:q])),
            "drain_s": w.t1 - w.requests[-1].due,
            "batches": {str(b): n - before.get(b, 0)
                        for b, n in after.items()},
            "at": time.strftime("%H:%M:%S")}), flush=True)
    prep.runner.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
