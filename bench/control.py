"""Readings that set a cell's correctness limit: the program's, and the
control's, on many seeds in one process.

    python3 bench/control.py --workload <name> --seconds 3 --seeds 11 12 ...

For each seed it makes one whole run of the cell (set-up, warm-up, a
short window of the cell's own traffic, every answer compared with the
reference at ``Precision.HIGHEST``: the program's reading), then puts
the control in the program's place: the same reference computed one
precision lower (``bf16x3``, ``Precision.HIGH``'s three bfloat16
passes) over the same images, compared in the same way.  The limit lies
between the largest program reading and the smallest control reading
(``PERF.md`` gives them).  One JSON line per seed, then a summary line.
The benchmark's own runs never run this.
"""

import argparse
import json
import os
import sys
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import cellspec  # noqa: E402
import harness  # noqa: E402
import inputs  # noqa: E402


def readings(cell, seed: int, seconds: float, rehearse: bool) -> dict:
    res = harness.run(cell, seed=seed, seconds=seconds, trace=False,
                      rehearse=rehearse, t_start=time.monotonic(),
                      log=lambda m: None)
    number = cell.config["check"]["number"]
    spec = harness.netspec(cell, rehearse)
    pool = inputs.make_pool(spec, int(cell.traffic["pool"]), seed)
    items = range(len(pool))
    hi = harness.reference_outputs(spec, seed, pool, items, "highest")
    lo = harness.reference_outputs(spec, seed, pool, items, "bf16x3")
    return {"seed": seed, "program": res["checks"][number]["value"],
            "control": harness.max_rel_err(list(lo.items()), hi),
            "correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"]}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    cell = cellspec.find_cell(args.workload)
    harness.configure_caches(cellspec.ROOT)
    import jax
    rows = []
    for seed in args.seeds:
        row = readings(cell, seed, args.seconds, args.rehearse)
        rows.append(row)
        print(json.dumps(row), flush=True)
        jax.clear_caches()
    lower = max(r["program"] for r in rows)
    upper = min(r["control"] for r in rows)
    print(json.dumps({"workload": cell.name, "seeds": len(rows),
                      "lower": lower, "upper": upper,
                      "upper_over_lower": upper / max(lower, 1e-300),
                      "all_correct": all(r["correct"] for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
