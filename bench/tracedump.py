"""Print the structure of a profiler trace, for reading one by hand.

    python3 bench/tracedump.py artifacts/bench/trace/<cell> [--events 20]

Lists every plane and line with its event count and most common names,
the first events of each line with their stats, and the reduction
``devtrace`` computes from the trace and the host log beside it.
"""

import argparse
import collections
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH_DIR)

import devtrace  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("log_dir", help="a traced run's trace directory")
    ap.add_argument("--events", type=int, default=20)
    args = ap.parse_args()
    from jax.profiler import ProfileData
    path = devtrace.newest_xplane(args.log_dir)
    print(f"trace {path} ({os.path.getsize(path)} bytes)")
    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            names = collections.Counter(e.name for e in evs)
            print(f"  LINE {line.name!r}: {len(evs)} events, "
                  f"{len(names)} names; most common "
                  f"{names.most_common(3)}")
            for e in evs[:args.events]:
                print(f"    {e.name!r} start {e.start_ns:.0f} dur "
                      f"{e.duration_ns:.0f} stats {dict(e.stats)}")
    print(devtrace.reduce_dir(args.log_dir))
    return 0


if __name__ == "__main__":
    sys.exit(main())
