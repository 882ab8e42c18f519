"""Look at one trace by hand: planes, lines, and the event names of each line
with their counts and total durations.

  python benchmarks/selftest/dump_trace.py <trace dir or .xplane.pb> [top]
"""

import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv) -> int:
    from jax.profiler import ProfileData
    from benchmarks import trace_reduce
    path = argv[1]
    top = int(argv[2]) if len(argv) > 2 else 12
    if os.path.isdir(path):
        path = trace_reduce.find_xplane(path)
    print(path, os.path.getsize(path), "bytes")
    for plane in ProfileData.from_file(path).planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            by = defaultdict(lambda: [0, 0.0])
            first = last = None
            stats = None
            for e in line.events:
                by[e.name][0] += 1
                by[e.name][1] += e.duration_ns
                first = e.start_ns if first is None else min(first, e.start_ns)
                last = max(last or 0, e.start_ns + e.duration_ns)
                if stats is None:
                    stats = dict(e.stats)
            n = sum(c for c, _ in by.values())
            print(f"  LINE {line.name!r}: {n} events, {len(by)} names, "
                  f"span {first}..{last} ns; first event's stats {stats}")
            for name, (c, ns) in sorted(by.items(),
                                        key=lambda kv: -kv[1][1])[:top]:
                print(f"      {ns / 1e6:12.3f} ms  x{c:<6} {name[:110]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
