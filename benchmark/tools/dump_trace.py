#!/usr/bin/env python3
"""Look at one profiler trace by hand: planes, lines, event counts, the
longest names. ``python3 benchmark/tools/dump_trace.py <dir or .xplane.pb>``"""
import collections
import glob
import os
import sys

import jax


def main(path: str) -> None:
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True))[-1]
    print(path, os.path.getsize(path), "bytes")
    data = jax.profiler.ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            total = collections.defaultdict(float)
            count = collections.Counter()
            for e in events:
                total[e.name] += e.duration_ns
                count[e.name] += 1
            lo = min(e.start_ns for e in events)
            hi = max(e.start_ns + e.duration_ns for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, span {lo / 1e9:.4f}..{hi / 1e9:.4f} s")
            for name, ns in sorted(total.items(), key=lambda kv: -kv[1])[:12]:
                print(f"      {ns / 1e6:10.3f} ms  x{count[name]:<6d} {name[:110]}")


if __name__ == "__main__":
    main(sys.argv[1])
