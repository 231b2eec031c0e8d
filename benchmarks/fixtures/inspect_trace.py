#!/usr/bin/env python3
"""Look at one profiler trace by hand: planes, lines, event counts, the
names that took most time, and the stats an event carries.

    python3 benchmarks/fixtures/inspect_trace.py TRACE.xplane.pb [TOP]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import trace_reduce  # noqa: E402


def main(path: str, top: int = 12) -> None:
    pd = trace_reduce.load(path)
    print(f"{path}: {os.path.getsize(path)} bytes")
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} line(s)")
        for line in lines:
            evs = list(line.events)
            if not evs:
                print(f"  LINE {line.name!r}: 0 events")
                continue
            lo = min(e.start_ns for e in evs)
            hi = max(e.start_ns + e.duration_ns for e in evs)
            print(f"  LINE {line.name!r}: {len(evs)} events, "
                  f"{lo / 1e9:.6f}..{hi / 1e9:.6f} s")
            tot = {}
            for e in evs:
                t = tot.setdefault(e.name, [0.0, 0])
                t[0] += e.duration_ns
                t[1] += 1
            for name, (ns, n) in sorted(tot.items(), key=lambda kv: -kv[1][0])[:top]:
                print(f"      {ns / 1e6:12.3f} ms {n:7d} x  {name[:100]}")
            try:
                print(f"      stats of the first event: {dict(evs[0].stats)}")
            except Exception as e:  # a stat type ProfileData cannot render
                print(f"      stats unreadable: {e}")


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]) if len(sys.argv) > 2 else 12)
