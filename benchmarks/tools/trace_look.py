#!/usr/bin/env python3
"""Look at a cell's last trace by hand: planes, lines, and the events that
took most time on each line with their stats. Run a cell with ``--trace 1``
first; its trace stays under ``.bench_trace/<cell>/``.

    python3 benchmarks/tools/trace_look.py <cell> [events per line]
"""

import glob
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    from jax.profiler import ProfileData

    cell = sys.argv[1]
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 12
    files = sorted(glob.glob(os.path.join(
        ROOT, ".bench_trace", cell, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise SystemExit(f"no trace under .bench_trace/{cell}")
    print(files[-1], os.path.getsize(files[-1]), "bytes")
    for plane in ProfileData.from_file(files[-1]).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} line(s)")
        for line in lines:
            events = list(line.events)
            if not events:
                continue
            by_name = {}
            for e in events:
                tot, n, _ = by_name.get(e.name, (0.0, 0, None))
                by_name[e.name] = (tot + e.duration_ns, n + 1, e)
            t0 = min(e.start_ns for e in events)
            t1 = max(e.start_ns + e.duration_ns for e in events)
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{len(by_name)} names, span {(t1 - t0) * 1e-6:.3f} ms, "
                  f"starts at {t0:.0f} ns")
            for name, (tot, n, e) in sorted(
                    by_name.items(), key=lambda x: -x[1][0])[:top]:
                stats = {k: (v if not isinstance(v, str) else v[:80])
                         for k, v in list(e.stats)[:12]}
                print(f"    {tot * 1e-6:10.3f} ms x{n:<5} {name[:200]!r} "
                      f"{stats}")


if __name__ == "__main__":
    main()
