"""Look at a trace by hand: planes, lines, and on each line the events
that took most time, with one event's stats. The first thing to do with
a trace from a new device or a new jax, before trusting
`trace_reduce.py` on it.

    python3 -m benchmark.tools.trace_dump <trace dir or .xplane.pb> [top]
"""
from __future__ import annotations

import sys

from benchmark import trace_reduce


def main() -> int:
    from jax.profiler import ProfileData

    path = sys.argv[1]
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 25
    if not path.endswith(".pb"):
        path = trace_reduce.find_xplane(path)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name!r}")
        for line in plane.lines:
            by_name = {}
            first = last = None
            n = 0
            for e in line.events:
                n += 1
                rec = by_name.setdefault(e.name, [0.0, 0, None])
                rec[0] += e.duration_ns
                rec[1] += 1
                if rec[2] is None:
                    try:
                        rec[2] = {k: (str(v)[:80]) for k, v in e.stats}
                    except Exception as err:  # noqa: BLE001 - a look, not a tool
                        rec[2] = {"stats": repr(err)}
                first = e.start_ns if first is None else min(first, e.start_ns)
                end = e.start_ns + e.duration_ns
                last = end if last is None else max(last, end)
            span = (last - first) * 1e-9 if n else 0.0
            print(f"  LINE {line.name!r}: {n} events, {len(by_name)} names, "
                  f"spanning {span:.3f} s")
            ranked = sorted(by_name.items(), key=lambda kv: -kv[1][0])[:top]
            for name, (ns, count, stats) in ranked:
                print(f"    {ns * 1e-9:10.6f} s {count:7d} x {name[:90]!r} "
                      f"{stats}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
