#!/usr/bin/env python3
"""Look at one trace by hand: planes, their lines, and the events that
took most time on each line.

    python benchmark/selftest/trace_dump.py TRACE_DIR_OR_XPLANE_PB [top]
"""

from __future__ import annotations

import glob
import os
import sys


def main(argv) -> int:
    from jax.profiler import ProfileData

    path = argv[1]
    top = int(argv[2]) if len(argv) > 2 else 8
    if os.path.isdir(path):
        path = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                                recursive=True))[-1]
    print(f"{path}: {os.path.getsize(path)} bytes")
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            total: dict = {}
            n = 0
            t_min, t_max = None, None
            for ev in line.events:
                n += 1
                total[ev.name] = total.get(ev.name, 0) + ev.duration_ns
                t_min = ev.start_ns if t_min is None else min(t_min,
                                                              ev.start_ns)
                end = ev.start_ns + ev.duration_ns
                t_max = end if t_max is None else max(t_max, end)
            if not n:
                continue
            span = (t_max - t_min) * 1e-9
            print(f"  line {line.name!r}: {n} events over {span:.3f} s")
            for name, ns in sorted(total.items(),
                                   key=lambda kv: -kv[1])[:top]:
                print(f"    {ns * 1e-6:10.3f} ms  {name[:100]}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
