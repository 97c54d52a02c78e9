"""Print the planes, lines and heaviest events of a profiler trace.

    python bench/tracedump.py <trace dir> [events per line]

For looking at a trace by hand before reading it with code: which planes
are chips and which are host threads, and how programs and kernels are
named.
"""

from __future__ import annotations

import glob
import os
import sys
from collections import defaultdict


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    top = int(argv[1]) if len(argv) > 1 else 15
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(argv[0], "**", "*.xplane.pb"),
                            recursive=True), key=os.path.getmtime)[-1]
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            total, count = defaultdict(float), defaultdict(int)
            for e in events:
                total[e.name] += e.duration_ns
                count[e.name] += 1
            print(f"  LINE {line.name!r}: {len(events)} events")
            for e in events[:2]:
                print(f"    e.g. {e.name!r} start {e.start_ns} dur "
                      f"{e.duration_ns} stats "
                      f"{[(k, str(v)[:50]) for k, v in e.stats][:6]}")
            for name, ns in sorted(total.items(), key=lambda kv: -kv[1])[:top]:
                print(f"    {ns / 1e6:11.3f} ms x{count[name]:6d}  {name[:110]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
