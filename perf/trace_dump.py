#!/usr/bin/env python3
"""Print what a trace holds: planes, lines, event counts and a few events of
each line.  Look at one trace by hand before trusting the reduction.

    python3 perf/trace_dump.py perf/out/trace/<workload>
"""

import os
import sys

from jax.profiler import ProfileData

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from perf import trace_reduce  # noqa: E402


def main(log_dir: str, show: int = 6) -> None:
    path = trace_reduce.find_xplane(log_dir)
    print(path)
    for plane in ProfileData.from_file(path).planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
            for ev in events[:show]:
                print(f"      {ev.name[:100]!r} start={ev.start_ns:.0f} dur={ev.duration_ns:.0f}")


if __name__ == "__main__":
    main(sys.argv[1])
