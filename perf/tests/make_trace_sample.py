#!/usr/bin/env python3
"""Cut a real trace down to a sample small enough to keep under tests/data.

    python3 perf/tests/make_trace_sample.py perf/out/trace/<workload> out.json [calls]

Keeps the window span, the first ``calls`` call spans and what lies inside
them (device events of every plane, the calling thread's host spans), names
cut to 96 characters.  Run where the trace was taken (the chip's machine).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from perf import trace_reduce  # noqa: E402


def main(log_dir, out_path, calls=2):
    trace = trace_reduce.load_xplane(trace_reduce.find_xplane(log_dir))
    lo, _hi, caller = trace_reduce._window(trace)
    spans = sorted((e for e in caller["events"] if e[0] == trace_reduce.CALL),
                   key=lambda e: e[1])[:calls]
    hi = spans[-1][1] + spans[-1][2]
    planes = []
    for plane in trace["planes"]:
        device = plane["name"].startswith("/device:")
        lines = []
        for line in plane["lines"]:
            if not device and line is not caller:
                continue
            events = [[n[:96], s - lo, d] for n, s, d in line["events"]
                      if n != trace_reduce.WINDOW and s >= lo and s + d <= hi]
            if line is caller:
                events.insert(0, [trace_reduce.WINDOW, 0.0, hi - lo])
            lines.append({"name": line["name"], "events": events})
        planes.append({"name": plane["name"], "lines": lines})
    with open(out_path, "w") as fh:
        json.dump({"planes": planes}, fh, separators=(",", ":"))
    print(out_path, os.path.getsize(out_path), "bytes")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], int(sys.argv[3]) if len(sys.argv) > 3 else 2)
