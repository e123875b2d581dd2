#!/usr/bin/env python3
"""Cut a real trace down to a sample for ``test_span_reduce.py``, with the
method of ``make_trace_sample.py`` and the scope path kept on each device
operation (``span_reduce.load_xplane``'s fourth field).

    python3 perf/tests/make_span_sample.py <trace dir | file.xplane.pb> out.json [calls]

Keeps the window span, the first ``calls`` call spans and what lies inside
them: the device's events, and of the calling thread the window, the calls,
the program's spans (``ht:``) and the runtime's readback events that
``trace_reduce`` names idle gaps by.  Names cut to 96 characters.  Needs only
the file, so it runs wherever the ``.xplane.pb`` has been copied to.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
from perf import span_reduce, trace_reduce  # noqa: E402

KEEP = (trace_reduce.CALL, span_reduce.PREFIX, "np.asarray", "DevicePut")


def main(source, out_path, calls=2):
    path = source if source.endswith(".pb") else trace_reduce.find_xplane(source)
    trace = span_reduce.load_xplane(path)
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
            events = [[e[0][:96], e[1] - lo, e[2], *e[3:]] for e in line["events"]
                      if e[0] != trace_reduce.WINDOW and e[1] >= lo and e[1] + e[2] <= hi
                      and (device or e[0].startswith(KEEP))]
            if line is caller:
                events.insert(0, [trace_reduce.WINDOW, 0.0, hi - lo])
            lines.append({"name": line["name"], "events": events})
        planes.append({"name": plane["name"], "lines": lines})
    with open(out_path, "w") as fh:
        json.dump({"planes": planes}, fh, separators=(",", ":"))
    print(out_path, os.path.getsize(out_path), "bytes")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2], int(sys.argv[3]) if len(sys.argv) > 3 else 2)
