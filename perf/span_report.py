#!/usr/bin/env python3
"""Print what the program says about itself in a trace: its spans and its
device scopes (``perf/span_reduce.py``).

    python3 perf/span_report.py perf/out/trace/<workload>

First the program spans (``ht:<span>``, ``telemetry.span`` in ``trace`` mode):
count, total and self milliseconds per call, the fullest device's idle
milliseconds per call while the span was the innermost one, and the largest
single idle stretch under it (a call that comes back late from a readback
shows there).  The idle column and the line "no program span" add up to the
window minus the device's busy union.  Then the device's seconds by
``jax.named_scope`` (``ht.kmeans.assign``, ``ht.qr.gram1``, ...), per call:
unions, so a scope's time contains the scopes inside it.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from perf import span_reduce, trace_reduce  # noqa: E402


def main(log_dir: str) -> None:
    path = trace_reduce.find_xplane(log_dir)
    got = span_reduce.reduce(span_reduce.load_xplane(path))
    calls = max(got["calls"], 1)
    print(path)
    print(f"window {got['window_s']:.6f} s, {got['calls']} calls, busy {got['busy_s']:.6f} s, "
          f"idle {got['idle_s']:.6f} s, {got['syncs']} syncs ({got['syncs'] / calls:.2f} a call)")
    print(f"{'program span':44s} {'count':>6s} {'total ms/call':>14s} {'self ms/call':>13s} "
          f"{'idle ms/call':>13s} {'max gap ms':>11s}")
    rows = sorted(got["spans"].items(), key=lambda kv: -kv[1]["total_s"])
    for name, r in rows:
        print(f"{name:44s} {r['count']:6d} {r['total_s'] / calls * 1e3:14.4f} "
              f"{r['self_s'] / calls * 1e3:13.4f} {r['idle_s'] / calls * 1e3:13.4f} "
              f"{r['max_gap_s'] * 1e3:11.4f}")
    print(f"{span_reduce.OUTSIDE:44s} {'':6s} {'':14s} {'':13s} "
          f"{got['idle_outside_s'] / calls * 1e3:13.4f} {got['max_gap_outside_s'] * 1e3:11.4f}")
    print(f"{'idle while a sync span was innermost':44s} {'':6s} {'':14s} {'':13s} "
          f"{got['sync_idle_s'] / calls * 1e3:13.4f}")
    if not got["scopes"]:
        print("the device's operations carry no scope path")
        return
    print(f"{'device scope':44s} {'ms/call':>14s} {'share of busy':>14s}")
    for name, secs in sorted(got["scopes"].items(), key=lambda kv: -kv[1]):
        print(f"{name:44s} {secs / calls * 1e3:14.4f} {100 * secs / got['busy_s']:13.2f}%")


if __name__ == "__main__":
    main(sys.argv[1])
