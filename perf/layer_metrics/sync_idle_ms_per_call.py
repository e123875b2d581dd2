"""Idle time of the fullest device while the calling thread's innermost
program span was a sync span (``ht:sync:<site>``), per call: what the
device waits while the host waits for it."""

from perf import span_reduce


def read(run):
    got = span_reduce.for_run(run)
    if not got or not got["calls"]:
        return None
    return got["sync_idle_s"] / got["calls"] * 1e3
