"""Sync spans (``ht:sync:<site>``, ``telemetry.sync``) closed inside the traced
window, per call: how often a call makes the host wait for the device."""

from perf import span_reduce


def read(run):
    got = span_reduce.for_run(run)
    if not got or not got["calls"]:
        return None
    return got["syncs"] / got["calls"]
