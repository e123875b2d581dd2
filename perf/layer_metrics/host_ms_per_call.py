"""Traced window minus the fullest device's busy union, per call: the time
in which the device waited for the host (User API, fusion, guard, telemetry
and memtrack, seen from outside)."""


def read(run):
    trace = run.get("trace")
    if not trace or not run["calls"]:
        return None
    busy = trace["devices"][trace["fullest"]]["busy_s"]
    return (trace["window_s"] - busy) / run["calls"] * 1e3
