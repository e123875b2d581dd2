"""Executions of XLA programs on the first device per call (a count)."""


def read(run):
    trace = run.get("trace")
    if not trace or not run["calls"]:
        return None
    return trace["devices"][0]["programs"] / run["calls"]
