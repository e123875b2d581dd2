"""The least time the chip could take for the traced calls' mandatory work
(``run["floor_s"]``, from the cell's work model and ``perf/peaks.json``) over
the seconds the fullest device was busy."""


def read(run):
    trace = run.get("trace")
    if not trace:
        return None
    busy = trace["devices"][trace["fullest"]]["busy_s"]
    if busy <= 0 or not run["floor_s"]:
        return None
    return 100.0 * run["floor_s"] / busy
