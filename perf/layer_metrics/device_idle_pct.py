"""1 - busy union over the traced window, on the fullest device."""


def read(run):
    trace = run.get("trace")
    if not trace or trace["window_s"] <= 0:
        return None
    busy = trace["devices"][trace["fullest"]]["busy_s"]
    return 100.0 * (1.0 - busy / trace["window_s"])
