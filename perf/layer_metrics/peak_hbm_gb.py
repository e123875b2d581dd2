"""``memory_stats()["peak_bytes_in_use"]`` after the window, fullest device."""


def read(run):
    peak = run.get("memory_peak_bytes")
    return None if not peak else peak / 1e9
