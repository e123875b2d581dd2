"""Autotune calls that ran both arms inside the window; should be 0."""


def read(run):
    return float(run["counters"]["autotune_explores"])
