"""What the readers of a device scope's time share."""

from perf import span_reduce


def ms_per_call(run, scopes):
    """Milliseconds per call of the fullest device under ``scopes`` together
    (scopes that do not nest, so their unions add up); None where the run has
    no reduction or its device events carry none of them."""
    got = span_reduce.for_run(run)
    if not got or not got["calls"] or not any(s in got["scopes"] for s in scopes):
        return None
    return sum(got["scopes"].get(s, 0.0) for s in scopes) / got["calls"] * 1e3
