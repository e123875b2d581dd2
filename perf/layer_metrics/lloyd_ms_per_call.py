"""Union of the fullest device's operation intervals under the scope
``ht.kmeans.lloyd``, per call (a union: the ``while`` operation's interval
contains its body's).  None where the device events carry no scope path."""

from perf import span_reduce

SCOPE = "ht.kmeans.lloyd"


def read(run):
    got = span_reduce.for_run(run)
    if not got or not got["calls"] or SCOPE not in got["scopes"]:
        return None
    return got["scopes"][SCOPE] / got["calls"] * 1e3
