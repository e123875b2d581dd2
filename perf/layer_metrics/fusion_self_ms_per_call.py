"""Self time of ``ht:fusion.materialize`` (the span's duration minus its
child program spans: guard check, timed fence), per call."""

from perf import span_reduce


def read(run):
    return span_reduce.span_self_ms_per_call(run, ("fusion.materialize",))
