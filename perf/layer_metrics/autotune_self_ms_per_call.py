"""Self time of ``ht:autotune.decide`` and ``ht:autotune.explore`` together,
per call (an explore's timed arms wait under their own sync spans)."""

from perf import span_reduce


def read(run):
    return span_reduce.span_self_ms_per_call(run, ("autotune.decide", "autotune.explore"))
