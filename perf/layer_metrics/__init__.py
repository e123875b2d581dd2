"""Per-layer metrics: one reader per file, named as in ``BENCHMARK.json``.

``read(run) -> float | None``.  ``run`` is what a traced run of one cell
gathered (``perf/run.py`` ``gather``): ``trace`` (``trace_reduce.reduce``),
``calls`` and ``floor_s`` of the traced window, ``counters`` (after minus
before), ``memory_peak_bytes``, ``chips``.  A reader that finds nothing to
read returns None and the metric is left out of the line; it never returns 0
for a share.
"""
