"""heat_tpu's benchmark: the yardstick that later PRs are measured with.

Everything here is driven by ``BENCHMARK.json`` and the data files it names;
see ``perf/README.md``.
"""
