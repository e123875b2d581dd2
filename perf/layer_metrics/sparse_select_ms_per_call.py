"""Device time under the scope ``ht.lm.sparse_select`` (the indexer's
projections, the scan of the index cache and the top-k), per call."""

from perf.layer_metrics import _scopes


def read(run):
    return _scopes.ms_per_call(run, ("ht.lm.sparse_select",))
