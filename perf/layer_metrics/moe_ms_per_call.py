"""Device time under the scope ``ht.lm.moe`` (an expert layer's second half:
norm, router, the held experts' part of the routed sum and the shared
expert), per call."""

from perf.layer_metrics import _scopes


def read(run):
    return _scopes.ms_per_call(run, ("ht.lm.moe",))
