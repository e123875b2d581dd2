"""Device time under the scope ``ht.lm.mtp`` (the MTP module: its input
norms and ``W_eh``, its decoder layer and its head), per call."""

from perf.layer_metrics import _scopes


def read(run):
    return _scopes.ms_per_call(run, ("ht.lm.mtp",))
