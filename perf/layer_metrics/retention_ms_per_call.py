"""Device time under the scope ``ht.lm.retention`` (a power-retention model's
whole mixer: norms, projections, rotary embedding, gate, the state step and
the output projection), per call."""

from perf.layer_metrics import _scopes


def read(run):
    return _scopes.ms_per_call(run, ("ht.lm.retention",))
