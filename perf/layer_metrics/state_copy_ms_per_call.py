"""Device time under the scope ``ht.lm.state_copy`` (the copy of a session's
constant-size state that ``rewind`` and ``save`` make), per call."""

from perf.layer_metrics import _scopes


def read(run):
    return _scopes.ms_per_call(run, ("ht.lm.state_copy",))
