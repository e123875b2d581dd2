"""Device time under the scope ``ht.lm.ssm`` (the Mamba mixers: projections,
convolution, the selective scan's step; and the gated memory units), per call."""

from perf.layer_metrics import _scopes


def read(run):
    return _scopes.ms_per_call(run, ("ht.lm.ssm",))
