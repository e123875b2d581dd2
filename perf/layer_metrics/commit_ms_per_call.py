"""Device time under the scope ``ht.lm.commit`` (a block-diffusion model's
commit passes: the finished blocks' keys and values through every layer),
per call."""

from perf.layer_metrics import _scopes


def read(run):
    return _scopes.ms_per_call(run, ("ht.lm.commit",))
