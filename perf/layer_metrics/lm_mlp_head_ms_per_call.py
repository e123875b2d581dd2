"""Device time under the scopes ``ht.lm.mlp`` (every layer's gated MLP) and
``ht.lm.head`` (final norm, logits by the tied embedding, argmax), per call:
the part of a decode step that any dense model has, bound by reading weights."""

from perf.layer_metrics import _scopes


def read(run):
    return _scopes.ms_per_call(run, ("ht.lm.mlp", "ht.lm.head"))
