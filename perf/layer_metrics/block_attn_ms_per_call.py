"""Device time under the scope ``ht.lm.block_attn`` (the key/value stream
kernel of a block-diffusion model's passes, commits included), per call."""

from perf.layer_metrics import _scopes


def read(run):
    return _scopes.ms_per_call(run, ("ht.lm.block_attn",))
