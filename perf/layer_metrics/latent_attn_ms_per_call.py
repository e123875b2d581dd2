"""Device time under the scope ``ht.lm.latent_attn`` (a whole latent-attention
block: norms, projections, rotary embedding, the selection, the attention over
the chosen rows and the output projection), per call."""

from perf.layer_metrics import _scopes


def read(run):
    return _scopes.ms_per_call(run, ("ht.lm.latent_attn",))
