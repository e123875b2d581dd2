"""Device time under the scope ``ht.lm.latent_stream`` (the full read of
every visible latent row, every layer and the MTP layer: on a TPU the kernel
``ht_latent_stream``), per call."""

from perf.layer_metrics import _scopes


def read(run):
    return _scopes.ms_per_call(run, ("ht.lm.latent_stream",))
