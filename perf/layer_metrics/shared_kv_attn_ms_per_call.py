"""Device time under the scope ``ht.lm.shared_kv_attn`` (the full-attention
layer's write and read of the shared key/value cache and the cross-attention
layers' reads), per call.  None where the device events carry no such scope."""

from perf.layer_metrics import _scopes


def read(run):
    return _scopes.ms_per_call(run, ("ht.lm.shared_kv_attn",))
