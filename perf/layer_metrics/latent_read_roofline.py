"""Share of the HBM roofline that the read of the chosen latent rows reaches:
the least seconds to read ``selected`` rows a step, layer and session once
(``perf/work_models/sparse_read.py:latent_rows``) at the published HBM peak,
over the device time under the scope ``ht.lm.latent_read`` (the gather, the
logits, the softmax and the value sum in the latent space)."""

from perf.layer_metrics import _sparse
from perf.work_models import sparse_read


def read(run):
    return _sparse.roofline(run, "ht.lm.latent_read", sparse_read.latent_rows)
