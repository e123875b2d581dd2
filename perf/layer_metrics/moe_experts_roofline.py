"""Share of the HBM roofline that the held experts' products reach: the least
seconds to read every held expert of every expert layer once a step
(``perf/work_models/sparse_read.py:expert_weights``) at the published HBM
peak, over the device time under the scope ``ht.lm.moe_experts``."""

from perf.layer_metrics import _sparse
from perf.work_models import sparse_read


def read(run):
    return _sparse.roofline(run, "ht.lm.moe_experts", sparse_read.expert_weights)
