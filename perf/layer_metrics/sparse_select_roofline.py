"""Share of the HBM roofline that the sparse selection reaches: the least
seconds to read the call's visible index keys once a step, layer and session
(``perf/work_models/sparse_read.py:index_scan``) at the published HBM peak,
over the device time under the scope ``ht.lm.sparse_select`` (which also holds
the indexer's projections and the top-k: they are what stands between the
scan and its floor)."""

from perf.layer_metrics import _sparse
from perf.work_models import sparse_read


def read(run):
    return _sparse.roofline(run, "ht.lm.sparse_select", sparse_read.index_scan)
