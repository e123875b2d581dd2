"""Schedule-controlled TPU kernels (Pallas + shard_map).

Most of the framework relies on XLA/GSPMD to schedule compute and insert
collectives.  This package holds the few hot paths where controlling the
schedule ourselves wins (SURVEY.md §7, design stance #6):

* :mod:`~heat_tpu.ops.halo` — halo exchange for stencils/convolution, the
  TPU counterpart of the reference's eager ``DNDarray.get_halo``
  (heat/core/dndarray.py:383-453).
* :mod:`~heat_tpu.ops.cdist` — fused pairwise-distance kernel, the hot loop
  of KMeans (reference: heat/spatial/distance.py:16-134 metric kernels).
* :mod:`~heat_tpu.ops.attention` — flash attention (blockwise online
  softmax); no reference counterpart (Heat has no attention at all,
  SURVEY.md §5) but required for long-context sequence parallelism.
"""

from .halo import halo_exchange, map_with_halos
from .cdist import cdist as fused_cdist
from .attention import flash_attention

__all__ = [
    "halo_exchange",
    "map_with_halos",
    "fused_cdist",
    "flash_attention",
]
