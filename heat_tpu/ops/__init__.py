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
* :mod:`~heat_tpu.ops.decode_attention` — a few query rows against a long
  key/value cache: the read a decode step of a long generation spends its
  time in (Pallas on a TPU), and the position-masked blockwise attention
  that prefill chunks and rolling windows go through.
* :mod:`~heat_tpu.ops.selective_scan` — Mamba's selective state-space scan,
  chunked over a sequence and as one step.
* :mod:`~heat_tpu.ops.power_retention` — power retention of degree 2 (linear
  attention with weights ``(q . k)^2`` under a gate): one decode step as one
  pass over the constant-size state in place (Pallas on a TPU), and the
  chunked form that prefill walks.

The last three are imported from their modules (``from heat_tpu.ops.decode_attention
import decode_attention``): a function re-exported here under its module's
name would hide the module.
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
