"""Explicit collectives for schedule-controlled algorithms.

Most of the framework never names a collective: XLA's GSPMD partitioner inserts
them from shardings.  The few algorithms that control their own schedule
(TSQR panel merges, ring pairwise distances, halo-exchange convolution — the
TPU counterparts of the reference's hand-written Send/Recv rings in
heat/core/linalg/qr.py, heat/spatial/distance.py:209 and
heat/core/dndarray.py:383) run under ``jax.shard_map`` and use these
wrappers.

Mapping from the reference's MPI calls (SURVEY.md §2.5):

==================  =========================================
reference (MPI)     here (XLA over ICI/DCN)
==================  =========================================
Allreduce           :func:`psum` / :func:`pmax` / :func:`pmin`
Allgather(v)        :func:`all_gather`
Alltoall(v/w)       :func:`all_to_all`
Send/Recv rings     :func:`ring_shift` (collective-permute)
Bcast               sharding (replicate) or :func:`bcast`
Exscan/Scan         :func:`exscan`
==================  =========================================
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "shard_map",
    "shard_map_unchecked",
    "on_each_device",
    "jit_shard_map_cached",
    "psum",
    "pmax",
    "pmin",
    "all_gather",
    "all_to_all",
    "ring_shift",
    "bcast",
    "exscan",
    "axis_index",
    "axis_size",
]


def shard_map_unchecked(fn, mesh, in_specs, out_specs):
    """shard_map with varying-manual-axes (replication) checking off."""
    return shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )


def on_each_device(fn, mesh):
    """``fn`` run redundantly by every device of ``mesh`` over replicated
    operands, its results replicated.  What a single-device Pallas kernel
    needs on a multi-device mesh: GSPMD refuses to partition a Mosaic
    custom call even when every operand is replicated and there is
    nothing to partition ("wrap the call in a shard_map")."""
    return shard_map_unchecked(fn, mesh, in_specs=P(), out_specs=P())


@lru_cache(maxsize=None)
def jit_shard_map_cached(builder: Callable, mesh, *key):
    """Build-and-jit a shard_map'd kernel once per ``(builder, mesh, *key)``.

    ``builder(mesh, *key)`` must return the shard_map'd callable.  Rebuilding
    the closure per call would defeat jit's trace cache and recompile the
    kernel on every invocation; every hot shard_map site (spatial.cdist,
    linalg TSQR) routes through this cache."""
    return jax.jit(builder(mesh, *key))


def axis_index(axis: str):
    """This shard's position along the mesh axis (reference: comm.rank)."""
    return lax.axis_index(axis)


def axis_size(axis: str) -> int:
    """Number of shards along the mesh axis (reference: comm.size)."""
    return lax.axis_size(axis)


def psum(x, axis: str):
    """All-reduce sum (reference: MPICommunication.Allreduce with MPI.SUM,
    heat/core/communication.py:774)."""
    return lax.psum(x, axis_name=axis)


def pmax(x, axis: str):
    return lax.pmax(x, axis_name=axis)


def pmin(x, axis: str):
    return lax.pmin(x, axis_name=axis)


def all_gather(x, axis: str, *, concat_axis: int = 0, tiled: bool = True):
    """All-gather along an array axis (reference: axis-aware Allgather(v),
    heat/core/communication.py:1027-1220).

    With ``tiled=True`` the per-shard blocks are concatenated along
    ``concat_axis`` (matching Allgatherv's flattened layout); otherwise a new
    leading axis indexes the source shard.
    """
    return lax.all_gather(x, axis_name=axis, axis=concat_axis, tiled=tiled)


def all_to_all(x, axis: str, *, split_axis: int, concat_axis: int, tiled: bool = True):
    """All-to-all redistribution (reference: Alltoall(v/w) with derived
    datatypes for axis permutation, heat/core/communication.py:1222-1492)."""
    return lax.all_to_all(
        x, axis_name=axis, split_axis=split_axis, concat_axis=concat_axis, tiled=tiled
    )


def ring_shift(x, axis: str, *, shift: int = 1):
    """Pass each shard to the neighbor ``shift`` positions up the ring.

    This is the TPU idiom for every Send/Recv ring in the reference (e.g. the
    moving block in heat/spatial/distance.py:209, redistribute_'s pairwise
    exchanges in dndarray.py:1161-1318): a ``collective_permute`` rides the ICI
    torus links directly.
    """
    n = axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name=axis, perm=perm)


def bcast(x, axis: str, *, root: int = 0):
    """Broadcast the ``root`` shard's value to all shards (reference: Bcast,
    communication.py:714-772). Implemented as mask + psum, which XLA lowers to
    an efficient broadcast."""
    idx = lax.axis_index(axis)
    masked = jnp.where(idx == root, x, jnp.zeros_like(x))
    return lax.psum(masked, axis_name=axis)


def exscan(x, axis: str, *, op: Callable = jnp.add, neutral=0):
    """Exclusive prefix scan over the mesh axis (reference: Exscan,
    communication.py:925-1025). Gathers the per-shard values (small — one
    scalar/slab per shard) and combines prefixes locally."""
    n = axis_size(axis)
    idx = lax.axis_index(axis)
    gathered = lax.all_gather(x, axis_name=axis, axis=0, tiled=False)  # (n, ...)
    mask = (jnp.arange(n) < idx).reshape((n,) + (1,) * (gathered.ndim - 1))
    neutral_arr = jnp.full_like(gathered, neutral)
    contrib = jnp.where(mask, gathered, neutral_arr)
    out = contrib[0]
    for i in range(1, n):
        out = op(out, contrib[i])
    return out
