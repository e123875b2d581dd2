"""Shape and data manipulations (reference: heat/core/manipulations.py,
4024 LoC — the largest ops file).

The reference's distribution-aware case analyses (``concatenate``'s split
matrix :188, ``reshape``'s resplit-to-0 + Alltoallv :1821, ``resplit``'s
Allgatherv/tile-shuffle :3325, the sample-sort ``sort`` :2261, ``unique``'s
gather-merge :3048) all become jnp calls on the global array plus a sharding
enforcement — XLA emits the all-to-alls.  ``sort`` uses XLA's distributed-
capable sort; ``unique``/``nonzero``-style data-dependent shapes return
replicated results (their size is data-dependent, which GSPMD cannot shard
statically).
"""

from __future__ import annotations

import builtins

from typing import List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from . import factories, fusion, sanitation, stride_tricks, types
from .dndarray import DNDarray, _ensure_split, _to_physical
from ..analysis import sanitize as spmd_sanitize
from ..parallel import transport

__all__ = [
    "balance",
    "broadcast_arrays",
    "broadcast_to",
    "column_stack",
    "concatenate",
    "diag",
    "diagonal",
    "dsplit",
    "dstack",
    "expand_dims",
    "flatten",
    "flip",
    "fliplr",
    "flipud",
    "hsplit",
    "hstack",
    "moveaxis",
    "pad",
    "ravel",
    "redistribute",
    "repeat",
    "reshape",
    "resplit",
    "roll",
    "rot90",
    "row_stack",
    "shape",
    "sort",
    "split",
    "squeeze",
    "stack",
    "swapaxes",
    "tile",
    "mpi_topk",
    "topk",
    "unique",
    "vsplit",
    "vstack",
]


def _wrap(arr, like: DNDarray, split) -> DNDarray:
    out = DNDarray(
        arr, tuple(arr.shape), types.canonical_heat_type(arr.dtype),
        split, like.device, like.comm,
    )
    return _ensure_split(out, split)


def _require_dndarray(arrays: Sequence, fname: str) -> DNDarray:
    """First DNDarray in ``arrays``; TypeError otherwise (stack-family guard)."""
    ref = next((a for a in arrays if isinstance(a, DNDarray)), None)
    if ref is None:
        raise TypeError(f"{fname} expected at least one DNDarray input")
    return ref


def balance(array: DNDarray, copy: bool = False) -> DNDarray:
    """Out-of-place balance (reference: manipulations.py:63). Always already
    balanced under GSPMD."""
    from .memory import copy as _copy

    return _copy(array) if copy else array


def broadcast_arrays(*arrays: DNDarray) -> List[DNDarray]:
    """Broadcast arrays against each other."""
    shapes = [a.shape for a in arrays]
    target = stride_tricks.broadcast_shapes(*shapes)
    return [broadcast_to(a, target) for a in arrays]


def broadcast_to(x: DNDarray, shape) -> DNDarray:
    """Broadcast to a new shape."""
    shape = stride_tricks.sanitize_shape(shape)
    result = jnp.broadcast_to(x.larray, shape)
    split = x.split
    if split is not None:
        split = split + (len(shape) - x.ndim)
    return _wrap(result, x, split)


def column_stack(arrays: Sequence[DNDarray]) -> DNDarray:
    """Stack 1-D/2-D arrays as columns (reference: manipulations.py)."""
    arrays = list(arrays)  # generators survive the _require_dndarray pass
    ref = _require_dndarray(arrays, "column_stack")
    prepared = [a.larray if isinstance(a, DNDarray) else jnp.asarray(a) for a in arrays]
    result = jnp.column_stack(prepared)
    split = ref.split if ref.split == 0 else None
    return _wrap(result, ref, split)


def concatenate(arrays: Sequence[DNDarray], axis: int = 0) -> DNDarray:
    """Join arrays along an existing axis (reference: manipulations.py:188 —
    a 3-way case analysis on splits there; one jnp.concatenate here, with the
    first operand's split dominating)."""
    arrays = list(arrays)
    if len(arrays) < 1:
        raise ValueError("need at least one array to concatenate")
    ref = _require_dndarray(arrays, "concatenate")
    axis = stride_tricks.sanitize_axis(ref.shape, axis)
    prepared = [a.larray if isinstance(a, DNDarray) else jnp.asarray(a) for a in arrays]
    # validate up front so shape mismatches surface as ValueError (the
    # reference's error class) instead of jax's TypeError at dispatch
    for p in prepared[1:]:
        if p.ndim != prepared[0].ndim or any(
            p.shape[d] != prepared[0].shape[d]
            for d in range(p.ndim) if d != axis
        ):
            raise ValueError(
                "all input array dimensions except the concatenation axis "
                f"must match: {prepared[0].shape} vs {p.shape} on axis {axis}"
            )
    result = jnp.concatenate(prepared, axis=axis)
    split = next((a.split for a in arrays if isinstance(a, DNDarray) and a.split is not None), None)
    return _wrap(result, ref, split)


def diag(a: DNDarray, offset: int = 0) -> DNDarray:
    """Extract or construct a diagonal (reference: manipulations.py diag)."""
    sanitation.sanitize_in(a)
    if a.ndim == 1:
        result = jnp.diag(a.larray, k=offset)
        return _wrap(result, a, a.split)
    return diagonal(a, offset=offset)


def diagonal(a: DNDarray, offset: int = 0, dim1: int = 0, dim2: int = 1) -> DNDarray:
    """Diagonal view (reference: manipulations.py diagonal)."""
    sanitation.sanitize_in(a)
    result = jnp.diagonal(a.larray, offset=offset, axis1=dim1, axis2=dim2)
    split = None if a.split in (dim1, dim2) else a.split
    if split is not None:
        split -= sum(1 for d in (dim1, dim2) if d < split)
        split = min(split, result.ndim - 1)
    return _wrap(result, a, split)


def dsplit(x: DNDarray, indices_or_sections) -> List[DNDarray]:
    """Split along axis 2 (reference: manipulations.py dsplit)."""
    return split(x, indices_or_sections, axis=2)


def expand_dims(a: DNDarray, axis: int) -> DNDarray:
    """Insert a new axis (reference: manipulations.py expand_dims)."""
    sanitation.sanitize_in(a)
    axis = stride_tricks.sanitize_axis(tuple(a.shape) + (1,), axis)
    result = jnp.expand_dims(a.larray, axis)
    split = a.split
    if split is not None and split >= axis:
        split += 1
    return _wrap(result, a, split)


def flatten(a: DNDarray) -> DNDarray:
    """1-D copy (reference: manipulations.py flatten)."""
    sanitation.sanitize_in(a)
    result = a.larray.reshape(-1)
    split = 0 if a.split is not None else None
    return _wrap(result, a, split)


def flip(a: DNDarray, axis=None) -> DNDarray:
    """Reverse element order along axes (reference: manipulations.py flip)."""
    sanitation.sanitize_in(a)
    result = jnp.flip(a.larray, axis=axis)
    return _wrap(result, a, a.split)


def fliplr(a: DNDarray) -> DNDarray:
    return flip(a, 1)


def flipud(a: DNDarray) -> DNDarray:
    return flip(a, 0)


def hsplit(x: DNDarray, indices_or_sections) -> List[DNDarray]:
    """Split along axis 1 (axis 0 for 1-D; reference parity)."""
    return split(x, indices_or_sections, axis=1 if x.ndim > 1 else 0)


def hstack(arrays: Sequence[DNDarray]) -> DNDarray:
    """Horizontal stack."""
    arrays = list(arrays)  # generators survive the _require_dndarray pass
    ref = _require_dndarray(arrays, "hstack")
    axis = 0 if ref.ndim == 1 else 1
    return concatenate(arrays, axis=axis)


def dstack(arrays: Sequence[DNDarray]) -> DNDarray:
    """Depth-wise stack along the third axis (numpy parity; the reference
    ships vstack/hstack/row_stack only — dstack completes the family the
    same way dsplit already does)."""
    arrays = list(arrays)  # generators survive the _require_dndarray pass
    ref = _require_dndarray(arrays, "dstack")
    prepared = [a.larray if isinstance(a, DNDarray) else jnp.asarray(a) for a in arrays]
    result = jnp.dstack(prepared)
    if ref.ndim == 1:
        # dstack maps a 1-D input's data axis to output axis 1 (shape
        # (1, n, k)); a split=0 input's distribution follows it there
        split = 1 if ref.split == 0 else None
    else:
        split = ref.split if (ref.split is not None and ref.split < 2) else None
    return _wrap(result, ref, split)


def moveaxis(x: DNDarray, source, destination) -> DNDarray:
    """Move axes to new positions (reference: manipulations.py moveaxis)."""
    sanitation.sanitize_in(x)
    result = jnp.moveaxis(x.larray, source, destination)
    # track the split through the permutation
    split = x.split
    if split is not None:
        src = [source] if isinstance(source, int) else list(source)
        dst = [destination] if isinstance(destination, int) else list(destination)
        src = [s % x.ndim for s in src]
        dst = [d % x.ndim for d in dst]
        order = [n for n in range(x.ndim) if n not in src]
        for d, s in sorted(zip(dst, src)):
            order.insert(d, s)
        split = order.index(split)
    return _wrap(result, x, split)


def pad(array: DNDarray, pad_width, mode: str = "constant", constant_values=0) -> DNDarray:
    """Pad an array (reference: manipulations.py:1128)."""
    sanitation.sanitize_in(array)
    kwargs = {"constant_values": constant_values} if mode == "constant" else {}
    result = jnp.pad(array.larray, pad_width, mode=mode, **kwargs)
    return _wrap(result, array, array.split)


def ravel(a: DNDarray) -> DNDarray:
    """Flatten (view when possible; reference: manipulations.py ravel)."""
    return flatten(a)


def redistribute(arr: DNDarray, lshape_map=None, target_map=None) -> DNDarray:
    """Out-of-place redistribute (reference: manipulations.py:1513)."""
    from .memory import copy as _copy

    out = _copy(arr)
    out.redistribute_(lshape_map=lshape_map, target_map=target_map)
    return out


def repeat(a: DNDarray, repeats, axis=None) -> DNDarray:
    """Repeat elements (reference: manipulations.py:1570)."""
    sanitation.sanitize_in(a)
    r = repeats.larray if isinstance(repeats, DNDarray) else repeats
    result = jnp.repeat(a.larray, r, axis=axis)
    # axis=None flattens: any distributed input ends up split along axis 0
    split = 0 if (axis is None and a.split is not None) else a.split
    return _wrap(result, a, split)


def reshape(a: DNDarray, *shape, new_split=None) -> DNDarray:
    """Reshape (reference: manipulations.py:1821 — resplit-to-0 + Alltoallv
    there).  ``new_split`` sets the split of the result (defaults to the
    input's split when the dim count allows, else 0 for distributed inputs).

    Distributed→distributed reshapes route through the tiled transport
    engine (:mod:`heat_tpu.parallel.transport`): split-preserving shapes
    reshape each shard locally (collective-free); split-crossing shapes run
    resplit-to-0 → flat rechunk (one ``ppermute`` per host-known chunk-
    boundary shift) → resplit-to-target, all on physical arrays with the
    stage intermediates donated.  Shapes outside the engine's plan budget —
    and replicated inputs or outputs — keep the global-``jnp.reshape``
    route, where XLA emits the collectives."""
    sanitation.sanitize_in(a)
    if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
        shape = tuple(shape[0])
    shape = stride_tricks.sanitize_shape(shape, lval=-1)
    known = [d for d in shape if d != -1]
    n_unknown = sum(1 for d in shape if d == -1)
    prod = int(np.prod(known)) if known else 1
    if n_unknown > 1:
        raise ValueError("can only specify one unknown dimension")
    if (n_unknown == 0 and prod != a.size) or (
        n_unknown == 1 and (prod == 0 or a.size % prod != 0)
    ):
        raise ValueError(
            f"cannot reshape array of size {a.size} into shape {tuple(shape)}"
        )
    gout = tuple(a.size // prod if d == -1 else int(d) for d in shape)
    if new_split is None:
        if a.split is None:
            new_split = None
        elif a.split < len(gout):
            new_split = a.split
        else:
            new_split = 0
    if (
        a.split is not None
        and new_split is not None
        and a.comm.size > 1
        and len(gout) >= 1
    ):
        try:
            ns = stride_tricks.sanitize_axis(gout, new_split)
        except (ValueError, TypeError):
            ns = None
        if ns is not None and transport.reshape_applicable(
            a.shape, a.split, gout, ns, a.comm
        ):
            phys = None
            if a.split != 0:
                # split-crossing reshape stages through split 0: a pending
                # lazy chain can fuse its elementwise tail into that first
                # resplit's tile loop, and the fused output (owned solely
                # by this call) is donated to the remaining stages
                preserving = (
                    transport._prefix_prod(a.shape, a.split)
                    == transport._prefix_prod(gout, ns)
                    and int(a.shape[a.split]) == int(gout[ns])
                )
                if not preserving:
                    fused0 = fusion.materialize_resplit(a, 0)
                    if fused0 is not None:
                        phys = transport.tiled_reshape(
                            fused0, a.shape, 0, gout, ns, a.comm, donate=True
                        )
                        spmd_sanitize.poison(
                            fused0,
                            donated_site="manipulations.reshape(stage0)",
                        )
            if phys is None:
                phys = transport.tiled_reshape(
                    a.parray, a.shape, a.split, gout, ns, a.comm
                )
            return DNDarray(
                phys, gout, a.dtype, ns, a.device, a.comm
            )
    result = jnp.reshape(a.larray, shape)
    return _wrap(result, a, new_split)


def resplit(arr: DNDarray, axis: Optional[int] = None) -> DNDarray:
    """Out-of-place re-partition (reference: manipulations.py:3325 — axis=None
    is an Allgatherv there).  Axis-to-axis moves run through the tiled
    transport engine on the physical array (bounded ``all_to_all`` tiles, no
    unpad/re-pad round trip; the input buffer is NOT donated — the caller
    keeps its array); moves to/from ``split=None`` keep the ``device_put``
    route."""
    sanitation.sanitize_in(arr)
    axis = stride_tricks.sanitize_axis(arr.shape, axis)
    if axis == arr.split:
        return arr
    if transport.resplit_applicable(arr.shape, arr.split, axis, arr.comm):
        # a still-pending lazy chain lowers its elementwise tail directly
        # into the per-tile all_to_all loop (no old-split materialization);
        # `arr` itself stays pending for any other consumers
        physical = fusion.materialize_resplit(arr, axis)
        if physical is None:
            physical = transport.tiled_resplit(
                arr.parray, arr.shape, arr.split, axis, arr.comm, donate=False
            )
    else:
        physical = _to_physical(arr.larray, arr.shape, axis, arr.comm)
    return DNDarray(physical, arr.shape, arr.dtype, axis, arr.device, arr.comm)


def roll(x: DNDarray, shift, axis=None) -> DNDarray:
    """Circular shift (reference: manipulations.py:1983 — Isend/Irecv ring
    there; XLA's collective-permute here)."""
    sanitation.sanitize_in(x)
    result = jnp.roll(x.larray, shift, axis=axis)
    return _wrap(result, x, x.split)


def rot90(m: DNDarray, k: int = 1, axes=(0, 1)) -> DNDarray:
    """Rotate in a plane (reference: manipulations.py rot90)."""
    sanitation.sanitize_in(m)
    result = jnp.rot90(m.larray, k=k, axes=axes)
    split = m.split
    if split is not None and k % 2 == 1:
        a0, a1 = axes[0] % m.ndim, axes[1] % m.ndim
        if split == a0:
            split = a1
        elif split == a1:
            split = a0
    return _wrap(result, m, split)


def row_stack(arrays: Sequence[DNDarray]) -> DNDarray:
    return vstack(arrays)


def shape(a: DNDarray) -> Tuple[int, ...]:
    """Global shape (reference: manipulations.py shape)."""
    return a.shape


def sort(a: DNDarray, axis: int = -1, descending: bool = False, out=None):
    """Sort along an axis; returns (sorted, original indices) like the
    reference (manipulations.py:2261 — a hand-written sample sort with ragged
    Alltoallv there).

    When the sorted axis is the split axis, a block odd-even merge-split
    network over the mesh does the sort (``parallel/sort.py``): only
    collective-permutes of one shard block per round, never an all-gather of
    the data axis, so sorting scales past one device's memory.  Other axes
    sort locally per shard.
    """
    sanitation.sanitize_in(a)
    axis = stride_tricks.sanitize_axis(a.shape, axis)
    if a.split == axis and a.comm.size > 1 and a.is_distributed():
        from ..parallel.sort import distributed_sort

        arr = a.parray
        payloads = ()
        if descending:
            # sort a monotone-decreasing transform of the keys instead of
            # flipping the ascending result: a flip would reverse tie
            # order, making duplicate-value indices differ from the
            # single-device stable descending path (mesh-invariance).
            # Floats need a NaN-aware total-order key — descending sorts
            # (jnp, reference torch.sort) put NaNs FIRST, but negation
            # leaves NaN as NaN (ordered last).  IEEE total-order bit
            # trick: canonicalize NaNs, bitcast to the signed int whose
            # ascending order equals the float ascending order, then
            # bitwise-NOT to reverse it (NaN key becomes most negative →
            # sorts to the global front).  The key transform is lossy
            # (-0.0 → +0.0, NaN payload bits), so the ORIGINAL values
            # ride the sort network as an aligned payload and are returned
            # bit-exact.  Ints and bools use bitwise NOT directly
            # (~k = -k-1, bijective, no INT_MIN overflow) — exact, no
            # payload needed.
            if jnp.issubdtype(arr.dtype, jnp.floating):
                int_dtype = jnp.dtype(f"int{jnp.finfo(arr.dtype).bits}")
                mask = np.array(jnp.iinfo(int_dtype).max, int_dtype)

                def _to_key(v):
                    v = jnp.where(jnp.isnan(v), jnp.array(jnp.nan, v.dtype), v)
                    b = jax.lax.bitcast_convert_type(v, int_dtype)
                    # canonicalize -0.0 (bit pattern == signed int min) to
                    # +0.0 at the BIT level: keeps ±0 a tie (broken by
                    # index) like the stable local path.  Float `v + 0`
                    # would do the same but flushes subnormals to zero on
                    # TPU, collapsing them into the tie class.
                    b = jnp.where(
                        b == np.array(jnp.iinfo(int_dtype).min, int_dtype),
                        np.array(0, int_dtype),
                        b,
                    )
                    return ~jnp.where(b < 0, b ^ mask, b)

                payloads = (arr,)
                arr = _to_key(arr)
                undo = None
            elif arr.dtype == jnp.bool_:
                arr, undo = ~arr, lambda v: ~v
            else:
                arr, undo = jnp.invert(arr), jnp.invert
        values, indices, *rest = distributed_sort(
            arr, a.comm.mesh, a.comm.split_axis, axis, a.shape[axis],
            payloads=payloads,
        )
        if descending:
            values = rest[0] if payloads else undo(values)
        v = DNDarray(values, a.shape, a.dtype, a.split, a.device, a.comm)
        i = DNDarray(
            indices, a.shape, types.canonical_heat_type(indices.dtype),
            a.split, a.device, a.comm,
        )
    else:
        arr = a.larray
        indices = jnp.argsort(arr, axis=axis, descending=descending, stable=True)
        values = jnp.take_along_axis(arr, indices, axis=axis)
        v = _wrap(values, a, a.split)
        i = _wrap(indices, a, a.split)
    if out is not None:
        out.larray = v.larray
        return out, i
    return v, i


def split(x: DNDarray, indices_or_sections, axis: int = 0) -> List[DNDarray]:
    """Split into sub-arrays (reference: manipulations.py split)."""
    sanitation.sanitize_in(x)
    axis = stride_tricks.sanitize_axis(x.shape, axis)
    if isinstance(indices_or_sections, DNDarray):
        indices_or_sections = np.asarray(indices_or_sections.larray)
    if isinstance(indices_or_sections, (list, tuple, np.ndarray)):
        parts = jnp.split(x.larray, np.asarray(indices_or_sections), axis=axis)
    else:
        parts = jnp.split(x.larray, int(indices_or_sections), axis=axis)  # ht: HT002 ok — indices_or_sections is a caller-supplied host argument
    split_ = None if axis == x.split else x.split
    return [_wrap(p, x, split_) for p in parts]


def squeeze(x: DNDarray, axis=None) -> DNDarray:
    """Remove size-1 dims (reference: manipulations.py squeeze)."""
    sanitation.sanitize_in(x)
    result = jnp.squeeze(x.larray, axis=axis)
    split = x.split
    if split is not None:
        removed = (
            [i for i in range(x.ndim) if x.shape[i] == 1]
            if axis is None
            else [a % x.ndim for a in (axis if isinstance(axis, (tuple, list)) else (axis,))]
        )
        if split in removed:
            split = None
        else:
            split -= sum(1 for r in removed if r < split)
    return _wrap(result, x, split)


def stack(arrays: Sequence[DNDarray], axis: int = 0, out=None) -> DNDarray:
    """Join along a new axis (reference: manipulations.py stack)."""
    arrays = list(arrays)  # generators survive the _require_dndarray pass
    ref = _require_dndarray(arrays, "stack")
    prepared = [a.larray if isinstance(a, DNDarray) else jnp.asarray(a) for a in arrays]
    result = jnp.stack(prepared, axis=axis)
    split = ref.split
    if split is not None and axis % result.ndim <= split:
        split += 1
    wrapped = _wrap(result, ref, split)
    if out is not None:
        out.larray = wrapped.larray
        return out
    return wrapped


def swapaxes(x: DNDarray, axis1: int, axis2: int) -> DNDarray:
    """Interchange two axes (reference: manipulations.py swapaxes)."""
    sanitation.sanitize_in(x)
    a1, a2 = axis1 % x.ndim, axis2 % x.ndim
    result = jnp.swapaxes(x.larray, a1, a2)
    split = x.split
    if split == a1:
        split = a2
    elif split == a2:
        split = a1
    return _wrap(result, x, split)


def tile(x: DNDarray, reps) -> DNDarray:
    """Tile an array (reference: manipulations.py:3574)."""
    sanitation.sanitize_in(x)
    result = jnp.tile(x.larray, reps)
    split = x.split
    if split is not None:
        split = split + (result.ndim - x.ndim)
    return _wrap(result, x, split)


def topk(a: DNDarray, k: int, dim: int = -1, largest: bool = True, sorted: bool = True, out=None):
    """Top-k values and indices (reference: manipulations.py:3830 + custom MPI
    reduce mpi_topk:3981).

    Along a split axis this runs shard-local top-k plus one all-gather of
    the small candidate pool (``parallel/sort.py:distributed_topk``) — the
    data axis itself is never gathered."""
    sanitation.sanitize_in(a)
    dim = stride_tricks.sanitize_axis(a.shape, dim)
    if k > a.shape[dim]:
        # match lax.top_k's behavior on the unsplit path (the distributed
        # path would otherwise silently return padding sentinels)
        raise ValueError(f"k={k} exceeds dimension size {a.shape[dim]}")
    if a.split == dim and a.comm.size > 1 and a.is_distributed():
        from ..parallel.sort import distributed_topk

        values, indices = distributed_topk(
            a.parray, a.comm.mesh, a.comm.split_axis, dim, a.shape[dim],
            int(k), largest,
        )
        shape = tuple(int(k) if d == dim else s for d, s in enumerate(a.shape))
        v = DNDarray(values, shape, a.dtype, None, a.device, a.comm)
        i = DNDarray(
            indices.astype(jnp.int64 if jax.config.jax_enable_x64 else jnp.int32),
            shape, types.canonical_heat_type(
                jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
            ), None, a.device, a.comm,
        )
        if out is not None:
            out[0].larray = v.larray
            out[1].larray = i.larray
            return out
        return v, i
    arr = a.larray
    if dim != a.ndim - 1:
        arr = jnp.moveaxis(arr, dim, -1)
    if largest:
        values, indices = jax.lax.top_k(arr, k)
    else:
        values, indices = jax.lax.top_k(-arr, k)
        values = -values
    if dim != a.ndim - 1:
        values = jnp.moveaxis(values, -1, dim)
        indices = jnp.moveaxis(indices, -1, dim)
    split = None if a.split == dim else a.split
    v = _wrap(values, a, split)
    i = _wrap(indices.astype(jnp.int64 if jax.config.jax_enable_x64 else jnp.int32), a, split)
    if out is not None:
        out[0].larray = v.larray
        out[1].larray = i.larray
        return out
    return v, i


def mpi_topk(a, b, dim: int = -1, largest: bool = True, sorted: bool = True):
    """Combine two partial top-k results (reference: manipulations.py:3981, a
    custom MPI reduce op over metadata-prefixed byte buffers).  XLA reduces
    arbitrary computations so :func:`topk` never needs this; it survives as a
    functional combiner for reference-API code: each operand is a
    ``(values, indices)`` pair, the result is the top-k of their
    concatenation along ``dim`` where ``k = values.shape[dim]``."""
    (av, ai), (bv, bi) = a, b
    k = av.shape[dim]
    values = jnp.concatenate((jnp.asarray(av), jnp.asarray(bv)), axis=dim)
    indices = jnp.concatenate((jnp.asarray(ai), jnp.asarray(bi)), axis=dim)
    if dim not in (-1, values.ndim - 1):
        values = jnp.moveaxis(values, dim, -1)
        indices = jnp.moveaxis(indices, dim, -1)
    top, sel = jax.lax.top_k(values if largest else -values, k)
    if not largest:
        top = -top
    picked = jnp.take_along_axis(indices, sel, axis=-1)
    if dim not in (-1, top.ndim - 1):
        top = jnp.moveaxis(top, -1, dim)
        picked = jnp.moveaxis(picked, -1, dim)
    return top, picked


def unique(a: DNDarray, sorted: bool = False, return_inverse: bool = False, axis=None):
    """Unique elements (reference: manipulations.py:3048 — local unique +
    gather + re-unique there). Result is replicated: its size is data-
    dependent.

    A split 1-D input goes through the distributed sort (parallel/sort.py)
    first, then ON-DEVICE per-shard dedup + compaction (one ppermute
    carries each left neighbor's last element for the boundary compare —
    round 3; the previous host loop pulled every sorted slab to numpy,
    O(n) device-to-host traffic per call).  The host reads the tiny per-shard
    counts and then transfers exactly the uniques, one compacted slab
    prefix at a time — never the full data axis.
    """
    sanitation.sanitize_in(a)
    if (
        axis is None
        and a.ndim == 1
        and a.split == 0
        and a.comm.size > 1
        and a.is_distributed()
    ):
        from ..parallel.sort import unique_compact_sorted

        sv, _ = sort(a, axis=0)
        phys = sv.parray
        n = a.shape[0]
        compacted, counts = unique_compact_sorted(
            phys, a.comm.mesh, a.comm.split_axis, n
        )
        counts_host = np.asarray(counts)
        from .dndarray import _split_axis_shards

        shards = _split_axis_shards(compacted, 0)
        parts = []
        for r, sh in enumerate(shards):
            c = int(counts_host[r])  # ht: HT002 ok — per-shard counts already fetched to host above
            if c:
                # slice ON DEVICE before the transfer: np.asarray of the
                # whole slab would move the full padded buffer to host —
                # the O(n) traffic this path exists to avoid
                parts.append(np.asarray(sh.data[:c]))
        np_dtype = np.dtype(a.dtype.jax_type())
        uni = np.concatenate(parts) if parts else np.empty(0, dtype=np_dtype)
        vals = jnp.asarray(uni)
        v = DNDarray(
            vals, tuple(vals.shape), types.canonical_heat_type(vals.dtype),
            None, a.device, a.comm,
        )
        if return_inverse:
            inverse = jnp.searchsorted(vals, a.larray)
            if np.issubdtype(np_dtype, np.floating):
                # NaN queries: make the mapping to the collapsed NaN slot
                # explicit instead of leaning on searchsorted's NaN-last
                # total order (reference parity: numpy maps every NaN input
                # to the single NaN in the uniques)
                nan_slots = np.nonzero(np.isnan(uni))[0]
                if nan_slots.size:
                    inverse = jnp.where(
                        jnp.isnan(a.larray), jnp.asarray(int(nan_slots[0]), inverse.dtype), inverse
                    )
            # the inverse is elementwise-indexed like the input: keep it
            # sharded the same way (was replicated pre-round-4 — an n-sized
            # replicated buffer for a split input)
            from .dndarray import _to_physical

            inv = DNDarray(
                _to_physical(inverse, tuple(inverse.shape), a.split, a.comm),
                tuple(inverse.shape),
                types.canonical_heat_type(inverse.dtype), a.split, a.device, a.comm,
            )
            return v, inv
        return v
    if return_inverse:
        vals, inverse = jnp.unique(a.larray, return_inverse=True, axis=axis)
        v = DNDarray(vals, tuple(vals.shape), types.canonical_heat_type(vals.dtype), None, a.device, a.comm)
        inv = DNDarray(inverse, tuple(inverse.shape), types.canonical_heat_type(inverse.dtype), None, a.device, a.comm)
        return v, inv
    vals = jnp.unique(a.larray, axis=axis)
    return DNDarray(vals, tuple(vals.shape), types.canonical_heat_type(vals.dtype), None, a.device, a.comm)


def vsplit(x: DNDarray, indices_or_sections) -> List[DNDarray]:
    return split(x, indices_or_sections, axis=0)


def vstack(arrays: Sequence[DNDarray]) -> DNDarray:
    arrays = list(arrays)  # generators survive the _require_dndarray pass
    ref = _require_dndarray(arrays, "vstack")
    prepared = []
    for a in arrays:
        v = a.larray if isinstance(a, DNDarray) else jnp.asarray(a)
        if v.ndim == 1:
            v = v.reshape(1, -1)
        prepared.append(v)
    result = jnp.vstack(prepared)
    # 1-D inputs become rows: their element axis (old split 0) is now axis 1
    split = ref.split if ref.ndim > 1 else (1 if ref.split == 0 else None)
    return _wrap(result, ref, split)


# method bindings
DNDarray.reshape = lambda self, *shape, **kw: reshape(self, *shape, **kw)
DNDarray.flatten = lambda self: flatten(self)
DNDarray.ravel = lambda self: ravel(self)
DNDarray.squeeze = lambda self, axis=None: squeeze(self, axis)
DNDarray.expand_dims = lambda self, axis: expand_dims(self, axis)
DNDarray.resplit = lambda self, axis=None: resplit(self, axis)
DNDarray.flip = lambda self, axis=None: flip(self, axis)
DNDarray.rot90 = lambda self, k=1, axes=(0, 1): rot90(self, k, axes)
DNDarray.swapaxes = lambda self, axis1, axis2: swapaxes(self, axis1, axis2)
DNDarray.redistribute = lambda self, lshape_map=None, target_map=None: redistribute(self, lshape_map, target_map)
DNDarray.balance = lambda self, copy=False: balance(self, copy)
