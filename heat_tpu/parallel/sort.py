"""Distributed sort along a split axis: columnsort at scale, block
odd-even merge-split on small meshes.

The reference sorts a split axis with a hand-written sample sort — local
sort, splitter exchange, ragged ``Alltoallv``, local merge
(heat/core/manipulations.py:2261-3047).  Ragged exchanges don't exist on
TPU: XLA collectives are static-shape.  Two TPU-native redesigns, chosen
by mesh size:

**Columnsort** (Leighton 1985) for ``nshards >= 6`` — the pod-scale path.
Each shard's block is one column of an ``r x s`` matrix.  Five
data-oblivious steps sort it: local sort, transpose-deal (ONE static
``all_to_all`` — the permutation is an involution, so the untranspose is
the *same* collective), local sort, the same all_to_all again, local
sort; after these, every element is provably within half a column of its
final position (requires ``r >= 2(s-1)^2``, checked at dispatch), so
three adjacent merge-split rounds finish the job.  Total wire traffic is
~6 block-volumes regardless of mesh size — O(n), matching the sample
sort's "move the data about once" property with zero dynamic shapes —
where the odd-even network moves O(n * nshards).

**Block odd-even transposition sort** for small meshes (and as the
fallback when the input is too small for columnsort's r-bound): every
shard keeps a fixed-size block, each round partners exchange whole blocks
over ICI (``ppermute``) and run a merge-split (left partner keeps the
lower half, right the upper).  After ``n_shards`` rounds the blocks are
globally ordered (Knuth TAOCP 5.3.4).

Both paths share the properties that matter:

- static shapes end to end (the padded physical layout *is* the block),
- peak per-device memory of a few blocks (the global array never lands in
  one place — the reference's reason for sample sort, kept),
- only static collectives on the wire: no all-gather of the data axis.

Correctness detail: each merge orders by the **total** key
``(pad, value, original index)``.  Totality is load-bearing, not a
stylistic choice — the partners concatenate in opposite orders
``(mine, theirs)``, so a mere ``(pad, value)`` key would let them disagree
on tie order and the kept lower/upper halves could double-count one
partner's duplicates while dropping the other's.  The index tiebreak makes
both partners compute the same merged sequence, and as a bonus the sort is
stable and its result independent of the mesh size.

Pads sink to the global tail (their key class orders last), which is
exactly the canonical physical layout of a split DNDarray, and NaNs keep
NumPy's "sorted last among valid" position without sentinel arithmetic.

``payloads`` ride along with the keys: each merge round moves payload
blocks with the same ``ppermute`` and reorders them with the same argsort.
*Aligned* payloads (same shape as the keys) work for any key rank — the
descending float sort rides its untransformed values this way; *row*
payloads (extra trailing dims, 1-D keys only) are the sharded Fisher–Yates
replacement (sort-by-random-key) behind ``randperm``/``permutation`` and
the epoch shuffle (reference: random.py:649, utils/data/datatools.py:246).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from .collectives import axis_size, shard_map_unchecked

__all__ = ["distributed_sort", "distributed_topk", "unique_compact_sorted"]


def _apply_order(order, arrs, axis):
    """Gather every array by ``order`` along ``axis``; payloads with extra
    trailing dims (1-D keys only) use a plain take on axis 0."""
    key_ndim = order.ndim
    out = []
    for a in arrs:
        if a.ndim == key_ndim:
            out.append(jnp.take_along_axis(a, order, axis=axis))
        else:
            out.append(jnp.take(a, order, axis=0))
    return out


def _total_sort(arrs, axis, *, index_presorted=False):
    """Stable-sort ``arrs = [vals, idxs, pad, *payloads]`` by the total key
    ``(pad, value, index)`` via three stable argsort passes (least
    significant first)."""
    if not index_presorted:
        order = jnp.argsort(arrs[1], axis=axis, stable=True)
        arrs = _apply_order(order, arrs, axis)
    order = jnp.argsort(arrs[0], axis=axis, stable=True)
    arrs = _apply_order(order, arrs, axis)
    order = jnp.argsort(arrs[2], axis=axis, stable=True)
    return _apply_order(order, arrs, axis)


def _merge_split_round(arrs, axis, ndim, r, per, nshards, parity, axis_name):
    """One odd-even round: adjacent pairs ((0,1)(2,3)… when ``parity`` is
    even, (1,2)(3,4)… when odd) exchange whole blocks over ICI and run a
    merge-split — the left partner keeps the lower ``per`` of the merged
    2*per block, the right the upper.  Shards without a partner this round
    pass through unchanged."""
    perm = []
    for left in range(parity, nshards - 1, 2):
        perm.append((left, left + 1))
        perm.append((left + 1, left))
    if not perm:
        return arrs
    others = [lax.ppermute(a, axis_name, perm) for a in arrs]
    has_partner = jnp.zeros((), bool)
    is_left = jnp.zeros((), bool)
    for s, d in perm:
        has_partner = has_partner | (r == s)
        if s < d:
            is_left = is_left | (r == s)
    merged = _total_sort(
        [
            jnp.concatenate((a, o), axis=axis if a.ndim == ndim else 0)
            for a, o in zip(arrs, others)
        ],
        axis,
    )
    lo_hi = []
    for m in merged:
        ax = axis if m.ndim == ndim else 0
        sel_lo = [slice(None)] * m.ndim
        sel_hi = [slice(None)] * m.ndim
        sel_lo[ax] = slice(0, per)
        sel_hi[ax] = slice(per, 2 * per)
        lo_hi.append(jnp.where(is_left, m[tuple(sel_lo)], m[tuple(sel_hi)]))
    return [jnp.where(has_partner, m, a) for m, a in zip(lo_hi, arrs)]


def _build_sorter(mesh, axis_name, axis, ndim, n_valid, per, payload_ndims=()):
    """Build the shard_map'd odd-even merge-split sorter (jitted once per
    (mesh, axis, shape-class) through the lru cache below).

    Payloads come in two layouts: *aligned* payloads share the key's shape
    and sharding and are permuted with ``take_along_axis`` (e.g. original
    float values riding a transformed sort key); *row* payloads (1-D keys
    only) are axis-0-sharded row blocks moved with a plain ``take``."""
    nshards = mesh.shape[axis_name]
    spec_list = [None] * ndim
    spec_list[axis] = axis_name
    key_spec = P(*spec_list)
    payload_specs = tuple(
        key_spec if pnd == ndim else P(axis_name) for pnd in payload_ndims
    )

    def local(phys_vals, *payloads):
        r = lax.axis_index(axis_name)
        shape = phys_vals.shape
        axis_shape = tuple(per if d == axis else 1 for d in range(ndim))
        # global position along the sort axis of each local element
        pos = r * per + jnp.arange(per)
        pad = jnp.broadcast_to((pos >= n_valid).reshape(axis_shape), shape)
        idxs = jnp.broadcast_to(pos.reshape(axis_shape), shape).astype(jnp.int32)

        arrs = _total_sort(
            [phys_vals, idxs, pad, *payloads], axis, index_presorted=True
        )

        for round_ in range(nshards):
            arrs = _merge_split_round(
                arrs, axis, ndim, r, per, nshards, round_ % 2, axis_name
            )
        vals, idxs, _ = arrs[0], arrs[1], arrs[2]
        return (vals, idxs, *arrs[3:])

    in_specs = (key_spec,) + payload_specs
    out_specs = (key_spec, key_spec) + payload_specs
    return shard_map_unchecked(local, mesh, in_specs=in_specs, out_specs=out_specs)


@lru_cache(maxsize=None)
def _jit_sorter(mesh, axis_name, axis, ndim, n_valid, per, payload_ndims):
    return jax.jit(
        _build_sorter(mesh, axis_name, axis, ndim, n_valid, per, payload_ndims)
    )


def columnsort_applicable(nshards: int, per: int) -> bool:
    """Leighton's r-bound: a column of ``r`` rows over ``s`` columns is
    sortable by the 5-step schedule iff ``r >= 2(s-1)^2`` (r here is the
    block padded up to a multiple of s for the transpose-deal).  Below 6
    shards the odd-even network needs <= 5 rounds anyway, so columnsort's
    fixed ~6-block-volume cost wouldn't pay."""
    per_pad = -(-per // nshards) * nshards
    return nshards >= 6 and per_pad >= 2 * (nshards - 1) ** 2


def _build_columnsort(mesh, axis_name, axis, ndim, n_valid, per, payload_ndims=()):
    """Build the shard_map'd columnsort (see the module docstring).

    The sort axis is normalized to axis 0 inside the kernel: keys and
    aligned payloads are ``moveaxis``-ed so every step (local total sort,
    the transpose-deal all_to_all, merge-split cleanup, compaction) is an
    axis-0 operation for every carried array, row payloads included.
    """
    nshards = mesh.shape[axis_name]
    b_sub = -(-per // nshards)          # ceil: rows per transpose sub-block
    per_pad = b_sub * nshards           # column height r (divisible by s)
    extra = per_pad - per
    n_total = per * nshards             # size of the physical layout
    spec_list = [None] * ndim
    spec_list[axis] = axis_name
    key_spec = P(*spec_list)
    payload_specs = tuple(
        key_spec if pnd == ndim else P(axis_name) for pnd in payload_ndims
    )

    def local(phys_vals, *payloads):
        r = lax.axis_index(axis_name)
        x = jnp.moveaxis(phys_vals, axis, 0)
        pls = [
            jnp.moveaxis(p, axis, 0) if p.ndim == ndim else p for p in payloads
        ]
        lead = (per,) + (1,) * (x.ndim - 1)
        pos = r * per + jnp.arange(per)
        pad = jnp.broadcast_to((pos >= n_valid).reshape(lead), x.shape)
        idxs = jnp.broadcast_to(pos.reshape(lead), x.shape).astype(jnp.int32)
        arrs = [x, idxs, pad, *pls]

        if extra:
            # pad the column up to r = per_pad: extension rows carry the
            # pad flag (they sort to the global tail) and unique indices
            # beyond every real position (deterministic tie order)
            epos = (n_total + r * extra + jnp.arange(extra)).astype(jnp.int32)
            elead = (extra,) + (1,) * (x.ndim - 1)

            def extend(a, fill_rows):
                return jnp.concatenate((a, fill_rows), axis=0)

            arrs = [
                extend(x, jnp.zeros((extra,) + x.shape[1:], x.dtype)),
                extend(
                    idxs,
                    jnp.broadcast_to(
                        epos.reshape(elead), (extra,) + x.shape[1:]
                    ),
                ),
                extend(pad, jnp.ones((extra,) + pad.shape[1:], bool)),
                *[
                    extend(p, jnp.zeros((extra,) + p.shape[1:], p.dtype))
                    for p in pls
                ],
            ]

        # Leighton's transpose is a round-robin deal: element i of column
        # j goes to column (i mod s), landing at row j*b + i//s.  The
        # cyclic subsequence destined for shard c is made contiguous by a
        # local (b, s) reshape + swap, so ONE static tiled all_to_all
        # ships it; the untranspose is the inverse — the same all_to_all
        # followed by the mirrored local permute.
        def deal(a):
            rest = a.shape[1:]
            y = jnp.swapaxes(a.reshape((b_sub, nshards) + rest), 0, 1)
            y = y.reshape((per_pad,) + rest)
            return lax.all_to_all(
                y, axis_name, split_axis=0, concat_axis=0, tiled=True
            )

        def undeal(a):
            rest = a.shape[1:]
            z = lax.all_to_all(
                a, axis_name, split_axis=0, concat_axis=0, tiled=True
            )
            z = jnp.swapaxes(z.reshape((nshards, b_sub) + rest), 0, 1)
            return z.reshape((per_pad,) + rest)

        # steps 1-5: sort, transpose, sort, untranspose, sort
        arrs = _total_sort(arrs, 0, index_presorted=True)
        arrs = [deal(a) for a in arrs]
        arrs = _total_sort(arrs, 0)
        arrs = [undeal(a) for a in arrs]
        arrs = _total_sort(arrs, 0)

        # steps 6-8: every element is now within r/2 of its final position
        # (Leighton's bound under r >= 2(s-1)^2), i.e. within one column of
        # home and only dirty across a single boundary — adjacent
        # merge-split rounds (even, odd + one spare even) finish the sort
        # without the shift's conceptual extra column
        for parity in (0, 1, 0):
            arrs = _merge_split_round(
                arrs, 0, arrs[0].ndim, r, per_pad, nshards, parity, axis_name
            )

        if extra:
            # compact the per_pad layout back to the canonical per layout:
            # output shard q needs sorted positions [q*per, (q+1)*per),
            # which lie in source shards {q-1, q} (per_pad - per < s and
            # per_pad >= 2(s-1)^2 >= s^2 bound the drift to one shard), so
            # one neighbor ppermute + a static-length slice suffice
            ring = [(i, (i + 1) % nshards) for i in range(nshards)]
            prevs = [lax.ppermute(a, axis_name, ring) for a in arrs]
            start = r * per - (r - 1) * per_pad
            arrs = [
                lax.dynamic_slice_in_dim(
                    jnp.concatenate((pv, a), axis=0), start, per, axis=0
                )
                for pv, a in zip(prevs, arrs)
            ]

        vals = jnp.moveaxis(arrs[0], 0, axis)
        idxs_out = jnp.moveaxis(arrs[1], 0, axis)
        outs = [
            jnp.moveaxis(a, 0, axis) if pnd == ndim else a
            for a, pnd in zip(arrs[3:], payload_ndims)
        ]
        return (vals, idxs_out, *outs)

    in_specs = (key_spec,) + payload_specs
    out_specs = (key_spec, key_spec) + payload_specs
    return shard_map_unchecked(local, mesh, in_specs=in_specs, out_specs=out_specs)


@lru_cache(maxsize=None)
def _jit_columnsort(mesh, axis_name, axis, ndim, n_valid, per, payload_ndims):
    return jax.jit(
        _build_columnsort(mesh, axis_name, axis, ndim, n_valid, per, payload_ndims)
    )


def _build_topk(mesh, axis_name, axis, ndim, n_valid, per, k, largest):
    """Shard_map'd distributed top-k: local top-k per shard (any global
    winner is in its own shard's local top-k), then one all-gather of the
    tiny (nshards * min(k, per)) candidate pool — never the data axis
    (the reference's mpi_topk combiner tree, manipulations.py:3981,
    restated as a single small collective)."""
    k_local = min(k, per)
    in_spec_list = [None] * ndim
    in_spec_list[axis] = axis_name
    in_spec = P(*in_spec_list)

    def local(block):
        r = lax.axis_index(axis_name)
        vals = jnp.moveaxis(block, axis, -1)
        dtype = vals.dtype
        if jnp.issubdtype(dtype, jnp.floating):
            worst = jnp.array(-jnp.inf if largest else jnp.inf, dtype)
        elif dtype == jnp.bool_:
            worst = jnp.array(not largest, dtype)
        else:
            info = jnp.iinfo(dtype)
            worst = jnp.array(info.min if largest else info.max, dtype)
        pos = r * per + jnp.arange(per)
        vals = jnp.where(pos >= n_valid, worst, vals)
        # monotone transform for "smallest": negate floats, bitwise-NOT
        # ints/bools (~x = -x-1 — bijective, no INT_MIN overflow)
        if largest:
            tf = lambda a: a  # noqa: E731
        elif jnp.issubdtype(dtype, jnp.floating):
            tf = lambda a: -a  # noqa: E731
        else:
            tf = jnp.invert
        v, i = lax.top_k(tf(vals), k_local)
        v = tf(v)
        gi = (i + r * per).astype(jnp.int32)
        cand_v = lax.all_gather(v, axis_name, axis=v.ndim - 1, tiled=True)
        cand_i = lax.all_gather(gi, axis_name, axis=gi.ndim - 1, tiled=True)
        out_v, sel = lax.top_k(tf(cand_v), k)
        out_v = tf(out_v)
        out_i = jnp.take_along_axis(cand_i, sel, axis=-1)
        return jnp.moveaxis(out_v, -1, axis), jnp.moveaxis(out_i, -1, axis)

    return shard_map_unchecked(
        local, mesh, in_specs=(in_spec,), out_specs=(P(), P())
    )


@lru_cache(maxsize=None)
def _jit_topk(mesh, axis_name, axis, ndim, n_valid, per, k, largest):
    return jax.jit(
        _build_topk(mesh, axis_name, axis, ndim, n_valid, per, k, largest)
    )


def distributed_topk(
    phys_vals: jax.Array, mesh, axis_name: str, axis: int, n_valid: int,
    k: int, largest: bool = True,
):
    """Top-k along a split ``axis`` without gathering it: returns
    replicated ``(values, global indices)`` with the k-extent at ``axis``.
    ``phys_vals`` must carry the canonical even-chunk physical layout."""
    per = phys_vals.shape[axis] // mesh.shape[axis_name]
    fn = _jit_topk(
        mesh, axis_name, axis, phys_vals.ndim, int(n_valid), per, int(k),
        bool(largest),
    )
    return fn(phys_vals)


def distributed_sort(
    phys_vals: jax.Array, mesh, axis_name: str, axis: int, n_valid: int,
    payloads=(), method: str = "auto",
):
    """Sort a physically even-sharded array along its split ``axis``.

    ``phys_vals`` must carry the canonical even-chunk physical layout
    (split dim a multiple of the mesh axis size; tail beyond ``n_valid``
    is pad).  Returns ``(values, indices, *payloads)`` in the same physical
    layout: logical elements globally ascending (stable on ties) with pads
    at the global tail, ``indices`` the original global positions along
    ``axis`` (int32), and every payload reordered by the same permutation.
    Aligned payloads (``payload.ndim == phys_vals.ndim``, same shape and
    sharding as the keys) work for any key rank; row payloads (extra
    trailing dims, axis-0 sharded) require 1-D keys.

    ``method``: "auto" uses columnsort (O(n) wire traffic) when the mesh
    is large enough and the block satisfies Leighton's r-bound, the
    odd-even network otherwise; "columnsort"/"network" force a path (the
    total key makes both produce the identical permutation).
    """
    nshards = mesh.shape[axis_name]
    per = phys_vals.shape[axis] // nshards
    payload_ndims = tuple(p.ndim for p in payloads)
    if any(pnd != phys_vals.ndim for pnd in payload_ndims) and phys_vals.ndim != 1:
        raise ValueError("row payloads require 1-D sort keys")
    if method == "auto":
        method = "columnsort" if columnsort_applicable(nshards, per) else "network"
    if method == "columnsort":
        per_pad = -(-per // nshards) * nshards
        if per_pad < 2 * (nshards - 1) ** 2:
            raise ValueError(
                f"columnsort needs a padded block of >= 2(s-1)^2 = "
                f"{2 * (nshards - 1) ** 2} rows per shard, got {per_pad}; "
                "use method='network'"
            )
        fn = _jit_columnsort(
            mesh, axis_name, axis, phys_vals.ndim, int(n_valid), per,
            payload_ndims,
        )
    elif method == "network":
        fn = _jit_sorter(
            mesh, axis_name, axis, phys_vals.ndim, int(n_valid), per,
            payload_ndims,
        )
    else:
        raise ValueError(f"unknown sort method {method!r}")
    return fn(phys_vals, *payloads)


def _build_unique_compact(mesh, axis_name, n_valid, per):
    """Per-shard dedup + compaction of a SORTED split axis, on device
    (round 3; the previous host loop pulled every sorted slab to numpy —
    O(n) device-to-host traffic per call).  Each shard receives its left
    neighbor's last element with one ppermute, keeps elements that differ
    from their predecessor (NaNs compare EQUAL here: numpy's unique
    collapses them, equal_nan=True), and compacts survivors to its slab
    front.  The host then reads the tiny per-shard counts and transfers
    exactly the uniques."""

    def local(vals):
        r = lax.axis_index(axis_name)
        nshards = axis_size(axis_name)
        pos = r * per + jnp.arange(per)
        validm = pos < n_valid
        ring = [(i, (i + 1) % nshards) for i in range(nshards)]
        prev_last = lax.ppermute(vals[-1:], axis_name, ring)
        prev = jnp.concatenate([prev_last, vals[:-1]])
        same = vals == prev
        if jnp.issubdtype(vals.dtype, jnp.floating):
            # numpy's unique collapses NaNs (equal_nan=True default)
            same = same | (jnp.isnan(vals) & jnp.isnan(prev))
        keep = validm & (~same | (pos == 0))
        order = jnp.argsort(~keep, stable=True)
        cvals = jnp.take(vals, order)
        return cvals, keep.sum(dtype=jnp.int32)[None]

    return shard_map_unchecked(
        local, mesh, in_specs=(P(axis_name),),
        out_specs=(P(axis_name), P(axis_name)),
    )


@lru_cache(maxsize=None)
def _jit_unique_compact(mesh, axis_name, n_valid, per):
    return jax.jit(_build_unique_compact(mesh, axis_name, n_valid, per))


def unique_compact_sorted(phys_sorted: jax.Array, mesh, axis_name: str, n_valid: int):
    """On-device dedup of a sorted physical 1-D split axis: returns
    ``(compacted_slabs, counts)`` — shard r's uniques are
    ``compacted_slabs[r*per : r*per + counts[r]]``."""
    per = phys_sorted.shape[0] // mesh.shape[axis_name]
    fn = _jit_unique_compact(mesh, axis_name, int(n_valid), per)
    return fn(phys_sorted)
