"""K-Means clustering (reference: heat/cluster/kmeans.py, 139 LoC).

The reference's Lloyd iteration issues one Allreduce per cluster per step for
the masked sums (kmeans.py:73-100).  Here the whole iteration — distance
matrix (quadratic expansion on the MXU), argmin, one-hot count/sum matmuls —
is a single jitted XLA program with one fused cross-device reduction
(SURVEY.md §3.4), the benchmark north-star workload.

On unpacked rows an iteration reads the data **once**: where the Pallas tier
is on (``ops/_pallas_common.mode()``) and the input is one the kernel takes
(:func:`_fused_rows`: float32 or bfloat16, at most 128 clusters, a width of
8 to 512 that is no multiple of 128, rows on one device or ``split=0`` over
the mesh), :func:`_lloyd_step` is one pass of ``ops.lloyd_pass`` over the
rows where they lie — distances, argmin, one-hot sums, counts and inertia per
tile in VMEM, nothing of size ``n`` written — and the centre update.  Every
other input, and every backend without the tier, runs the classic body: the
``(n, k)`` distances, then the one-hot GEMM, two passes over a bfloat16 copy
XLA keeps of float32 rows.  ``ht:kmeans.fit`` notes which ran (``lloyd``).

The labels, ``fit``'s and ``predict``'s, are one more run of the same pass on
the inputs it takes (:func:`_labels_of_rows`, reached through the hook
``_labels_by_kernel`` of ``_KCluster._assign_to_cluster``): the kernel writes
the cluster number it assigns each row, 4 bytes a row beside the one read of
the rows, and neither distances nor norms exist.  Every other input takes
the lazy distances and argmin; ``ht:kmeans.labels`` notes which (``assign``).
"""

from __future__ import annotations

from functools import lru_cache, partial
from typing import Optional, Union

import jax
import jax.numpy as jnp
from jax.experimental.layout import Format, Layout
from jax.sharding import PartitionSpec

from ..core.dndarray import DNDarray
from ..core import memtrack, telemetry, types
from ..ops import lloyd_pass
from ..ops.cdist import cdist as ops_cdist
from ..parallel.collectives import shard_map_unchecked
from ..spatial import distance
from ._kcluster import _KCluster

__all__ = ["KMeans"]


# an ``in_shardings`` entry meaning 'let the layout solver choose'
_AUTO_FMT = Format(Layout.AUTO)


def _lloyd_while(step, centers, max_iter, tol):
    """Shared convergence driver: iterate ``step`` until ``shift² <= tol``
    or ``max_iter``, entirely on-device (``lax.while_loop``).  The
    reference reads the convergence scalar back to the host every iteration
    (kmeans.py:102-139, ``.item()`` broadcast); a readback inside the loop
    stalls the device every iteration, so the whole loop is a single XLA
    program and the host sees only the final
    (centers, shift, inertia, n_iter)."""

    def cond(state):
        _, shift, _, it = state
        return jnp.logical_and(it < max_iter, shift > tol)

    def body(state):
        centers, _, _, it = state
        new_centers, shift, inertia = step(centers)
        return new_centers, shift, inertia, it + 1

    # convergence scalars stay f32 whatever the data dtype: shift/inertia
    # come out of f32 distance accumulation, and a bf16 carry would both
    # mismatch the while_loop types and quantize the tol comparison
    init = (centers, jnp.array(jnp.inf, jnp.float32), jnp.array(0.0, jnp.float32), 0)
    with jax.named_scope("ht.kmeans.lloyd"):
        return jax.lax.while_loop(cond, body, init)


# jitted functions whose device scopes (``jax.named_scope``) a reader of
# traces relies on carry a module name of their own: telemetry.module_name


@partial(jax.jit, static_argnames=("k", "fused"))
@telemetry.module_name("ht_lloyd_loop")
def _lloyd_loop(x, centers, k: int, max_iter, tol, fused=None):
    """Lloyd iterations over unpacked data (see :func:`_lloyd_while`);
    ``fused`` is :func:`_lloyd_step`'s."""
    # the classic call stays positional: perf/tests plant their faults by
    # replacing _lloyd_step with a function of (x, centers, k)
    step = _lloyd_step if fused is None else partial(_lloyd_step, fused=fused)
    return _lloyd_while(lambda c: step(x, c, k), centers, max_iter, tol)


def _fused_rows(x: DNDarray, k: int):
    """Where the rows of ``x`` lie, if :func:`_lloyd_step` can take them in
    one pass an iteration, else None: ``(n, mesh, axis)``, the rows on one
    device (``mesh`` None) or split over ``axis`` of ``mesh``.  Decided by
    ``mode()`` and by what the input shows (shape, dtype, layout, split), as
    ``ops/_pallas_common.py`` has it for a kernel that replaces a lowering
    on every input it accepts."""
    if not lloyd_pass.accepts(x.parray, k):
        return None
    if x.comm.n_devices == 1:
        return x.shape[0], None, None
    if x.split == 0:
        return x.shape[0], x.comm.mesh, x.comm.split_axis
    return None


def _pass_over_rows(x, centers, fused, labels=False):
    """``(sums, counts, inertia)`` of one fused pass over all rows: the
    kernel on one device, or on each shard of the physical (evenly padded)
    array under ``shard_map`` with one ``psum`` of the three.  With
    ``labels`` the pass's fourth result alone, every physical row's cluster
    number, split as the rows are and with no collective at all."""
    n, mesh, axis = fused
    if mesh is None:
        got = lloyd_pass.lloyd_pass(x.T, centers, n, labels)
        return got[3] if labels else got

    def shard(xs, c):
        first = jax.lax.axis_index(axis) * xs.shape[0]
        got = lloyd_pass.lloyd_pass(xs.T, c, jnp.clip(n - first, 0, xs.shape[0]), labels)
        return got[3] if labels else jax.lax.psum(got, axis)

    return shard_map_unchecked(
        shard, mesh, in_specs=(PartitionSpec(axis, None), PartitionSpec()),
        out_specs=PartitionSpec(axis) if labels else PartitionSpec())(x, centers)


@partial(jax.jit, static_argnames=("fused",))
@telemetry.module_name("ht_kmeans_labels")
def _labels_of_rows(x, centers, fused):
    """The number of the nearest of ``centers`` for every row of the physical
    array ``x``, ``(rows, 1)`` in the index type ``jnp.argmin`` gives: one
    run of the fused pass (``fused`` is :func:`_fused_rows`'s) that also
    writes what it assigns; no distances are kept."""
    with jax.named_scope("ht.kmeans.labels"):
        numbers = _pass_over_rows(x, centers, fused, labels=True)
        return numbers.astype(jax.dtypes.canonicalize_dtype(jnp.int64)).reshape(-1, 1)


def _moved_centers(sums, counts, centers):
    """The centre update of a Lloyd step and the squared shift it makes:
    the mean of each cluster's rows; an empty cluster keeps its centre."""
    new_centers = jnp.where(
        counts[:, None] > 0, sums / jnp.maximum(counts, 1)[:, None], centers.astype(jnp.float32)
    ).astype(centers.dtype)
    return new_centers, jnp.sum((new_centers - centers).astype(jnp.float32) ** 2)


@partial(jax.jit, static_argnames=("k", "fused"))
@telemetry.module_name("ht_lloyd_step")
def _lloyd_step(x, centers, k: int, fused=None):
    """One fused Lloyd iteration: returns (new_centers, shift², inertia).

    With ``x`` row-sharded and ``centers`` replicated, XLA compiles this to
    local MXU matmuls plus a single psum of the (k, f) sums and (k,) counts.

    ``fused`` (:func:`_fused_rows`) says that ``x`` is the physical array
    and its rows are read once, by ``ops.lloyd_pass``: no distances, labels
    or copy of the rows are kept; the update below is the same.
    """
    if fused is not None:
        sums, counts, inertia = _pass_over_rows(x, centers, fused)
        with jax.named_scope("ht.kmeans.update"):
            new_centers, shift = _moved_centers(sums, counts, centers)
        return new_centers, shift, inertia
    with jax.named_scope("ht.kmeans.assign"):
        d2 = ops_cdist(x, centers, sqrt=False)
        labels = jnp.argmin(d2, axis=1)
    with jax.named_scope("ht.kmeans.update"):
        onehot = (labels[:, None] == jnp.arange(k)[None, :]).astype(x.dtype)
        # counts/sums accumulate in f32 whatever the data dtype: a bf16
        # accumulator drops counts by ~0.2% at 4e5 members and skews centroids
        # (the 0/1 products are exact, only the accumulator needs width)
        counts = jnp.sum(onehot, axis=0, dtype=jnp.float32)
        sums = jax.lax.dot_general(
            onehot, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        new_centers, shift = _moved_centers(sums, counts, centers)
    with jax.named_scope("ht.kmeans.assign"):
        # distance to the assigned (= nearest) centroid is the row minimum; a
        # take_along_axis gather here costs ~20x the rest of the step on TPU
        inertia = jnp.sum(jnp.min(d2, axis=1))
    return new_centers, shift, inertia


@partial(jax.jit, static_argnames=("k",))
def _stream_lloyd_stats(x, valid, centers, k: int):
    """Per-slab Lloyd sufficient statistics for the out-of-core path:
    (masked counts, masked sums, masked inertia) against FIXED centers.

    Same math as :func:`_lloyd_step` — f32 count/sum accumulation, row-min
    inertia — restricted to rows ``[0, valid)`` (the streaming engine
    zero-pads slab tails to keep one compiled bucket per pass; ``valid``
    arrives as a Python int and traces as a weak scalar, so tail slabs hit
    the same executable).  The center UPDATE happens host-side in
    ``fit_stream`` after all slabs of a pass are folded together."""
    x = x.astype(centers.dtype)
    d2 = ops_cdist(x, centers, sqrt=False)
    labels = jnp.argmin(d2, axis=1)
    mask = jnp.arange(x.shape[0]) < valid
    onehot = (labels[:, None] == jnp.arange(k)[None, :]).astype(x.dtype)
    onehot = onehot * mask[:, None].astype(x.dtype)
    counts = jnp.sum(onehot, axis=0, dtype=jnp.float32)
    sums = jax.lax.dot_general(
        onehot, x, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    inertia = jnp.sum(jnp.where(mask, jnp.min(d2, axis=1), 0.0))
    return counts, sums, inertia


@partial(jax.jit, static_argnames=("k", "p", "with_inertia"))
@telemetry.module_name("ht_lloyd_loop_packed")
def _lloyd_loop_packed(x2, sq, valid, centers, k: int, p: int, max_iter, tol,
                       with_inertia: bool = True):
    """Lloyd loop over lane-packed data.

    Sub-128-lane bf16 rows read f32-sized HBM on this chip (layout
    ``T(8,128)(2,1)`` pads the minor dim to 128 lanes — see
    docs/PERFORMANCE.md).  Packing ``p = 128//f`` samples per 128-lane row
    (``x2``: (n/p, 128)) makes every pass over the data read the packed
    bytes: the cross term is one matmul against a block-diagonal centroid
    matrix (slot s's columns see only feature block s), and the masked
    centroid sums slice slot s's feature block out of ``one_hot_sᵀ @ x2``.
    FLOPs grow p-fold on the cross term but the step is memory-bound at
    small k, so halved traffic wins.  ``sq`` carries per-slot ``|x|²``
    (n/p, p) f32; ``valid`` masks the zero-padded tail slots.
    """

    f = x2.shape[1] // p

    def step(centers):
        with jax.named_scope("ht.kmeans.assign"):
            cT = centers.astype(x2.dtype).T  # (f, k)
            w = jnp.zeros((p * f, p * k), x2.dtype)
            for s in range(p):
                w = jax.lax.dynamic_update_slice(w, cT, (s * f, s * k))
            # (n/p, p*k): slot s's distances live in columns [s*k, (s+1)*k)
            cross = jax.lax.dot_general(
                x2, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            )
            cn2 = jnp.sum(centers.astype(jnp.float32) ** 2, axis=1)
            # all slots at once: (n/p, p, k) distances, slot-major one-hots.
            # |x|^2 shifts every cluster equally, so the argmin only needs
            # m2 = |c|^2 - 2<x,c>; the full d2 (clamped at 0 like ops_cdist —
            # f32 rounding near centroids can dip negative) is built only
            # when the caller wants the per-iteration inertia
            m2 = cn2[None, None, :] - 2.0 * cross.reshape(-1, p, k)
            labels = jnp.argmin(m2, axis=2)  # (n/p, p)
        with jax.named_scope("ht.kmeans.update"):
            vf = valid[..., None].astype(x2.dtype)
            oh = (labels[..., None] == jnp.arange(k)[None, None, :]).astype(x2.dtype) * vf
            counts = jnp.sum(oh, axis=(0, 1), dtype=jnp.float32)
        with jax.named_scope("ht.kmeans.assign"):
            if with_inertia:
                d2min = jnp.maximum(sq + jnp.min(m2, axis=2), 0.0)
                inertia = jnp.sum(d2min * valid)
            else:
                inertia = jnp.array(0.0, jnp.float32)
        with jax.named_scope("ht.kmeans.update"):
            # ONE masked-sum matmul for every slot: a per-slot dot would read
            # x2 p times and hand the traffic win straight back
            all_sums = jax.lax.dot_general(
                oh.reshape(-1, p * k), x2, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # (p*k, p*f); slot s's contribution is its diagonal block
            sums = jnp.zeros((k, f), jnp.float32)
            for s in range(p):
                sums = sums + jax.lax.dynamic_slice(all_sums, (s * k, s * f), (k, f))
            new_centers = jnp.where(
                counts[:, None] > 0,
                sums / jnp.maximum(counts, 1)[:, None],
                centers.astype(jnp.float32),
            ).astype(centers.dtype)
            shift = jnp.sum((new_centers - centers).astype(jnp.float32) ** 2)
        return new_centers, shift, inertia

    return _lloyd_while(step, centers, max_iter, tol)


def _lloyd_loop_packed_blocked_impl(x2, centers, k: int, p: int, n: int, blk: int, max_iter, tol):
    """Packed Lloyd loop with ROW-BLOCKED accumulation, for data near the
    HBM ceiling (the 1e8x64 bf16 north-star: the payload alone is 12.8 GB
    of a 16 GB chip, so whole-array f32 temporaries — cross (rows, p*k),
    d2 (rows, p, k), even the (rows, p) |x|² — cannot exist).  Each Lloyd
    iteration runs a ``fori_loop`` over row blocks carrying only the
    (k, f) sums, (k,) counts and scalar inertia; per-slot |x|² and the
    validity mask are computed per block and never materialize globally.
    One extra read of each block (the |x|² pass fuses into the same
    sweep), temporaries capped at ~blk * p * k floats.

    Compile through :func:`_lloyd_loop_packed_blocked` (AOT with AUTO
    layouts): under jit's default pinned layouts XLA's layout assignment
    relayouts the ENTIRE x2 parameter into a column-major while-state
    copy — an 11.9 GB HLO temp at n=1e8, reproducibly gone when the
    layout solver is free (probed both ways on the v5e; temps drop
    27 GB → 1.6 GB and the chosen x2 layout is the default row-major)."""
    rows, pf = x2.shape
    f = pf // p
    nb = -(-rows // blk)

    def step(centers):
        with jax.named_scope("ht.kmeans.assign"):
            cT = centers.astype(x2.dtype).T
            w = jnp.zeros((p * f, p * k), x2.dtype)
            for s in range(p):
                w = jax.lax.dynamic_update_slice(w, cT, (s * f, s * k))
            cn2 = jnp.sum(centers.astype(jnp.float32) ** 2, axis=1)

        def body(i, carry):
            sums, counts = carry
            with jax.named_scope("ht.kmeans.assign"):
                # dynamic_slice clamps the start: the last block re-reads
                # earlier rows, so mask rows below this block's true start
                start = jnp.minimum(i * blk, rows - blk)
                xb = jax.lax.dynamic_slice_in_dim(x2, start, blk, 0)
                # NO optimization barrier here: with the slimmed body the
                # layout solver keeps the payload's natural orientation and
                # fuses the slice into its consumers (compile-reported temps
                # 0.02 GB); the earlier fuller body needed a barrier to stop
                # a transpose-hoist of the whole payload — re-probe if ops
                # are added back
                gsl = (start * p) + jnp.arange(blk * p)
                vb = ((gsl < n) & (gsl >= i * blk * p)).astype(jnp.float32)
                vb = vb.reshape(blk, p)
                # m2[j] = |c_j|^2 - 2<x, c_j> has the same argmin as d^2: the
                # per-sample |x|^2 shifts every cluster equally, so neither
                # the labels nor the convergence check need it — the profiled
                # per-iteration |x|^2 pass (convert+square+reduce, ~59 ms of
                # a 169 ms iteration at n=1e8) is gone; fit computes the
                # final inertia once in the labels pass
                cross = jax.lax.dot_general(
                    xb, w, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ).reshape(blk, p, k)
                m2 = cn2[None, None, :] - 2.0 * cross
                labels = jnp.argmin(m2, axis=2)
            with jax.named_scope("ht.kmeans.update"):
                oh = (labels[..., None] == jnp.arange(k)[None, None, :]).astype(
                    x2.dtype
                ) * vb[..., None].astype(x2.dtype)
                counts = counts + jnp.sum(
                    oh.astype(jnp.float32), axis=(0, 1), dtype=jnp.float32
                )
                # transpose the BLOCK explicitly: contracting the row dim of
                # the slice directly makes layout assignment want the whole
                # x2 payload transposed — a wish that penetrates optimization
                # barriers and lands as an 11.9 GB relayout copy (verified
                # both ways); a per-block transposed temp satisfies the GEMM
                # locally
                xbT = jnp.swapaxes(xb, 0, 1)
                all_sums = jax.lax.dot_general(
                    oh.reshape(blk, p * k), xbT, (((0,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for s in range(p):
                    sums = sums + jax.lax.dynamic_slice(
                        all_sums, (s * k, s * f), (k, f)
                    )
            return sums, counts

        sums, counts = jax.lax.fori_loop(
            0,
            nb,
            body,
            (
                jnp.zeros((k, f), jnp.float32),
                jnp.zeros((k,), jnp.float32),
            ),
        )
        # the loop reports inertia 0: its true value is only needed once,
        # after convergence — _fit_packed computes it in the labels pass
        inertia = jnp.array(0.0, jnp.float32)
        with jax.named_scope("ht.kmeans.update"):
            new_centers = jnp.where(
                counts[:, None] > 0,
                sums / jnp.maximum(counts, 1)[:, None],
                centers.astype(jnp.float32),
            ).astype(centers.dtype)
            shift = jnp.sum((new_centers - centers).astype(jnp.float32) ** 2)
        return new_centers, shift, inertia

    return _lloyd_while(step, centers, max_iter, tol)


@lru_cache(maxsize=None)
def _blocked_loop_compiled(rows, pf, dtype_str, k, p, n, blk, x2_format):
    """AOT-compile the blocked loop, baking in the payload's ACTUAL
    format (see the impl docstring for why the default pinned layouts
    OOM).  The slim loop body's layout solve prefers the payload's
    natural (generation-time) orientation, so no relayout copy appears;
    any layout the payload does not already have — whether jit's default
    or a free AUTO choice that happens to differ — costs a full-array
    relayout: 12.8 GB and the OOM at the north-star size.  Re-probe
    memory_analysis() both ways whenever the body changes."""
    dt = jnp.dtype(dtype_str)
    x2_s = jax.ShapeDtypeStruct((rows, pf), dt)
    c_s = jax.ShapeDtypeStruct((k, pf // p), dt)
    mi_s = jax.ShapeDtypeStruct((), jnp.int32)
    tol_s = jax.ShapeDtypeStruct((), jnp.float32)

    def ht_lloyd_loop_blocked(x2, centers, max_iter, tol):
        return _lloyd_loop_packed_blocked_impl(
            x2, centers, k, p, n, blk, max_iter, tol
        )

    jitted = jax.jit(
        ht_lloyd_loop_blocked,
        in_shardings=(
            x2_format,
            _AUTO_FMT,
            _AUTO_FMT,
            _AUTO_FMT,
        ),
    )
    return jitted.lower(x2_s, c_s, mi_s, tol_s).compile()


def _lloyd_loop_packed_blocked(x2, centers, k, p, n, blk, max_iter, tol):
    """Run the blocked Lloyd loop through its AUTO-layout AOT executable;
    small inputs are device_put into the compiled formats (x2 is passed
    as-is: the executable is compiled for its exact sharding, and the
    probed AUTO layout choice for it is the default row-major)."""
    comp = _blocked_loop_compiled(
        x2.shape[0], x2.shape[1], str(x2.dtype), int(k), int(p), int(n),
        int(blk), x2.format,
    )
    fmts = comp.input_formats[0]
    small = [
        jnp.asarray(centers),
        jnp.asarray(max_iter, jnp.int32),
        jnp.asarray(tol, jnp.float32),
    ]
    args = [x2] + [jax.device_put(a, f) for a, f in zip(small, fmts[1:])]
    return comp(*args)


@partial(jax.jit, static_argnames=("p",))
def _pack_relayout(arr, p: int):
    """Pad + pack into (n/p, p*f).  Jitted so intermediates fuse (eagerly
    each op materializes and OOMs the exact large-n case packing exists
    for).  Kept separate from the |x|² reduce below: one program emitting
    both the relayout copy and the row reduce sends the TPU compiler into
    a multi-minute layout-assignment spiral (observed hang at n=1e7)."""
    n, f = arr.shape
    n2 = -(-n // p) * p
    if n2 != n:
        arr = jnp.pad(arr, ((0, n2 - n), (0, 0)))
    return arr.reshape(n2 // p, p * f)


@partial(jax.jit, static_argnames=("p",))
def _pack_rownorms(arr, p: int):
    """Per-slot |x|² (n/p, p) f32 and the validity mask, from the unpacked
    array (the convert+square fuses into the reduce — no f32 copy)."""
    n = arr.shape[0]
    n2 = -(-n // p) * p
    sq = jnp.sum(arr.astype(jnp.float32) ** 2, axis=1)
    if n2 != n:
        sq = jnp.pad(sq, (0, n2 - n))
    valid = (jnp.arange(n2).reshape(n2 // p, p) < n).astype(jnp.float32)
    return sq.reshape(n2 // p, p), valid


def _pack_kernel(arr, p: int):
    x2 = _pack_relayout(arr, p)
    sq, valid = _pack_rownorms(arr, p)
    return x2, sq, valid


def _pack_lanes(arr):
    """Pack ``p = 128//f`` samples per 128-lane row when profitable:
    returns ``(x2, sq, valid, f, p)`` or None when not applicable."""
    n, f = arr.shape
    if arr.dtype != jnp.bfloat16 or f >= 128 or 128 % f != 0:
        return None
    # the conversion holds the lane-padded source (2x logical bytes for
    # f=64) AND the packed copy; without headroom for both, fall back to
    # the unpacked loop rather than OOM — packing at ingest (loader level)
    # is the path for arrays near the HBM ceiling
    dev = next(iter(arr.devices()))
    # the array is sharded over the mesh: memory budgets are per device;
    # the unified reader reports the TIGHTEST device (None where the
    # backend has no stats)
    n_dev = max(1, len(arr.devices()))
    need = arr.size * 2 // n_dev
    # THE budget formula (memtrack.suggest_budget, shared with transport's
    # informed retry and autotune's plan-time seeding): the packed copy
    # must fit free HBM minus a 1 GiB working-set reservation
    granted = memtrack.suggest_budget(need, fraction=1.0, headroom=1 << 30)
    if granted is not None:
        if granted < need:
            return None
    elif dev.platform == "tpu":
        # no stats: estimate — lane-padded source (n*128*2B) + packed copy
        # + loop temporaries must stay well under a 16 GB chip
        n_ = arr.shape[0]
        if n_ * (256 + 2 * arr.shape[1]) * 1.3 / n_dev > 12e9:
            return None
    p = 128 // f
    x2, sq, valid = _pack_kernel(arr, p)
    return x2, sq, valid, f, p


class KMeans(_KCluster):
    """K-Means with Lloyd's algorithm (reference: kmeans.py:13).

    Parameters mirror the reference: ``n_clusters``, ``init`` ("random",
    "kmeans++"/"probability_based", or explicit centroids), ``max_iter``,
    ``tol`` (convergence on squared centroid shift), ``random_state``.
    """

    def __init__(
        self,
        n_clusters: int = 8,
        init: Union[str, DNDarray] = "random",
        max_iter: int = 300,
        tol: float = 1e-4,
        random_state: Optional[int] = None,
    ):
        if isinstance(init, str) and init == "kmeans++":
            init = "probability_based"
        super().__init__(
            metric=lambda x, y: distance.cdist(x, y, quadratic_expansion=True),
            n_clusters=n_clusters,
            init=init,
            max_iter=max_iter,
            tol=tol,
            random_state=random_state,
        )

    def _update_centroids(self, x: DNDarray, matching_centroids: DNDarray) -> DNDarray:
        """Masked-mean centroid update (reference: kmeans.py:73). Exposed for
        API parity; ``fit`` uses the fused step."""
        labels = matching_centroids.larray.reshape(-1)
        arr = x.larray
        onehot = (labels[:, None] == jnp.arange(self.n_clusters)[None, :]).astype(arr.dtype)
        counts = jnp.sum(onehot, axis=0)
        sums = None
        if x.split == 0 and x.comm.size > 1:
            # inner-split GEMM: the sample axis is the contraction — the ring
            # reduce-scatter schedule lands the (k, f) sums replicated without
            # the all-gather-then-dot GSPMD would emit (decline-safe)
            from ..parallel import overlap
            k_ = self.n_clusters
            sums = overlap.matmul_raw(
                x.comm, onehot.T, arr,
                (k_, x.shape[0]), (x.shape[0], x.shape[1]), 1, 0, None,
            )
        if sums is None:
            sums = jnp.matmul(onehot.T, arr)
        old = self._cluster_centers.larray
        new = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1)[:, None], old)
        return DNDarray(
            new, tuple(new.shape), types.canonical_heat_type(new.dtype),
            None, x.device, x.comm,
        )

    def fit(self, x) -> "KMeans":
        """Lloyd iterations until centroid shift < tol (reference:
        kmeans.py:102-139).  Also accepts :class:`packing.PackedSamples`
        (lane-packed ingest — the 1e8x64 bf16 north-star path)."""
        with telemetry.span("kmeans.fit") as sp:
            return self._fit(x, sp)

    def _fit(self, x, sp) -> "KMeans":
        """:meth:`fit` inside its span ``sp``, which notes the Lloyd body
        that ran on unpacked rows (``lloyd`` = ``fused`` | ``classic``)."""
        from ..core import sanitation
        from .packing import PackedSamples

        if isinstance(x, PackedSamples):
            return self._fit_packed(x)
        sanitation.sanitize_in(x)
        if x.ndim != 2:
            raise ValueError(f"input needs to be 2-D, but was {x.ndim}-D")
        with telemetry.span("kmeans.init"):
            self._initialize_cluster_centers(x)

        arr = x.larray
        if not jnp.issubdtype(arr.dtype, jnp.floating):
            arr = arr.astype(jnp.float32)
        centers = self._cluster_centers.larray.astype(arr.dtype)

        packed = _pack_lanes(arr)
        if packed is not None:
            x2, sq, valid, f, p = packed
            centers, _, inertia, n_iter = _lloyd_loop_packed(
                x2, sq, valid, centers, self.n_clusters, p,
                self.max_iter, self.tol,
            )
        else:
            fused = _fused_rows(x, self.n_clusters)
            sp.note(lloyd="classic" if fused is None else "fused")
            rows, how = (arr, {}) if fused is None else (x.parray, {"fused": fused})
            centers, _, inertia, n_iter = _lloyd_loop(
                rows, centers, self.n_clusters, self.max_iter, self.tol, **how
            )
        self._cluster_centers = DNDarray(
            centers, tuple(centers.shape), types.canonical_heat_type(centers.dtype),
            None, x.device, x.comm,
        )
        # dispatched on the loop's centres before the host waits for the
        # loop: the device goes from one program to the next, one wait for both
        self._labels = self._assign_to_cluster(x)
        with telemetry.sync("kmeans.n_iter"):  # one scalar per fit
            self._n_iter = int(n_iter)
        with telemetry.sync("kmeans.inertia"):  # one scalar per fit
            self._inertia = float(inertia)
        return self

    def _labels_by_kernel(self, x: DNDarray) -> Optional[DNDarray]:
        """The squared Euclidean distance is the fused pass's metric: where
        :func:`_fused_rows` accepts ``x`` the labels are one more run of it."""
        fused = _fused_rows(x, self.n_clusters)
        if fused is None:
            return None
        labels = _labels_of_rows(x.parray, self._cluster_centers.larray, fused)
        return DNDarray(
            labels, (x.shape[0], 1), types.canonical_heat_type(labels.dtype),
            x.split, x.device, x.comm,
        )

    # ------------------------------------------------------ packed-ingest path
    def _init_centers_packed(self, packed) -> jax.Array:
        """Initial centroids from lane-packed data (see packing.py).

        "random" mirrors the stratified draw of
        ``_KCluster._initialize_cluster_centers``; "kmeans++" seeds on a
        bounded sample prefix (2^18 samples) — at north-star scale an
        exact kmeans++ scan would read the full array k times for a
        seeding whose quality a large subsample matches statistically."""
        from ..core import random as ht_random

        if self.random_state is not None:
            ht_random.seed(self.random_state)
        k = self.n_clusters
        n, f, p = packed.n, packed.f, packed.p
        if n < k:
            raise ValueError(
                f"n_samples={n} should be >= n_clusters={k}"
            )
        x2 = packed.x2.parray

        if isinstance(self.init, DNDarray):
            if self.init.shape != (k, f):
                raise ValueError("passed centroids do not match cluster count or data shape")
            return self.init.resplit(None).larray
        us = ht_random.rand(k, comm=packed.comm).larray.astype(jnp.float32)
        if isinstance(self.init, str) and self.init == "random":
            lo = jnp.arange(k) * (n // k)
            width = jnp.maximum(jnp.asarray(n // k), 1)
            idx = jnp.minimum(lo + (us * width).astype(jnp.int32), n - 1)
            return _gather_packed_samples(x2, idx, p, f, packed.comm)
        if isinstance(self.init, str) and self.init in ("probability_based", "kmeans++", "kmedians++"):
            from ._kcluster import _kmeanspp_init

            m_rows = min(x2.shape[0], (1 << 18) // p)
            sub = x2[:m_rows].reshape(-1, f)[: min(n, m_rows * p)]
            return _kmeanspp_init(sub, us, k)
        raise ValueError(f"unsupported init for packed data: {self.init!r}")

    def _fit_packed(self, packed) -> "KMeans":
        # the PHYSICAL payload: even row chunks over the mesh (trailing
        # pad rows' slots are >= n, so the validity masks drop them)
        x2 = packed.x2.parray
        with telemetry.span("kmeans.init"):
            centers = self._init_centers_packed(packed).astype(x2.dtype)
        if _use_blocked(x2):
            blk = min(x2.shape[0], _BLOCK_ROWS)
            centers, _, inertia, n_iter = _lloyd_loop_packed_blocked(
                x2, centers, self.n_clusters, packed.p, packed.n, blk,
                self.max_iter, self.tol,
            )
        else:
            # validity mask only — the per-slot |x|^2 pass would be dead
            # work here (with_inertia=False; inertia comes from the final
            # labels pass)
            rows = x2.shape[0]
            valid = (
                jnp.arange(rows * packed.p).reshape(rows, packed.p)
                < packed.n
            ).astype(jnp.float32)
            centers, _, inertia, n_iter = _lloyd_loop_packed(
                x2, jnp.zeros((1, 1), jnp.float32), valid, centers,
                self.n_clusters, packed.p, self.max_iter, self.tol,
                with_inertia=False,
            )
        with telemetry.sync("kmeans.n_iter"):  # one scalar per fit
            self._n_iter = int(n_iter)
        self._cluster_centers = DNDarray(
            centers, tuple(centers.shape),
            types.canonical_heat_type(centers.dtype), None, packed.device,
            packed.comm,
        )
        # BOTH packed branches take inertia from the final labels pass —
        # distance to the FINAL centers (sklearn's inertia_ definition),
        # identical on either side of the blocked-path size threshold.
        # (The dense path keeps the reference's definition: the last
        # iteration's assignment distances, pre-update centers.)
        del inertia
        with telemetry.span("kmeans.labels"):
            self._labels, inertia = self._predict_packed(packed, with_inertia=True)
        with telemetry.sync("kmeans.inertia"):  # one scalar per fit
            self._inertia = float(inertia)
        return self

    def _predict_packed(self, packed, with_inertia: bool = False):
        """Labels (and optionally inertia) from packed data.  The blocked
        single-chip path engages only under the same _use_blocked guard
        as the fit loop; mesh-sharded payloads keep the GSPMD-friendly
        whole-array matmul."""
        x2 = packed.x2.parray
        if _use_blocked(x2):
            # half-size blocks when the inertia sweep rides along: it
            # adds per-block |x|^2 temps, and full _BLOCK_ROWS puts the
            # compile-reported peak within ~300 MB of the ceiling
            blk = _BLOCK_ROWS // 2 if with_inertia else _BLOCK_ROWS
            labels, inertia = _packed_labels_blocked(
                x2, self._cluster_centers.larray, packed.p, packed.n,
                min(x2.shape[0], blk), with_inertia=with_inertia,
            )
        else:
            labels, inertia = _packed_labels(
                x2, self._cluster_centers.larray, packed.p, packed.n,
                with_inertia=with_inertia,
            )
        out = DNDarray(
            labels, tuple(labels.shape),
            types.canonical_heat_type(labels.dtype), packed.split,
            packed.device, packed.comm,
        )
        return (out, inertia) if with_inertia else out

    def predict(self, x) -> DNDarray:
        from .packing import PackedSamples

        if isinstance(x, PackedSamples):
            return self._predict_packed(x)
        return super().predict(x)

    # ------------------------------------------------------ streaming path
    def _init_centers_stream(self, src, comm) -> jax.Array:
        """Initial centroids off a chunk source (bounded host reads only).

        Mirrors :meth:`_init_centers_packed`'s strategies: explicit
        centroids pass through; "random" is the stratified per-cluster
        draw, each chosen row host-read individually; "kmeans++" seeds on
        a bounded sample prefix (2^18 rows) — an exact scan would stream
        the whole array k times for a seeding a large subsample matches
        statistically."""
        import numpy as np

        from ..core import random as ht_random

        k = self.n_clusters
        n, f = src.shape
        if n < k:
            raise ValueError(f"n_samples={n} should be >= n_clusters={k}")
        if isinstance(self.init, DNDarray):
            if self.init.shape != (k, f):
                raise ValueError(
                    "passed centroids do not match cluster count or data shape"
                )
            return self.init.resplit(None).larray.astype(jnp.float32)
        if self.random_state is not None:
            ht_random.seed(self.random_state)
        us = ht_random.rand(k, comm=comm).larray.astype(jnp.float32)
        if isinstance(self.init, str) and self.init == "random":
            width = max(n // k, 1)
            lo = np.arange(k) * (n // k)
            with telemetry.sync("kmeans.stream_init"):  # k uniforms, once
                off = (np.asarray(us) * width).astype(np.int64)
            idx = np.minimum(lo + off, n - 1)
            rows = np.concatenate([src.read(int(i), int(i) + 1) for i in idx])
            return jnp.asarray(rows, jnp.float32)
        if isinstance(self.init, str) and self.init in (
            "probability_based", "kmeans++", "kmedians++",
        ):
            from ._kcluster import _kmeanspp_init

            sub = jnp.asarray(src.read(0, min(n, 1 << 18)), jnp.float32)
            return _kmeanspp_init(sub, us, k)
        raise ValueError(f"unsupported init for streamed data: {self.init!r}")

    @telemetry.span("kmeans.fit_stream")
    def fit_stream(self, source, dataset: Optional[str] = None, *,
                   comm=None, budget: Optional[int] = None) -> "KMeans":
        """Exact multi-pass Lloyd over data that does not fit in HBM.

        Each Lloyd iteration is ONE streaming pass (core/stream.py):
        slabs arrive double-buffered under the residency budget, the
        jitted :func:`_stream_lloyd_stats` folds each into running
        (counts, sums, inertia) — compiled once per pass, the slab shape
        is fixed — and the center update + one scalar shift readback
        happen between passes.  The result is the same Lloyd fixed point
        as :meth:`fit` on the in-memory array (f32 accumulation; only
        the slab-wise summation order differs, so centroids agree to
        accumulation roundoff).  ``self.labels_`` stays ``None`` — a
        labels pass over out-of-core data is a separate full read the
        caller can run via chunked ``predict`` when actually wanted.

        ``source`` is anything :func:`heat_tpu.core.stream.open_source`
        accepts (HDF5/NetCDF path + ``dataset``, ``.npy``, ndarray, open
        ``ChunkSource``); ``budget`` overrides the measured residency
        budget in bytes."""
        import numpy as np

        from ..core import stream

        from ..parallel.mesh import sanitize_comm

        comm = sanitize_comm(comm)
        src = stream.open_source(source, dataset=dataset,
                                 np_dtype=np.float32)
        own = src is not source  # passthrough ChunkSource stays caller-owned
        try:
            if len(src.shape) != 2:
                raise ValueError(
                    f"input needs to be 2-D, but was {len(src.shape)}-D"
                )
            n, f = src.shape
            k = self.n_clusters
            centers = self._init_centers_stream(src, comm)
            inertia = 0.0
            self._n_iter = 0
            self.last_stream_report = None
            for _ in range(self.max_iter):
                pl = stream.plan_pass(src, comm=comm, site="kmeans_fit",
                                      budget=budget)
                sp = stream.StreamPass(src, comm=comm, plan=pl)
                counts = jnp.zeros((k,), jnp.float32)
                sums = jnp.zeros((k, f), jnp.float32)
                pass_inertia = jnp.zeros((), jnp.float32)
                for slab in sp:
                    c, s, i = _stream_lloyd_stats(
                        slab.x.larray, slab.valid, centers, k
                    )
                    counts = counts + c
                    sums = sums + s
                    pass_inertia = pass_inertia + i
                    del slab  # drop the loop reference: 3-slab residency cap
                rep = stream.finish_pass(sp)
                self.last_stream_report = dict(rep, arm=pl.arm,
                                               budget=pl.budget)
                fp = telemetry.fingerprint(
                    ("stream_kmeans", pl.slab_rows, f, k, comm.size)
                )
                telemetry.ensure_program(
                    fp, kind="stream_kmeans", dtype="float32",
                    flops=4.0 * n * f * k, hbm_bytes=float(n) * f * 4,
                )
                telemetry.record_timing(fp, rep["wall_s"])
                telemetry.annotate_program(
                    fp,
                    io_stall_frac=round(1.0 - rep["overlap_frac"], 4),
                    io_bytes=rep["bytes_read"],
                )
                new_centers = jnp.where(
                    counts[:, None] > 0,
                    sums / jnp.maximum(counts, 1)[:, None],
                    centers.astype(jnp.float32),
                ).astype(centers.dtype)
                shift2 = jnp.sum((new_centers - centers).astype(jnp.float32) ** 2)
                # one convergence scalar per full-data pass; the last pass's
                # inertia rides the same wait
                with telemetry.sync("kmeans.stream_shift"):
                    shift = float(shift2)
                    inertia = float(pass_inertia)
                centers = new_centers
                self._n_iter += 1
                if shift <= self.tol:
                    break
        finally:
            if own:
                src.close()
        from ..core.devices import sanitize_device

        self._cluster_centers = DNDarray(
            centers, tuple(centers.shape),
            types.canonical_heat_type(centers.dtype), None,
            sanitize_device(None), comm,
        )
        # dense-path definition: last iteration's assignment distances
        # against pre-update centers (see fit); labels stay out-of-core
        self._inertia = inertia
        self._labels = None
        return self


# row-block size for the near-HBM-ceiling paths: temporaries per block
# stay in the hundreds of MB (2^23 rows already OOMs the compile at the
# north-star size); and the threshold above which whole-array f32
# temporaries (cross/d2 at rows*p*k floats) stop fitting next to the
# payload on a 16 GB chip
_BLOCK_ROWS = 1 << 21
_BLOCKED_BYTES = 4 << 30


def _use_blocked(x2) -> bool:
    """Blocked accumulation is the SINGLE-CHIP near-HBM-ceiling path; on a
    mesh, GSPMD already divides the whole-array loop's temporaries per
    device."""
    try:
        single = len(x2.devices()) == 1
    except Exception:
        single = True
    return single and x2.size * x2.dtype.itemsize > _BLOCKED_BYTES


def _packed_labels_blocked_impl(x2, centers, p: int, n: int, blk: int, with_inertia: bool = True):
    """Blocked nearest-centroid labels AND the total inertia (see
    _lloyd_loop_packed_blocked — the whole-array cross term cannot exist
    next to the payload; and inertia is only needed once, after
    convergence, so the per-sample |x|^2 lives here rather than in every
    Lloyd iteration).

    The label buffer is FLAT (rows*p,): a (rows, p) int32 array lane-pads
    p -> 128 under the TPU's T(8,128) tiling — 64x, a 25.6 GB buffer for
    400 MB of labels at the north-star size."""
    rows, pf = x2.shape
    f = pf // p
    k = centers.shape[0]
    nb = -(-rows // blk)
    cT = centers.astype(x2.dtype).T
    w = jnp.zeros((p * f, p * k), x2.dtype)
    for s in range(p):
        w = jax.lax.dynamic_update_slice(w, cT, (s * f, s * k))
    cn2 = jnp.sum(centers.astype(jnp.float32) ** 2, axis=1)

    def body(i, carry):
        out, inertia = carry
        start = jnp.minimum(i * blk, rows - blk)
        xb = jax.lax.dynamic_slice_in_dim(x2, start, blk, 0)
        gsl = (start * p) + jnp.arange(blk * p)
        vbf = ((gsl < n) & (gsl >= i * blk * p)).astype(jnp.float32)
        cross = jax.lax.dot_general(
            xb, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ).reshape(blk, p, k)
        m2 = cn2[None, None, :] - 2.0 * cross
        lb = jnp.argmin(m2, axis=2).astype(jnp.int32)
        if with_inertia:
            sqb = jnp.sum(
                xb.reshape(blk * p, f).astype(jnp.float32) ** 2, axis=1
            )
            # d2 = |x|^2 + min m2, clamped at 0 per sample (f32 rounding
            # near centroids can dip negative)
            d2min = jnp.maximum(sqb + jnp.min(m2, axis=2).reshape(-1), 0.0)
            inertia = inertia + jnp.sum(d2min * vbf)
        # overlap from the clamped tail start rewrites identical values
        out = jax.lax.dynamic_update_slice(out, lb.reshape(-1), (start * p,))
        return out, inertia

    labels, inertia = jax.lax.fori_loop(
        0, nb, body,
        (jnp.zeros((rows * p,), jnp.int32), jnp.array(0.0, jnp.float32)),
    )
    return labels[:n], inertia


@lru_cache(maxsize=None)
def _labels_blocked_compiled(rows, pf, dtype_str, k, p, n, blk, x2_format, with_inertia):
    """AOT labels pass baking in the payload's actual format (same
    relayout-copy avoidance as :func:`_blocked_loop_compiled`).  The
    inertia sweep (an extra per-block |x|^2 pass) compiles in only when
    asked — predict wants labels alone."""
    dt = jnp.dtype(dtype_str)

    def fn(x2, centers):
        return _packed_labels_blocked_impl(
            x2, centers, p, n, blk, with_inertia
        )

    jitted = jax.jit(fn, in_shardings=(x2_format, _AUTO_FMT))
    return jitted.lower(
        jax.ShapeDtypeStruct((rows, pf), dt),
        jax.ShapeDtypeStruct((k, pf // p), dt),
    ).compile()


def _packed_labels_blocked(x2, centers, p, n, blk, with_inertia=True):
    """Returns ``(labels (n,), inertia scalar)`` — inertia is 0 when
    ``with_inertia`` is off (labels-only predict path)."""
    comp = _labels_blocked_compiled(
        x2.shape[0], x2.shape[1], str(x2.dtype), int(centers.shape[0]),
        int(p), int(n), int(blk), x2.format, bool(with_inertia),
    )
    fmts = comp.input_formats[0]
    centers = jax.device_put(jnp.asarray(centers, x2.dtype), fmts[1])
    return comp(x2, centers)


@lru_cache(maxsize=None)
def _gather_rows_compiled(rows_phys, pf, dtype_str, kcount, blk, x2_format):
    """AOT blocked row gather over the packed payload.

    A direct ``jnp.take`` on the big payload relayouts/reshards the WHOLE
    operand (observed both as an sdy reshard copy and as a gather-layout
    copy — 11.9 GB either way at the north-star size).  The blocked
    pattern sidesteps every preference: ``fori`` over dynamic-sliced row
    blocks, a small per-block take, masked accumulate — the same
    structure as the blocked Lloyd loop, compiled with the payload's
    actual format baked in."""
    dt = jnp.dtype(dtype_str)
    nb = -(-rows_phys // blk)

    def fn(x2, ridx):
        def body(i, acc):
            start = jnp.minimum(i * blk, rows_phys - blk)
            xb = jax.lax.dynamic_slice_in_dim(x2, start, blk, 0)
            lpos = ridx - start
            # the clamped tail block re-reads earlier rows: only own rows
            # at/after this block's true start count
            owned = (lpos >= 0) & (lpos < blk) & (ridx >= i * blk)
            take = jnp.clip(lpos, 0, blk - 1)
            got = jnp.take(xb, take, axis=0) * owned[:, None].astype(dt)
            return acc + got

        return jax.lax.fori_loop(
            0, nb, body, jnp.zeros((kcount, pf), dt)
        )

    jitted = jax.jit(fn, in_shardings=(x2_format, _AUTO_FMT))
    return jitted.lower(
        jax.ShapeDtypeStruct((rows_phys, pf), dt),
        jax.ShapeDtypeStruct((kcount,), jnp.int32),
    ).compile()


def _gather_packed_samples(x2, idx, p: int, f: int, comm):
    """Samples by global id from the packed layout: sample i is lanes
    [(i%p)*f, (i%p+1)*f) of row i//p (see :func:`_gather_rows_compiled`)."""
    blk = min(x2.shape[0], _BLOCK_ROWS)
    comp = _gather_rows_compiled(
        x2.shape[0], x2.shape[1], str(x2.dtype), int(idx.shape[0]), blk,
        x2.format,
    )
    fmts = comp.input_formats[0]
    ridx = jax.device_put((idx // p).astype(jnp.int32), fmts[1])
    rows = comp(x2, ridx).reshape(idx.shape[0], p, f)
    return jnp.take_along_axis(
        rows, (idx % p)[:, None, None].astype(jnp.int32), axis=1
    )[:, 0, :]


@partial(jax.jit, static_argnames=("p", "n"))
def _packed_stats(x2, p: int, n: int):
    """Per-slot |x|² (rows, p) f32 and validity mask, computed FROM the
    packed layout (the ingest path: the lane-padded source never exists)."""
    rows, pf = x2.shape
    f = pf // p
    x3 = x2.reshape(rows, p, f)
    sq = jnp.sum(x3.astype(jnp.float32) ** 2, axis=2)
    valid = (jnp.arange(rows * p).reshape(rows, p) < n).astype(jnp.float32)
    return sq, valid


@partial(jax.jit, static_argnames=("p", "n", "with_inertia"))
def _packed_labels(x2, centers, p: int, n: int, with_inertia: bool = False):
    """Nearest-centroid labels (flat (n,)) from packed data — one
    block-diagonal cross matmul, GSPMD-friendly for mesh-sharded
    payloads — plus the total inertia when asked (distance to these
    centers, sklearn's inertia_ definition)."""
    rows, pf = x2.shape
    f = pf // p
    k = centers.shape[0]
    cT = centers.astype(x2.dtype).T
    w = jnp.zeros((p * f, p * k), x2.dtype)
    for s in range(p):
        w = jax.lax.dynamic_update_slice(w, cT, (s * f, s * k))
    cross = jax.lax.dot_general(
        x2, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).reshape(rows, p, k)
    cn2 = jnp.sum(centers.astype(jnp.float32) ** 2, axis=1)
    m2 = cn2[None, None, :] - 2.0 * cross
    labels = jnp.argmin(m2, axis=2)
    if with_inertia:
        f = pf // p
        sq = jnp.sum(
            x2.reshape(rows * p, f).astype(jnp.float32) ** 2, axis=1
        )
        valid = (jnp.arange(rows * p) < n).astype(jnp.float32)
        d2min = jnp.maximum(sq + jnp.min(m2, axis=2).reshape(-1), 0.0)
        inertia = jnp.sum(d2min * valid)
    else:
        inertia = jnp.array(0.0, jnp.float32)
    # flat (n,) labels: a trailing length-1/length-p dim lane-pads to 128
    # under TPU tiling (see _packed_labels_blocked_impl)
    return labels.reshape(-1)[:n].astype(jnp.int32), inertia
