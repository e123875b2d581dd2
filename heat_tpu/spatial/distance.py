"""Pairwise distance matrices (reference: heat/spatial/distance.py, 494 LoC).

The reference hand-writes a **ring algorithm** (`_dist`, distance.py:209):
each rank keeps a stationary block, passes a moving block around the ring for
(size+1)//2 rounds, exploiting symmetry.  On TPU the same dataflow emerges
from GSPMD: with ``x`` row-split and ``y`` replicated (the KMeans case) the
computation is purely local; with both split, XLA schedules the all-gather of
the smaller operand over ICI.  The quadratic-expansion fast path
(``_quadratic_expand``, distance.py:~90) becomes the *default* here because it
routes the O(n·m·f) work through the MXU as a matmul instead of the VPU.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from ..core import sanitation, types
from ..core.dndarray import DNDarray, _ensure_split

__all__ = ["cdist", "cdist_quantized", "rbf", "manhattan"]


def _check(x: DNDarray, y: Optional[DNDarray]):
    """Validate operands and compute the promoted dtype from metadata only —
    no ``.larray`` read, so a lazy operand stays lazy on the fused path."""
    sanitation.sanitize_in(x)
    if y is None:
        y = x
    sanitation.sanitize_in(y)
    if x.ndim != 2 or y.ndim != 2:
        raise ValueError("cdist requires 2-D inputs")
    if x.shape[1] != y.shape[1]:
        raise ValueError(f"feature dimensions differ: {x.shape[1]} vs {y.shape[1]}")
    promoted = jnp.promote_types(x.dtype.jax_type(), y.dtype.jax_type())
    if not jnp.issubdtype(promoted, jnp.floating):
        promoted = jnp.float32
    return x, y, promoted


def _prep(x: DNDarray, y: Optional[DNDarray]):
    x, y, promoted = _check(x, y)
    xa, ya = x.larray, y.larray
    return x, y, xa.astype(promoted), ya.astype(promoted)


def _result_split(x: DNDarray, y: DNDarray) -> Optional[int]:
    # rows follow x's distribution; columns follow y's (reference: the result
    # inherits the stationary block's split)
    if x.split == 0:
        return 0
    if y.split == 0:
        return 1
    return None


@jax.jit
def _sq_euclidean(xa, ya):
    """Quadratic expansion ||a-b||² = |a|² + |b|² − 2a·b — MXU-resident,
    one compiled program (eager dispatch would run the casts/squares as
    separate XLA programs and materialize array-sized temporaries).

    Half-precision inputs accumulate in f32 (fused casts in the norm
    reductions, ``preferred_element_type`` on the cross term — never an
    array-sized f32 copy) so labels computed here agree with the
    f32-accumulated fused KMeans loop; f32/f64 inputs keep their native
    precision and dtype.  ``_prep`` has already unified the dtypes."""
    half = jnp.dtype(xa.dtype).itemsize < 4
    if not half:
        x2 = jnp.sum(xa * xa, axis=1)[:, None]
        y2 = jnp.sum(ya * ya, axis=1)[None, :]
        cross = jnp.matmul(xa, ya.T)
    else:
        x2 = jnp.sum(jnp.square(xa.astype(jnp.float32)), axis=1)[:, None]
        y2 = jnp.sum(jnp.square(ya.astype(jnp.float32)), axis=1)[None, :]
        cross = jax.lax.dot_general(
            xa, ya, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
    d2 = x2 + y2 - 2.0 * cross
    # noise floor: for a ≈ b the expansion cancels catastrophically and the
    # residual is rounding noise of magnitude ~eps·(|a|²+|b|²) — clamp it to
    # an exact 0 so self-distances come out 0, not sqrt(eps)·|a|
    eps = jnp.finfo(d2.dtype).eps
    d2 = jnp.where(d2 <= 4.0 * eps * (x2 + y2), 0.0, d2)
    return jnp.maximum(d2, 0.0)


@partial(jax.jit, static_argnames=("k",))
def _stream_topk_merge(q, slab, valid, base, best_d, best_i, k: int):
    """Running k-nearest merge against one streamed corpus slab.

    Squared distances from the queries to the slab (pad rows ``>= valid``
    masked to +inf), global corpus ids from the traced ``base`` offset,
    merged with the carried best-k via one ``lax.top_k`` over the
    concatenation.  Distances stay SQUARED — monotone in the sqrt'd
    metric, so the merged neighbor set (and any vote on it) matches the
    in-memory ``cdist`` + ``top_k`` predict.  Tie behavior matches too:
    ``top_k`` is stable, the carry (earlier global ids, themselves
    ascending) precedes the slab's ascending ids in the concatenation, so
    equal distances resolve to the smaller corpus index either way.
    ``valid``/``base`` arrive as Python ints and trace as weak scalars —
    every slab of a pass hits the same executable (no-retrace law)."""
    rows = slab.shape[0]
    d2 = _sq_euclidean(q, slab.astype(q.dtype))
    d2 = jnp.where(
        (jnp.arange(rows) < valid)[None, :], d2.astype(jnp.float32), jnp.inf
    )
    ids = jnp.broadcast_to(
        (base + jnp.arange(rows, dtype=jnp.int32))[None, :],
        (q.shape[0], rows),
    )
    cat_d = jnp.concatenate([best_d, d2], axis=1)
    cat_i = jnp.concatenate([best_i, ids], axis=1)
    neg, pos = jax.lax.top_k(-cat_d, k)
    return -neg, jnp.take_along_axis(cat_i, pos, axis=1)


@jax.named_scope("ht.cdist")
def _euclid_kernel(xv, yv, dtype=None, sqrt=True):
    """Composite cdist kernel for the fusion engine: dtype promotion, the
    quadratic expansion, and the optional sqrt all inside one traced body so
    a consumer (k-means' argmin) extends the same executable."""
    xv = xv.astype(dtype)
    yv = yv.astype(dtype)
    d2 = _sq_euclidean(xv, yv)
    return jnp.sqrt(d2) if sqrt else d2


def _lazy_cdist(x: DNDarray, y: DNDarray, promoted, split, sqrt: bool):
    """Defer the GSPMD cdist fallback as a fusion-DAG node. Returns None
    (caller falls through to eager) when the operands decline fusion."""
    from ..core import _operations, fusion

    try:
        nx = _operations._lazy_operand(x, x.comm)
        ny = _operations._lazy_operand(y, x.comm)
        res = fusion.node(_euclid_kernel, (nx, ny), dtype=jnp.dtype(promoted), sqrt=sqrt)
    except fusion.Unfusable:
        fusion.count_fallback()
        return None
    return fusion.defer(
        res,
        res.aval.shape,
        types.canonical_heat_type(res.aval.dtype),
        split,
        x.device,
        x.comm,
    )


def _pallas_eligible(x: DNDarray, y: DNDarray, promoted) -> bool:
    from ..ops._pallas_common import mode as _mode

    # only when the promoted dtype is f32: the kernel accumulates and returns
    # f32, and the GSPMD path must stay the dtype-authoritative fallback
    return (
        _mode() != "off"
        and x.split == 0
        and y.split is None
        and jnp.dtype(promoted) == jnp.float32
    )


def _ring_eligible(x: DNDarray, y: DNDarray) -> bool:
    n_dev = x.comm.size
    return (
        x.split == 0
        and y.split == 0
        and n_dev > 1
        and x.shape[0] % n_dev == 0
        and y.shape[0] % n_dev == 0
    )


def _build_rowsplit(mesh, spec, sqrt: bool):
    from ..ops.cdist import cdist as _fused
    from ..parallel.collectives import shard_map_unchecked
    from jax.sharding import PartitionSpec as P

    def ht_cdist_rowsplit(xs, ys):  # names the XLA module (telemetry.module_name)
        return _fused(xs, ys, sqrt=sqrt)

    return shard_map_unchecked(
        ht_cdist_rowsplit,
        mesh,
        in_specs=(spec, P()),
        out_specs=spec,
    )


def _pallas_rowsplit_cdist(x: DNDarray, y: DNDarray, ya, sqrt: bool) -> Optional[DNDarray]:
    """Fused-kernel fast path for the KMeans shape: x row-split, y replicated.

    Runs ops.cdist (Pallas, norms fused into the MXU matmul) on each shard
    under shard_map — the TPU analog of the reference's stationary block with
    a replicated small operand (distance.py:209, size-1 ring degenerate case).
    Returns None when the layout doesn't fit, to fall through to GSPMD.
    """
    if not _pallas_eligible(x, y, ya.dtype):
        return None
    from ..parallel.collectives import jit_shard_map_cached

    comm = x.comm
    out = jit_shard_map_cached(_build_rowsplit, comm.mesh, comm.spec(0, 2), sqrt)(
        x.parray.astype(jnp.float32), ya
    )
    gshape = (x.shape[0], y.shape[0])
    return DNDarray(
        out, gshape, types.canonical_heat_type(out.dtype), 0, x.device, x.comm
    )


def _build_ring_cdist(mesh, axis, n_dev, sqrt):
    """shard_map kernel: x blocks stationary, y blocks rotate the ring via
    :func:`heat_tpu.parallel.overlap.ring_sweep` — unrolled so each hop's
    ``ppermute`` overlaps the previous round's MXU work (a ``fori_loop``
    iteration is a scheduling barrier), and the useless final shift the old
    loop performed is elided."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..parallel.collectives import shard_map_unchecked
    from ..parallel.overlap import ring_sweep

    def shard_fn(xs, ys):
        me = lax.axis_index(axis)
        mb = ys.shape[0]

        def body(t, ys_rot, out):
            # after t backward shifts this device holds the block that
            # started on device (me - t) mod n — its column offset
            col = (((me - t) % n_dev) * mb).astype(jnp.int32)
            d2 = _sq_euclidean(xs, ys_rot)
            return lax.dynamic_update_slice(out, d2, (jnp.int32(0), col))

        out = jnp.zeros((xs.shape[0], n_dev * mb), jnp.promote_types(xs.dtype, jnp.float32))
        out = ring_sweep(axis, n_dev, ys, out, body)
        return jnp.sqrt(out) if sqrt else out

    return shard_map_unchecked(
        shard_fn, mesh, in_specs=(P(axis, None), P(axis, None)),
        out_specs=P(axis, None),
    )


def _build_ring_cdist_q(mesh, axis, n_dev, sqrt):
    """Quantized-corpus ring: same dataflow as :func:`_build_ring_cdist`
    but the MOVING operand is the int8/fp8 corpus block — each ring hop
    carries 1-byte elements over ICI (4x less wire traffic than f32) and
    HBM holds only the quantized copy.  The per-feature scales are
    replicated (they are O(d) bytes) and the dequant happens per step
    right before the MXU expansion, so the f32 corpus never exists at
    rest."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    from ..parallel.collectives import shard_map_unchecked
    from ..parallel.overlap import ring_sweep

    def shard_fn(xs, ys_q, scale):
        me = lax.axis_index(axis)
        mb = ys_q.shape[0]

        def body(t, ys_rot, out):
            col = (((me - t) % n_dev) * mb).astype(jnp.int32)
            ys = (ys_rot.astype(jnp.float32) * scale[None, :]).astype(xs.dtype)
            d2 = _sq_euclidean(xs, ys)
            return lax.dynamic_update_slice(out, d2, (jnp.int32(0), col))

        out = jnp.zeros(
            (xs.shape[0], n_dev * mb), jnp.promote_types(xs.dtype, jnp.float32)
        )
        out = ring_sweep(axis, n_dev, ys_q, out, body)
        return jnp.sqrt(out) if sqrt else out

    return shard_map_unchecked(
        shard_fn, mesh,
        in_specs=(P(axis, None), P(axis, None), P()),
        out_specs=P(axis, None),
    )


def cdist_quantized(x: DNDarray, qy, sqrt: bool = True) -> Optional[DNDarray]:
    """Distance matrix against a QUANTIZED corpus
    (:class:`~heat_tpu.core.quantize.QuantizedDNDarray` with per-feature
    scales, ``axis=1``) through the quantized ring.  Returns ``None``
    when the ring layout doesn't fit (single device, non-row splits,
    non-mesh-divisible rows) — the caller dequantizes and takes the
    ordinary :func:`cdist` dispatch instead."""
    from ..core import sanitation

    sanitation.sanitize_in(x)
    if qy.axis != 1:
        raise ValueError(
            "cdist_quantized needs per-feature scales (channel axis 1 of "
            f"the (n, d) corpus), got channel axis {qy.axis}"
        )
    if x.shape[-1] != qy.shape[1]:
        raise ValueError(
            f"feature dims disagree: {x.shape} vs corpus {qy.shape}"
        )
    comm = x.comm
    n_dev = comm.size
    if not (
        x.split == 0
        and qy.split == 0
        and n_dev > 1
        and x.shape[0] % n_dev == 0
        and qy.shape[0] % n_dev == 0
    ):
        return None
    from ..parallel.collectives import jit_shard_map_cached

    comp = jnp.promote_types(x.larray.dtype, jnp.float32)
    out = jit_shard_map_cached(
        _build_ring_cdist_q, comm.mesh, comm.split_axis, n_dev, sqrt
    )(x.larray.astype(comp), qy.q, qy.scale)
    gshape = (x.shape[0], qy.shape[0])
    return DNDarray(
        out, gshape, types.canonical_heat_type(out.dtype), 0, x.device, x.comm
    )


def _ring_cdist(x: DNDarray, y: DNDarray, xa, ya, sqrt: bool = True,
                exact: bool = False) -> Optional[DNDarray]:
    """Ring dataflow for the both-row-split case (the reference's hand-written
    Send/Recv ring, distance.py:209, as a ``ppermute`` chain): each device
    keeps its x block stationary while y blocks rotate, so the replicated
    copy of y that GSPMD's all-gather would materialize never exists —
    per-device memory stays O(m/N) for the moving operand.

    Returns None (fall through to GSPMD) unless both operands are split
    along rows with mesh-divisible row counts on a multi-device mesh.

    Wire plane (round 17): an eligible f32 corpus may rotate the ring
    absmax-quantized with global per-feature scales — the same program
    :func:`cdist_quantized` runs for an already-quantized corpus, here
    as a ``WIRE_ARMS`` tuning decision per geometry (``core/wire.py``)
    measured against the f32 ring."""
    comm = x.comm
    n_dev = comm.size
    if not _ring_eligible(x, y):
        return None
    from ..core import wire as _wire
    from ..parallel.collectives import jit_shard_map_cached

    # xa/ya are the dtype-promoted logical arrays from _prep; with the
    # divisibility guard they coincide with the physical layout
    mb = int(y.shape[0]) // n_dev
    d_feat = int(y.shape[1])
    itemsize = max(int(jnp.dtype(ya.dtype).itemsize), 1)
    moved = mb * d_feat * (n_dev - 1) * itemsize

    def run_f32():
        return jit_shard_map_cached(
            _build_ring_cdist, comm.mesh, comm.split_axis, n_dev, sqrt
        )(xa, ya)

    def run_q(wm):
        # per-feature grid over the WHOLE corpus: the scales are global
        # (replicated, O(d) bytes) so every rotating block dequantizes
        # with the same table — identical math to cdist_quantized
        q, scale = _wire.absmax_encode(ya, wm, (1,))
        return jit_shard_map_cached(
            _build_ring_cdist_q, comm.mesh, comm.split_axis, n_dev, sqrt
        )(xa, q, scale)

    wire_arm, wire_d = "wire_f32", None
    if _wire.eligible(ya.dtype, moved, exact=exact):
        wire_arm, wire_d = _wire.choose(
            "cdist", (tuple(x.shape), tuple(y.shape), n_dev, str(ya.dtype)),
            desc=f"ring cdist {tuple(x.shape)}x{tuple(y.shape)} S={n_dev}",
        )
    if wire_d is not None and wire_d.explore:
        out = _wire.explore(wire_d, lambda wm: run_q(wm) if wm else run_f32())
    elif wire_arm != "wire_f32":
        wm = wire_arm[len("wire_"):]
        _wire.account(
            "cdist", wire_arm, moved,
            _wire.payload_nbytes(mb * d_feat * (n_dev - 1), d_feat, wm),
        )
        out = run_q(wm)
    else:
        out = run_f32()
    gshape = (x.shape[0], y.shape[0])
    return DNDarray(
        out, gshape, types.canonical_heat_type(out.dtype), 0, x.device, x.comm
    )


def cdist(x: DNDarray, y: Optional[DNDarray] = None, quadratic_expansion: bool = False) -> DNDarray:
    """Euclidean distance matrix (reference: distance.py:136).

    ``quadratic_expansion`` is accepted for parity; on TPU the expansion is
    always used (it is the MXU path).  Layout dispatch: x row-split with
    small replicated y → fused Pallas kernel; both row-split → explicit
    ``ppermute`` ring (the reference's algorithm); anything else → GSPMD —
    deferred as a fusion-DAG node when the engine is on, so a trailing
    reduction (k-means' argmin) lands in the same executable."""
    from ..core import fusion

    x, y, promoted = _check(x, y)
    if (
        fusion.enabled()
        and not _pallas_eligible(x, y, promoted)
        and not _ring_eligible(x, y)
    ):
        lazy = _lazy_cdist(x, y, promoted, _result_split(x, y), sqrt=True)
        if lazy is not None:
            return lazy
    xa, ya = x.larray.astype(promoted), y.larray.astype(promoted)
    fast = _pallas_rowsplit_cdist(x, y, ya, sqrt=True)
    if fast is not None:
        return fast
    ring = _ring_cdist(x, y, xa, ya, sqrt=True)
    if ring is not None:
        return ring
    d = jnp.sqrt(_sq_euclidean(xa, ya))
    split = _result_split(x, y)
    out = DNDarray(d, tuple(d.shape), types.canonical_heat_type(d.dtype), split, x.device, x.comm)
    return _ensure_split(out, split)


def rbf(
    x: DNDarray,
    y: Optional[DNDarray] = None,
    sigma: float = 1.0,
    quadratic_expansion: bool = False,
) -> DNDarray:
    """Gaussian (RBF) similarity matrix exp(−d²/2σ²) (reference:
    distance.py:159)."""
    from ..core import exponential, fusion

    x, y, promoted = _check(x, y)
    if fusion.enabled():
        d2 = _lazy_cdist(x, y, promoted, _result_split(x, y), sqrt=False)
        if d2 is not None:
            # -, / and exp ride the heat ops and extend the same DAG
            return exponential.exp(-d2 / (2.0 * sigma * sigma))
    xa, ya = x.larray.astype(promoted), y.larray.astype(promoted)
    d2 = _sq_euclidean(xa, ya)
    s = jnp.exp(-d2 / (2.0 * sigma * sigma))
    split = _result_split(x, y)
    out = DNDarray(s, tuple(s.shape), types.canonical_heat_type(s.dtype), split, x.device, x.comm)
    return _ensure_split(out, split)


def manhattan(x: DNDarray, y: Optional[DNDarray] = None, expand: bool = False) -> DNDarray:
    """L1 distance matrix (reference: distance.py:186). No matmul form exists;
    the (n, m, f) broadcast is VPU work that XLA tiles."""
    x, y, xa, ya = _prep(x, y)
    d = jnp.sum(jnp.abs(xa[:, None, :] - ya[None, :, :]), axis=-1)
    split = _result_split(x, y)
    out = DNDarray(d, tuple(d.shape), types.canonical_heat_type(d.dtype), split, x.device, x.comm)
    return _ensure_split(out, split)


# fusion op-table entry: the composite kernel gets a stable census name so
# fused-chain HLO/describe() output reads "euclid_cdist" not a lambda repr
from ..core import fusion as _fusion

_fusion.register_op(_euclid_kernel, "euclid_cdist", kind="composite")
