"""Distributed QR decomposition (reference: heat/core/linalg/qr.py, 1039 LoC).

The reference implements a tiled CAQR over ``SquareDiagTiles`` with per-tile
geqrf + pairwise tile-row merges and hand-scheduled Bcast/Send/Recv
(qr.py:319, :487, :672).  The TPU rebuild replaces the tile scheduler with the
standard **TSQR tree** (SURVEY.md §7 hard-part #2): under ``shard_map`` each
device factors its row block locally (XLA geqrf on the MXU), the small R
factors are all-gathered (one ICI collective), a replicated merge-QR yields
the global R, and each device multiplies its local Q by its slice of the merge
Q — two local QRs and one all-gather in total, versus the reference's
O(columns × ranks) message rounds.

Applies when ``a.split == 0`` (tall-skinny: the per-device column count must
fit one device). Replicated or column-split inputs use XLA's native QR.
"""

from __future__ import annotations

import collections
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from .. import autotune, sanitation, telemetry, types
from ..dndarray import DNDarray, _ensure_split
from ...ops import qr_panel
from ...ops._pallas_common import LANE
from ...parallel.collectives import jit_shard_map_cached, on_each_device
from ...parallel.collectives import shard_map_unchecked as _shard_map

__all__ = ["qr", "orthogonality_defect"]

QR = collections.namedtuple("QR", "Q, R")


def orthogonality_defect(q: DNDarray) -> DNDarray:
    """Post-hoc orthogonality probe: ``max|QᵀQ - I|`` as a 0-d DNDarray.

    The opt-in companion to ``qr(..., check="defer")``: the deferred path
    NaN-latches Cholesky *breakdown* but cannot flag the conditioning band
    (cond(A) ≳ 1/sqrt(eps_f32) ≈ 3e3, see :func:`qr`) where the GEMM paths
    return finite factors of degraded orthogonality.  This is one GEMM over
    the split axis (split-0 inputs: XLA lowers the contraction to a single
    all-reduce of the n×n Gram matrix) and stays on device — dispatch
    remains async until the caller reads the scalar back.  Well-conditioned
    f32 factors probe at ~1e-6; values ≫ sqrt(eps_f32) ≈ 3e-4 mean the
    factorization should be re-run with Householder (the replicated
    ``jnp.linalg.qr`` route) or in f64."""
    sanitation.sanitize_in(q)
    gram = None
    if q.split == 0 and q.ndim == 2 and q.comm.size > 1:
        # the split axis is the contraction: ride the overlap engine's
        # reduce-scatter ring (out replicated) so the partial Gram transfer
        # overlaps each step's local dot; physical transpose keeps the
        # k-pads consistent (the rs kernel masks them).  Decline-safe.
        from ...parallel import overlap

        m, n = q.shape
        gram = overlap.matmul_raw(
            q.comm, q.parray.T, q.parray, (n, m), (m, n), 1, 0, None,
            precision=jax.lax.Precision.HIGHEST,
        )
    if gram is None:
        arr = q.larray
        gram = jnp.matmul(
            arr.T, arr, precision=jax.lax.Precision.HIGHEST
        )
    defect = jnp.max(jnp.abs(gram - jnp.eye(gram.shape[0], dtype=gram.dtype)))
    return DNDarray(
        defect, (), types.canonical_heat_type(defect.dtype),
        None, q.device, q.comm,
    )


def _build_tsqr(mesh, axis, calc_q: bool = True):
    """TSQR kernel for jit_shard_map_cached (one compile per mesh/axis/
    calc_q).  With ``calc_q=False`` the tall Q1·Q2-block GEMM — the
    dominant FLOPs — is skipped entirely (the reference's ``calc_q``
    contract, qr.py:17)."""

    def ht_tsqr(block):
        # block: (m_local, n) — local panel factorization on the MXU
        n = block.shape[1]
        with jax.named_scope("ht.tsqr.leaf"):
            q1, r1 = jnp.linalg.qr(block, mode="reduced")
        with jax.named_scope("ht.tsqr.gather"):
            # gather the small R factors: (nshards*n, n); one ICI all-gather
            rs = lax.all_gather(r1, axis_name=axis, axis=0, tiled=True)
        with jax.named_scope("ht.tsqr.merge"):
            q2, r = jnp.linalg.qr(rs, mode="reduced")
            # normalize signs so R has non-negative diagonal (deterministic
            # across merge orders, matching the reference's comparability
            # guarantees)
            signs = jnp.sign(jnp.diagonal(r))
            signs = jnp.where(signs == 0, 1.0, signs).astype(r.dtype)
            r = r * signs[:, None]
        if not calc_q:
            return r
        with jax.named_scope("ht.tsqr.apply"):
            q2 = q2 * signs[None, :]
            idx = lax.axis_index(axis)
            q2_block = lax.dynamic_slice_in_dim(q2, idx * n, n, axis=0)
            # HIGHEST precision: the MXU's default bf16 passes would cost ~3
            # digits of orthogonality in Q
            q = jnp.matmul(q1, q2_block, precision=jax.lax.Precision.HIGHEST)
        return q, r

    return _shard_map(
        ht_tsqr, mesh,
        in_specs=(P(axis, None),),
        out_specs=(P(axis, None), P(None, None)) if calc_q else P(None, None),
    )


@telemetry.span("qr.tsqr")
def _tsqr(a: DNDarray, calc_q: bool = True):
    """One-level TSQR tree over the split axis."""
    comm = a.comm
    arr = a.larray
    if not jnp.issubdtype(arr.dtype, jnp.inexact):
        arr = arr.astype(jnp.float32)
    fn = jit_shard_map_cached(_build_tsqr, comm.mesh, comm.split_axis, calc_q)
    if not calc_q:
        r = fn(arr)
        r_ht = DNDarray(r, tuple(r.shape), types.canonical_heat_type(r.dtype), None, a.device, comm)
        return None, r_ht
    q, r = fn(arr)
    q_ht = DNDarray(q, tuple(q.shape), types.canonical_heat_type(q.dtype), 0, a.device, comm)
    r_ht = DNDarray(r, tuple(r.shape), types.canonical_heat_type(r.dtype), None, a.device, comm)
    return _ensure_split(q_ht, 0), r_ht


# Widths of the column blocks in which :func:`_cholesky_qr2` computes its
# tall GEMMs; PERF.md section 6 (PR 26) holds the chip readings behind them.
# Blocks are about LANE columns wide, at most _MAX_BLOCKS a stage (compile
# time), and their edges are whole tiles of the tall operand's layout, so
# that XLA slices without a relayout and writes each block in place: on the
# TPU a tall float32 array is rows-minor, tiled T(8, 128) with the columns
# in eights, unless its width is a multiple of 128, where the columns lie
# on the lanes.
_SUBLANES = 8
_MAX_BLOCKS = 8


def _block_edges(n: int) -> tuple:
    """Column offsets ``(0, c_1, ..., n)`` of the blocks in which
    :func:`_cholesky_qr2` computes its Gram and apply GEMMs at width ``n``.
    Under two blocks' width it is ``(0, n)``: one block, the dense GEMMs
    (the saving did not pay there).  Else ``ceil(n / 128)`` blocks, at most
    ``_MAX_BLOCKS``, of one width rounded up to the layout's tile; the last
    block takes what is left."""
    if n < 2 * LANE:
        return (0, n)
    nb = min(_MAX_BLOCKS, -(-n // LANE))
    tile = _SUBLANES if n % LANE else LANE
    width = -(-n // (nb * tile)) * tile
    return tuple(range(0, n, width)) + (n,)


def _gram_upper(x, edges, **dot_kw):
    """``xᵀx`` from its upper block triangle: block row ``i`` is one GEMM
    ``x[:, c_i:c_{i+1}]ᵀ · x[:, c_i:]`` (contracting dim 0 — an explicit
    ``x.T`` would materialize a transposed copy of the tall operand), and
    the blocks left of the diagonal are the mirror image, never computed.
    With more than one block the result is exactly symmetric."""
    dot = functools.partial(
        jax.lax.dot_general, dimension_numbers=(((0,), (0,)), ((), ())), **dot_kw
    )
    if len(edges) == 2:
        return dot(x, x)
    strips = [dot(x[:, lo:hi], x[:, lo:]) for lo, hi in zip(edges, edges[1:])]
    u = jnp.concatenate(
        [jnp.pad(s, ((0, 0), (lo, 0))) for lo, s in zip(edges, strips)], axis=0
    )
    return jnp.triu(u) + jnp.triu(u, 1).T


def _apply_upper(x, rinv, edges, **dot_kw):
    """``x · rinv`` for an upper-triangular ``rinv``: column block ``j`` is
    one GEMM ``x[:, :c_{j+1}] · rinv[:c_{j+1}, c_j:c_{j+1}]``; the rows of
    ``rinv`` below the block are exact zeros and are never multiplied."""
    if len(edges) == 2:
        return jnp.matmul(x, rinv, **dot_kw)
    blocks = [
        jnp.matmul(x[:, :hi], rinv[:hi, lo:hi], **dot_kw)
        for lo, hi in zip(edges, edges[1:])
    ]
    # each block is written in place into one uninitialized buffer (XLA
    # fuses the GEMM with its dynamic-update-slice); a concatenate costs a
    # second read and write of the whole result, zeros a memset of it
    q = jax.lax.empty((x.shape[0], rinv.shape[1]), blocks[0].dtype)
    for lo, block in zip(edges, blocks):
        q = jax.lax.dynamic_update_slice(q, block, (0, lo))
    return q


@functools.partial(jax.jit, static_argnames=("calc_q", "mixed", "kernel"))
@telemetry.module_name("ht_cholesky_qr2")
def _cholesky_qr2(arr, calc_q: bool = True, mixed: bool = False, kernel: str = ""):
    """CholeskyQR2: tall-skinny QR as pure MXU matmuls.

    XLA's Householder QR runs at ~0.1 TFLOP/s on TPU (sequential panel
    updates); CholeskyQR2 is all GEMMs:
    ``G = AᵀA; R = chol(G)ᵀ; Q = A·R⁻¹``, repeated once to restore
    orthogonality to machine precision (Yamamoto et al. 2015 — stable for
    cond(A) up to ~1/√eps).  The triangular solve is materialized as
    ``A @ R⁻¹`` so the big operand rides the MXU.  Ill-conditioned inputs
    overflow the Gram matrix and surface as NaNs; :func:`qr` checks and
    falls back to Householder eagerly.

    ``G`` is symmetric and ``R⁻¹`` upper triangular, so both tall GEMMs
    are computed block-triangularly (:func:`_gram_upper`,
    :func:`_apply_upper`) over the column blocks :func:`_block_edges`
    derives from the width: with ``nb`` blocks ``(nb + 1) / (2 nb)`` of the
    dense multiply-adds, the same arithmetic on the entries that are kept.
    Dense it is ``8mn²`` FLOPs; towards ``4mn²`` as ``nb`` grows, which is
    Householder's count with explicit Q (and twice its ``2mn²`` for R
    alone).  One block is the dense program.

    ``mixed=True`` runs the FIRST pass's two tall GEMMs in bf16 with f32
    accumulation (bf16 shares f32's exponent range, so the cast cannot
    overflow the Gram); the second pass stays f32-HIGHEST, which restores
    orthogonality to f32 level (measured ~4e-5 for n=512 vs ~1e-5 full-f32)
    while the reconstruction ``A - QR`` is bf16-working-precision (~2e-3
    relative) because R1 derives from the bf16 Gram.  ~2.2x faster on v5e
    (the pass-1 GEMMs ride the MXU at bf16 rate).

    ``kernel`` (``""``/``"tpu"``/``"interpret"``, static) routes the
    f32 panel passes through the fused Pallas syrk+chol+trsm kernel
    (``ops/qr_panel.py``) instead of the three-launch chain; bf16 pass-1
    (``mixed``) always stays classic.  Callers gate on
    ``qr_panel.panel_mode`` — the autotune ``kernel`` arm in :func:`qr`."""
    eye = jnp.eye(arr.shape[1], dtype=arr.dtype)
    edges = _block_edges(arr.shape[1])

    # device scopes ht.qr.gram<i> / chol<i> / apply<i> name the stages of
    # pass i (trace-time only; every block of a stage sits under the stage's
    # scope); the fused panel kernel, which holds the Gram, its Cholesky and
    # the inverse in one launch, sits under gram<i>
    scope = jax.named_scope

    def operands(lowp, *xs):
        # the precision of a pass's tall GEMMs: bf16 operands with f32
        # accumulation (``mixed`` pass 1), else f32 at HIGHEST
        if lowp:
            return [x.astype(jnp.bfloat16) for x in xs], {
                "preferred_element_type": jnp.float32
            }
        return xs, {"precision": jax.lax.Precision.HIGHEST}

    def gram_chol(x, lowp, i):
        with scope(f"ht.qr.gram{i}"):
            (xo,), kw = operands(lowp, x)
            g = _gram_upper(xo, edges, **kw).astype(x.dtype)
        with scope(f"ht.qr.chol{i}"):
            return jnp.linalg.cholesky(g)

    def fused_panel(x, i):
        # fused panel pass: one launch, G stays in VMEM
        with scope(f"ht.qr.gram{i}"):
            return qr_panel.fused_gram_chol(x, interpret=(kernel == "interpret"))

    def chol_step(x, i, lowp=False):
        if kernel and not lowp:
            r, rinv = fused_panel(x, i)
        else:
            l = gram_chol(x, lowp, i)
            with scope(f"ht.qr.chol{i}"):
                rinv = jax.lax.linalg.triangular_solve(l, eye, lower=True, left_side=True).T
            r = l.T
        with scope(f"ht.qr.apply{i}"):
            (xo, ro), kw = operands(lowp, x, rinv)
            q = _apply_upper(xo, ro, edges, **kw).astype(x.dtype)
        return q, r

    q1, r1 = chol_step(arr, 1, lowp=mixed)
    if calc_q:
        q, r2 = chol_step(q1, 2)
    else:
        # R-only: the second pass still needs R2 = chol(Q1ᵀQ1)ᵀ for the
        # orthogonality-corrected R, but the tall Q1·R2⁻¹ GEMM is skipped
        if kernel:
            r2 = fused_panel(q1, 2)[0]
            q = None
        else:
            q, r2 = None, gram_chol(q1, False, 2).T
    with scope("ht.qr.chol2"):
        r = jnp.matmul(r2, r1, precision=jax.lax.Precision.HIGHEST)
    return q, r


@functools.partial(jax.jit, static_argnames=("mixed", "calc_q", "kernel"))
@telemetry.module_name("ht_blocked_qr")
def _blocked_qr(arr, mixed: bool = False, calc_q: bool = True, kernel: str = ""):
    """Blocked QR for square-ish matrices (m >= n) as pure GEMMs.

    XLA's Householder QR runs ~0.1-1 TFLOP/s on TPU (sequential panel
    updates off the MXU) — the round-4/5 cb artifacts measured the square
    n=2048 reference-CI shape at 2.4% MFU through it.  This path is BCGS2:
    split the columns, factor the left panel (recursively, bottoming out in
    :func:`_cholesky_qr2` once the panel is 2x-tall), then orthogonalize
    the right block against Q1 with a classical Gram-Schmidt update
    REPEATED ONCE (the "twice is enough" reorthogonalization — Barlow &
    Smoktunowicz 2013 give O(eps) orthogonality for BCGS2 with a stable
    panel factorization).  Every flop is a GEMM; the recursion unrolls at
    trace time (depth <= log2(n)).  Ill-conditioned inputs surface as NaNs
    through the panel Cholesky, so :func:`qr`'s eager check / Householder
    fallback protects this path exactly as it does the tall-skinny one.
    """
    m, n = arr.shape
    if m >= 2 * n:
        return _cholesky_qr2(arr, calc_q=calc_q, mixed=mixed, kernel=kernel)
    n1 = n // 2
    a1, a2 = arr[:, :n1], arr[:, n1:]
    # q1 is always needed (it orthogonalizes the right block); only the
    # RIGHTMOST leaf's Q is skippable for R-only factorizations
    q1, r11 = _blocked_qr(a1, mixed=mixed, kernel=kernel)

    def proj(q, x):
        # contract dim 0 directly: qᵀx without materializing qᵀ
        return jax.lax.dot_general(
            q, x, (((0,), (0,)), ((), ())), precision=jax.lax.Precision.HIGHEST
        )

    hi = jax.lax.Precision.HIGHEST
    with jax.named_scope("ht.qr.panel"):
        t1 = proj(q1, a2)
        a2 = a2 - jnp.matmul(q1, t1, precision=hi)
        t2 = proj(q1, a2)  # reorthogonalize: CGS2
        a2 = a2 - jnp.matmul(q1, t2, precision=hi)
        r12 = t1 + t2
    q2, r22 = _blocked_qr(a2, mixed=mixed, calc_q=calc_q, kernel=kernel)
    with jax.named_scope("ht.qr.panel"):
        q = jnp.concatenate([q1, q2], axis=1) if calc_q else None
        r = jnp.block([
            [r11, r12],
            [jnp.zeros((r22.shape[0], n1), r11.dtype), r22],
        ])
    return q, r


def _fact_on_each_device(mesh, tall: bool, calc_q: bool, mixed: bool, kernel: str):
    """``jit_shard_map_cached`` builder: the single-device GEMM
    factorization through the Pallas panel kernel on a multi-device mesh
    (replicated operand; see ``collectives.on_each_device``)."""
    fact = _cholesky_qr2 if tall else _blocked_qr
    return on_each_device(
        functools.partial(fact, calc_q=calc_q, mixed=mixed, kernel=kernel), mesh
    )


def qr(
    a: DNDarray,
    tiles_per_proc: int = 1,
    calc_q: bool = True,
    overwrite_a: bool = False,
    check: str = "eager",
    precision: str = "float32",
) -> QR:
    """QR decomposition of a 2-D DNDarray (reference: qr.py:17).

    ``tiles_per_proc`` is accepted for API parity; the TSQR tree has no tile
    knob (its panel is the device shard).

    ``check`` governs the Cholesky breakdown check on every single-device
    GEMM path — tall-skinny CholeskyQR2 (m >= 2n) AND the square-ish
    blocked BCGS2 path (n <= m < 2n, round 5):

    - ``"eager"`` (default): one host sync per call — a failed Cholesky
      (ill-conditioned input, NaNs cascade into R) is detected immediately
      and the call falls back to Householder QR.  The sync drains the
      dispatch queue, so back-to-back calls do not pipeline.
    - ``"defer"``: no sync; dispatch stays fully async.  Breakdown is
      NaN-latched: a failed Cholesky yields NaN-filled Q/R that surface at
      the caller's next readback (never silently-wrong finite numbers —
      Cholesky breakdown produces NaN, not garbage values).  Use in
      pipelines that already readback downstream.

      **Conditioning bound**: the NaN latch only fires when Cholesky
      *breaks down*.  CholeskyQR2 (and the blocked BCGS2 path built on
      it, n <= m < 2n) squares the condition number in the Gram matrix,
      so the first pass stays finite while ``cond(A)^2 * eps_f32 < 1`` —
      i.e. up to ``cond(A) ≈ 1/sqrt(eps_f32) ≈ 3e3`` in f32.  Inputs in
      the band between ~3e3 and breakdown (~1/eps ≈ 1e7) return FINITE
      factors whose orthogonality error ``||QᵀQ - I||`` degrades
      gradually; ``"defer"`` cannot flag those.  When the input's
      conditioning is unknown, either use ``"eager"`` (breakdown still
      NaN-latches; moderate ill-conditioning is inherent to the GEMM
      path either way) or probe the result post-hoc with
      :func:`orthogonality_defect` — one GEMM, no sync until *its*
      readback.

    ``precision`` selects the arithmetic on the same two GEMM paths:
    ``"float32"`` (default, all GEMMs f32-HIGHEST) or ``"mixed"``
    (pass-1 GEMMs in bf16 with f32 accumulation — ~2.2x faster on v5e
    with f32-level orthogonality; reconstruction at bf16 working
    precision; see :func:`_cholesky_qr2`; the blocked path applies it
    inside each panel).
    """
    sanitation.sanitize_in(a)
    if a.ndim != 2:
        raise ValueError(f"qr requires a 2-D array, got {a.ndim}-D")
    if check not in ("eager", "defer"):
        raise ValueError(f'check must be "eager" or "defer", got {check!r}')
    if precision not in ("float32", "mixed"):
        raise ValueError(f'precision must be "float32" or "mixed", got {precision!r}')

    m, n = a.shape
    with telemetry.span("linalg.qr", m=m, n=n) as sp:
        return _qr(a, calc_q, check, precision, sp)


def _qr(a: DNDarray, calc_q: bool, check: str, precision: str, sp) -> QR:
    """:func:`qr` after validation; notes the path taken (``tsqr`` /
    ``cholqr2`` / ``blocked`` / ``householder``) on the span ``sp``."""
    m, n = a.shape
    nshards = a.comm.size
    # TSQR needs each local block to have at least n rows: m/nshards >= n
    if a.split == 0 and nshards > 1 and m >= n * nshards:
        sp.note(path="tsqr")
        return QR(*_tsqr(a, calc_q=calc_q))

    arr = a.larray
    if not jnp.issubdtype(arr.dtype, jnp.inexact):
        arr = arr.astype(jnp.float32)
    if m >= n and n >= 2 and jnp.issubdtype(arr.dtype, jnp.floating):
        # tall: CholeskyQR2 directly; square-ish: blocked BCGS2 over
        # CholeskyQR2 panels (round 5 — the jnp.linalg.qr fallback ran the
        # reference-CI square shape at 2.4% MFU, ~10x below the GEMM path)
        mx = precision == "mixed"
        # how far the block-triangular GEMMs engage at this shape (1: dense);
        # for the blocked path, in its widest CholeskyQR2 leaf
        leaf = n if m >= 2 * n else qr_panel._leaf_panel_n(m, n)
        sp.note(blocks=len(_block_edges(leaf)) - 1)

        def fact(km: str = ""):
            if km and nshards > 1:
                return jit_shard_map_cached(
                    _fact_on_each_device, a.comm.mesh, m >= 2 * n, calc_q, mx, km
                )(arr)
            if m >= 2 * n:
                return _cholesky_qr2(arr, calc_q=calc_q, mixed=mx, kernel=km)
            return _blocked_qr(arr, mixed=mx, calc_q=calc_q, kernel=km)

        # round 15: the fused syrk+chol+trsm panel kernel as a measured
        # autotune arm beside the classic lowering (the reference arm)
        kmode = qr_panel.panel_mode(m, n, arr.dtype, mx, a.split, nshards)
        if kmode != "off" and autotune.enabled():
            dt = str(arr.dtype)
            q, r = autotune.run(
                autotune.key("kernel", "qr_panel", m, n, dt, calc_q, nshards),
                {"classic": fact, "kernel": functools.partial(fact, kmode)},
                prior="classic", desc=f"qr {m}x{n} {dt}", site="qr_panel",
                cost={"kernel": dict(
                    sig=("qr_panel_fused", m, n, dt, calc_q),
                    kind="kernel_qr_panel", ops=1,
                    flops=4.0 * m * n * n,
                    hbm_bytes=3.0 * m * n * arr.dtype.itemsize,
                    mesh={"devices": nshards}, dtype=dt,
                )},
            )
        else:
            q, r = fact()
        # "eager": one deliberate host sync per factorization call: the
        # breakdown check (failed Cholesky cascades NaNs into R) costs one
        # scalar readback, traded against never silently returning garbage
        # for ill-conditioned inputs.  An on-device lax.cond over a
        # Householder fallback would keep dispatch async but doubles the
        # compiled program and its HBM high-water mark (BASELINE's 1e5x1e4
        # has no head room to give: on one chip it does not even compile,
        # the triangular solve alone takes 15.79 GB of temporaries, PERF.md
        # section 7).  "defer" skips the sync; breakdown stays NaN-latched
        # in Q/R.
        ok = True
        if check != "defer":
            finite = jnp.all(jnp.isfinite(r))
            with telemetry.sync("qr.breakdown_check"):  # the documented one
                ok = bool(finite)
        if ok:
            # chol succeeded; diagonal is positive by construction, no sign
            # pass needed
            sp.note(path="cholqr2" if m >= 2 * n else "blocked")
            r_ht = DNDarray(
                r, tuple(r.shape), types.canonical_heat_type(r.dtype),
                1 if a.split == 1 else None, a.device, a.comm,
            )
            if not calc_q:
                return QR(None, _ensure_split(r_ht, r_ht.split))
            q_ht = DNDarray(
                q, tuple(q.shape), types.canonical_heat_type(q.dtype),
                a.split, a.device, a.comm,
            )
            return QR(_ensure_split(q_ht, a.split), _ensure_split(r_ht, r_ht.split))
    sp.note(path="householder")
    q, r = jnp.linalg.qr(arr, mode="reduced")
    signs = jnp.sign(jnp.diagonal(r))
    signs = jnp.where(signs == 0, 1.0, signs).astype(r.dtype)
    r = r * signs[:, None]
    q = q * signs[None, :]
    q_ht = DNDarray(q, tuple(q.shape), types.canonical_heat_type(q.dtype), a.split, a.device, a.comm)
    r_ht = DNDarray(
        r, tuple(r.shape), types.canonical_heat_type(r.dtype),
        1 if a.split == 1 else None, a.device, a.comm,
    )
    if not calc_q:
        return QR(None, _ensure_split(r_ht, r_ht.split))
    return QR(_ensure_split(q_ht, a.split), _ensure_split(r_ht, r_ht.split))
