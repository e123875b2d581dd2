"""Fused CholeskyQR2 panel kernel: syrk + Cholesky + trsm in one pass.

The measured problem (ROADMAP item 3): square QR runs at 9–14% MFU
because the BCGS2 panel chain in ``core/linalg/qr.py`` is three
launches per panel pass — ``G = AᵀA`` (syrk), ``chol(G)``, and the
triangular solve for ``R⁻¹`` — with the small ``(n, n)`` Gram matrix
round-tripping HBM between each.  XLA's Cholesky itself lowers to a
sequential loop of small kernels that never saturates anything.

This kernel runs the whole panel pass in ONE ``pallas_call``: the tall
operand streams through VMEM in row blocks accumulating ``G`` into an
f32 scratch (the syrk), and on the last grid step the same scratch is
factorized in-register — a masked right-looking Cholesky (one column
per ``fori_loop`` step, rank-1 Schur update on the MXU) followed by a
masked forward substitution for ``L⁻¹`` — writing ``R = Lᵀ`` and
``R⁻¹ = L⁻ᵀ`` without ``G`` ever leaving VMEM.  f32 accumulation
throughout (matching the classic path's ``Precision.HIGHEST``).

Numerics: same algorithm as the classic lowering to rounding — value
equality is within f32 tolerance, verified by the ``orthogonality_defect``
probe in tests.  Ill-conditioned panels break down to NaN exactly like
``jnp.linalg.cholesky`` (negative pivot → ``sqrt`` NaN → propagates),
so ``qr()``'s eager-check/Householder fallback contract is unchanged.

Dispatched as the ``kernel`` autotune arm behind ``qr()`` (see
``core/linalg/qr.py``): measured per geometry against the classic
three-launch chain, safe decline on mixed precision, non-f32 dtypes,
sharded operands, and panels whose Gram working set would overflow
VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_common import LANE, mode, pad_to

__all__ = ["fused_gram_chol", "panel_mode"]

# largest padded panel width whose in-kernel working set (G scratch +
# A/L/X temporaries, 4 × n_pad² f32) stays well inside ~16 MiB VMEM
_MAX_N_PAD_TPU = 512
# the interpreter has no VMEM; allow the blocked-QR leaf width of the
# reference-CI square shape so CPU tests cover the real recursion
_MAX_N_PAD_INTERPRET = 1024

_BLOCK_M = 1024


def _leaf_panel_n(m: int, n: int) -> int:
    """Widest CholeskyQR2 leaf the blocked BCGS2 recursion reaches from
    an ``(m, n)`` root: halve until the panel is 2x-tall (mirrors
    ``_blocked_qr``)."""
    while m < 2 * n and n > 1:
        n //= 2
    return n


def panel_mode(m: int, n: int, dtype, mixed: bool, split, nshards: int) -> str:
    """Dispatch mode for one ``qr()`` call: ``tpu``/``interpret`` when
    every CholeskyQR2 leaf panel fits the kernel, ``off`` otherwise.

    Safe declines: mixed precision (the bf16 pass-1 contract belongs to
    the classic path), non-f32 dtypes, sharded operands (the kernel is
    a single-device program; a replicated input on a multi-device mesh
    is run per device under shard_map by the call site), degenerate
    panels, and leaf widths whose Gram working set overflows VMEM."""
    if mixed or jnp.dtype(dtype) != jnp.dtype(jnp.float32):
        return "off"
    if split is not None and nshards > 1:
        return "off"
    if n < 2 or m < n:
        return "off"
    km = mode()
    if km == "off":
        return "off"
    leaf = _leaf_panel_n(m, n)
    leaf_pad = -(-leaf // LANE) * LANE
    limit = _MAX_N_PAD_INTERPRET if km == "interpret" else _MAX_N_PAD_TPU
    if leaf_pad > limit or leaf < 2:
        return "off"
    return km


def _panel_kernel(n_true, a_ref, r_ref, rinv_ref, g_ref):
    @pl.when(pl.program_id(0) == 0)
    def _():
        g_ref[:] = jnp.zeros_like(g_ref)

    a = a_ref[:].astype(jnp.float32)
    # syrk: contract the row-block dim; accumulates across grid steps
    g_ref[:] += jax.lax.dot_general(
        a, a, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(0) == pl.num_programs(0) - 1)
    def _():
        n = g_ref.shape[0]
        rows = jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
        cols = jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
        colr = jax.lax.broadcasted_iota(jnp.int32, (1, n), 1)
        rowc = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0)

        r_ref[:] = jnp.zeros_like(r_ref)
        # rinv_ref holds B^T of the right-looking solve L·X = I (B starts
        # as I and ends as X = L^-1), i.e. it ends as X^T = R^-1
        rinv_ref[:] = jnp.where(rows == cols, 1.0, 0.0).astype(rinv_ref.dtype)

        # One loop, one column per step, everything in VMEM refs (a
        # loop-carried (n, n) value would not fit the vector registers).
        # Right-looking Cholesky on the Schur complement in g_ref, with
        # the triangular solve folded in: column j of L is final at step
        # j, which is all the solve's step j needs.  Row j of the
        # symmetric Schur complement is a dynamic sublane slice; its
        # column form is a masked lane reduction (no transposes, no K=1
        # matmuls).  Pad columns of G are zero and never touched (the
        # loop stops at n_true); breakdown (d <= 0) NaN-latches through
        # rsqrt exactly like jnp.linalg.cholesky.
        def body(j, carry):
            g = g_ref[:]
            row = g_ref[pl.ds(j, 1), :]
            col = jnp.sum(jnp.where(cols == j, g, 0.0), axis=1, keepdims=True)
            d = jnp.sum(jnp.where(colr == j, row, 0.0), axis=1, keepdims=True)
            inv = jax.lax.rsqrt(d)
            rrow = jnp.where(colr >= j, row * inv, 0.0)   # R[j, :]
            lcol = jnp.where(rowc >= j, col * inv, 0.0)   # L[:, j]
            g_ref[:] = g - lcol * rrow
            r_ref[pl.ds(j, 1), :] = rrow.astype(r_ref.dtype)

            bt = rinv_ref[:].astype(jnp.float32)
            xcol = inv * jnp.sum(
                jnp.where(cols == j, bt, 0.0), axis=1, keepdims=True
            )                                             # X[j, :]^T
            bt = bt - xcol * jnp.where(colr > j, rrow, 0.0)
            rinv_ref[:] = jnp.where(cols == j, xcol, bt).astype(rinv_ref.dtype)
            return carry

        jax.lax.fori_loop(0, n_true, body, 0)


def fused_gram_chol(x: jax.Array, *, interpret: bool = False):
    """One fused panel pass over ``x`` (m, n): returns ``(r, rinv)``
    with ``r = chol(xᵀx)ᵀ`` and ``rinv = r⁻¹``, both ``(n, n)``.

    Callers gate on :func:`panel_mode` first.  Equivalent to the
    classic ``gram → cholesky → triangular_solve`` chain to f32
    rounding."""
    m, n = x.shape
    a = pad_to(x, (8, LANE))
    m_pad, n_pad = a.shape
    bm = m_pad if m_pad <= _BLOCK_M else _BLOCK_M
    if m_pad % bm:
        a = pad_to(a, (bm, LANE))
        m_pad = a.shape[0]
    r, rinv = pl.pallas_call(
        functools.partial(_panel_kernel, n),
        grid=(m_pad // bm,),
        in_specs=[pl.BlockSpec((bm, n_pad), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((n_pad, n_pad), lambda i: (0, 0)),
            pl.BlockSpec((n_pad, n_pad), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, n_pad), x.dtype),
            jax.ShapeDtypeStruct((n_pad, n_pad), x.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((n_pad, n_pad), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        cost_estimate=pl.CostEstimate(
            # syrk dominates; the in-VMEM factorization adds ~n³/3 + n³
            flops=m_pad * n_pad * n_pad + 2 * n_pad**3,
            # the fusion win: the panel is read ONCE, G never leaves
            # VMEM, only the two (n, n) factors are written
            bytes_accessed=(m_pad * n_pad + 2 * n_pad * n_pad)
            * x.dtype.itemsize,
            transcendentals=n_pad,
        ),
        interpret=interpret,
    )(a)
    return r[:n, :n], rinv[:n, :n]
