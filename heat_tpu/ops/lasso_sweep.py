"""Fused coordinate-descent sweep kernel for LASSO.

The measured problem (ROADMAP item 3): a CD sweep is n_features
dependent steps of two length-m vector ops (``rho = xⱼ·(r + θⱼxⱼ)``
and the rank-1 residual update), each arithmetically trivial.  XLA
compiles the ``fori_loop`` into a sequential program that re-streams
the residual from HBM every coordinate — ~3 length-m HBM round trips
per coordinate, pure memory-bound tail.

This kernel keeps the residual **resident in VMEM across the entire
sweep**: the grid walks 128-wide coordinate blocks ("arbitrary"
semantics — sequential, VMEM scratch carries over), each step loads one
128-coordinate panel of X laid out coordinate-major (each column a dense
``(m/128, 128)`` tile — an ``(m, 1)`` column would lane-pad 128x), and
an inner ``fori_loop`` runs the 128 dependent coordinate updates against
the in-VMEM residual.  Per sweep, X is read exactly once and the
residual never touches HBM.

Numerics: identical update order and f32 arithmetic as the classic
``_cd_sweep`` (``regression/lasso.py``) — the intercept (coordinate 0)
stays unpenalized, pad coordinates/rows are masked no-ops.  Dispatched
as the ``kernel`` autotune arm behind ``Lasso.fit``: measured per
geometry, safe decline on sharded operands, non-f32 dtypes, and
residuals too tall for VMEM.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_common import LANE, mode, pad_to

__all__ = ["prepare", "sweep", "sweep_mode", "sweep_prepared"]

# one grid step holds a 128-coordinate panel of X (double-buffered) plus
# the residual in VMEM: 2 x 128 x m_pad x 4 B = 8 MiB at the cap
_MAX_M_PAD = 8192
# residual tile: rows pad to 8 sublanes x 128 lanes
_ROW_TILE = 8 * LANE


def sweep_mode(m: int, n: int, dtype, split, nshards: int) -> str:
    """Dispatch mode for one ``Lasso.fit`` geometry: ``tpu`` /
    ``interpret`` when the fused sweep applies, ``off`` otherwise.

    Safe declines: non-f32 dtypes, sharded design matrices (the kernel
    is a single-device program; a replicated matrix on a multi-device
    mesh is run per device under shard_map by ``Lasso.fit``), residuals
    taller than the VMEM budget,
    and degenerate shapes.  Tiny problems decline too — launch overhead
    dwarfs the win — unless the operator forced the Pallas tier
    (``HEAT_TPU_PALLAS``, the cdist skinny-decline precedent)."""
    if jnp.dtype(dtype) != jnp.dtype(jnp.float32):
        return "off"
    if split is not None and nshards > 1:
        return "off"
    if m < 1 or n < 2:
        return "off"
    if -(-m // _ROW_TILE) * _ROW_TILE > _MAX_M_PAD:
        return "off"
    forced = os.environ.get("HEAT_TPU_PALLAS", "") in ("interpret", "tpu")
    if not forced and m * n < 1 << 16:
        return "off"
    return mode()


def _sweep_kernel(m_true, n_true, lam_ref, x_ref, th_ref, r0_ref, o_ref, r_ref):
    j_blk = pl.program_id(0)

    @pl.when(j_blk == 0)
    def _():
        r_ref[:] = r0_ref[:]

    lam = lam_ref[0, 0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANE), 1)

    def total(v):
        return jnp.sum(jnp.sum(v, axis=1, keepdims=True), axis=0, keepdims=True)

    # 128 dependent coordinate updates against the in-VMEM residual.
    # Column j of X arrives as a dense (m_pad/128, 128) tile — the
    # wrapper lays X out coordinate-major — so every step is full-width
    # VPU work; theta lives in one (1, 128) lane vector with masked lane
    # extraction (2-D iota — TPU has no 1-D iota).  Pad rows of X are
    # zero so rho sums only real rows; pad coordinates (jg >= n_true)
    # are forced to zero and cannot move the residual.
    def body(jl, carry):
        th, r = carry
        lm = lane == jl
        xj = x_ref[jl]
        thj = jnp.sum(jnp.where(lm, th, 0.0), axis=1, keepdims=True)
        rho = total(xj * (r + thj * xj)) / m_true
        jg = j_blk * LANE + jl
        # intercept (global coordinate 0) unpenalized — reference
        # lasso.py:100 and the classic _cd_sweep agree
        pen = jnp.where(jg == 0, 0.0, lam)
        new = jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - pen, 0.0)
        new = new * jnp.where(jg < n_true, 1.0, 0.0)
        r = r + (thj - new) * xj
        th = jnp.where(lm, new, th)
        return th, r

    th, r = jax.lax.fori_loop(0, LANE, body, (th_ref[:], r_ref[:]))
    r_ref[:] = r
    o_ref[:] = th


def prepare(X: jax.Array, y: jax.Array):
    """Lay ``X`` (m, n) and ``y`` (m,) out for :func:`sweep_prepared`:
    coordinate-major ``(n_pad, m_pad/128, 128)`` so each column is a
    dense vector tile, rows zero-padded to 1024, coordinates to 128.
    Loop-invariant: ``Lasso.fit`` calls it once, outside the sweep loop."""
    Xp = pad_to(X, (_ROW_TILE, LANE))
    m_pad, n_pad = Xp.shape
    Xt = Xp.T.reshape(n_pad, m_pad // LANE, LANE)
    yt = pad_to(y, (m_pad,)).reshape(m_pad // LANE, LANE)
    return Xt, yt


def sweep_prepared(Xt: jax.Array, yt: jax.Array, theta: jax.Array, lam,
                   m: int, *, interpret: bool = False) -> jax.Array:
    """One fused CD sweep over :func:`prepare`'d operands; ``m`` is the
    true row count.  Returns the updated ``(n,)`` theta."""
    n = theta.shape[0]
    n_pad, s, _ = Xt.shape
    thp = pad_to(theta.reshape(1, n), (1, n_pad))
    r0 = yt - jnp.tensordot(thp[0], Xt, axes=(0, 0))
    lam_arr = jnp.full((1, 1), lam, dtype=Xt.dtype)
    out = pl.pallas_call(
        functools.partial(_sweep_kernel, float(m), n),
        grid=(n_pad // LANE,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((LANE, s, LANE), lambda j: (j, 0, 0)),
            pl.BlockSpec((1, LANE), lambda j: (0, j)),
            pl.BlockSpec((s, LANE), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, LANE), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), Xt.dtype),
        scratch_shapes=[pltpu.VMEM((s, LANE), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * s * LANE * n_pad,
            # the fusion win: X read ONCE per sweep, the residual never
            # leaves VMEM (classic re-streams it every coordinate)
            bytes_accessed=(s * LANE * n_pad + s * LANE + 2 * n_pad)
            * Xt.dtype.itemsize,
            transcendentals=0,
        ),
        interpret=interpret,
    )(lam_arr, Xt, thp, r0)
    return out[0, :n]


def sweep(X: jax.Array, y: jax.Array, theta: jax.Array, lam, *,
          interpret: bool = False) -> jax.Array:
    """One fused CD sweep: the drop-in counterpart of ``_cd_sweep`` —
    same update order, residual held in VMEM across all coordinates.

    ``X`` is ``(m, n)``, ``y`` ``(m,)``, ``theta`` ``(n,)``; returns the
    updated ``(n,)`` theta.  Callers gate on :func:`sweep_mode`."""
    Xt, yt = prepare(X, y)
    return sweep_prepared(Xt, yt, theta, lam, X.shape[0], interpret=interpret)
