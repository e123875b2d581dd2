"""Sparse matmul: the DCSR compute path, dispatched as measured
autotune arms (ROADMAP item 6 — the sparse counterpart of the
ring-vs-GSPMD and classic-vs-kernel consults).

``matmul(A, x)`` computes ``A @ x`` for a row-split :class:`DCSR_matrix`
against a dense vector/matrix.  Two arms per (sparsity-geometry
fingerprint, device kind):

``dense``
    ``todense()`` + the ordinary matmul — the authoritative reference.
    Explore always returns THIS arm's result, so numerics never depend
    on tuning state (the round-15 explore contract).
``gather``
    Jitted segment-sum CSR matvec over the padded slabs (gather
    ``x[cols]``, scatter-add per-entry products into the row outputs) —
    runs on every backend, and is the static-dispatch default when the
    tuning plane is off (``HEAT_TPU_SPMV`` overrides: ``dense`` /
    ``gather``).

Each arm carries a telemetry cost-ledger row (``kind="spmv_*"`` with
nnz-based FLOP/HBM models) so ``roofline_report()`` places the measured
winner.  :func:`matvec_program` is the chain-consult path: it returns a
jit-static ``(apply_fn, operands)`` pair for ``v ↦ A @ v`` inside a
fused loop (Lanczos), consuming a resolved winner but never exploring —
and never returning the ``dense`` arm, so a sparse solve stays sparse
end-to-end (zero densifications of the operand).
"""

from __future__ import annotations

import os
from functools import lru_cache, partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core import autotune, memtrack, types
from ..core.dndarray import DNDarray, _ensure_split
from ..parallel.collectives import shard_map_unchecked
from ._operations import _expand_rows
from .dcsr_matrix import DCSR_matrix

__all__ = ["SPMV_ARMS", "matmul", "matvec_program"]


# ------------------------------------------------------------- gather arm


def _gather_block(data, idx, ptr, x2, rows_per):
    """One shard's CSR matvec as gather + scatter-add: per-entry products
    ``data[e] * x[idx[e]]`` land in their row via ``.at[].add`` (pad
    entries carry the sentinel row — ``mode="drop"`` discards them)."""
    cap = data.shape[0]
    rows = _expand_rows(ptr, cap, rows_per)
    contrib = data[:, None] * jnp.take(x2, idx, axis=0)
    out = jnp.zeros((rows_per, x2.shape[1]), contrib.dtype)
    return out.at[rows].add(contrib, mode="drop")


@lru_cache(maxsize=None)
def _jit_gather_sharded(mesh, axis_name, rows_per):
    spec = P(axis_name, None)

    def local(data, idx, ptr, x2):
        return _gather_block(data[0], idx[0], ptr[0], x2, rows_per)

    return jax.jit(
        shard_map_unchecked(
            local, mesh,
            in_specs=(spec, spec, spec, P(None, None)),
            out_specs=P(axis_name, None),
        )
    )


@lru_cache(maxsize=None)
def _jit_gather_local(rows_per):
    return jax.jit(
        lambda data, idx, ptr, x2: _gather_block(data, idx, ptr, x2, rows_per)
    )


def _run_gather(A: DCSR_matrix, x2: jax.Array) -> jax.Array:
    n = A.shape[0]
    if A.is_distributed():
        fn = _jit_gather_sharded(A.comm.mesh, A.comm.split_axis, A.rows_per_shard)
        y = fn(A._data, A._indices, A._lindptr, x2)
    else:
        fn = _jit_gather_local(A.shape[0])
        y = fn(A._data[0], A._indices[0], A._lindptr[0], x2)
    return y[:n]


# -------------------------------------------------------------- dense arm


def _run_dense(A: DCSR_matrix, x2: jax.Array) -> jax.Array:
    from . import manipulations

    dense = manipulations.todense(A)
    return jnp.matmul(dense.larray.astype(x2.dtype), x2)


# round 19: the sparse compute tier — "dense" is the todense() matmul (the
# authoritative reference; explore always returns its result so numerics
# never depend on tuning state), "gather" the jitted segment-sum CSR
# matvec (dense wins near-full matrices, gather the sparse ones).
SPMV_ARMS = ("dense", "gather")
_ARM_RUNNERS = {"dense": _run_dense, "gather": _run_gather}


# --------------------------------------------------------------- dispatch


def _static_arm() -> str:
    """Static dispatch when the tuning plane is off: ``HEAT_TPU_SPMV``
    in ``dense`` / ``gather`` (default ``gather`` — the
    every-backend sparse path); a malformed value raises, naming the
    variable (the env_bytes strictness contract)."""
    raw = os.environ.get("HEAT_TPU_SPMV", "").strip().lower()
    if raw in ("", "auto", "gather"):
        return "gather"
    if raw == "dense":
        return raw
    raise ValueError(
        f"HEAT_TPU_SPMV must be auto|dense|gather, got {raw!r}"
    )


def _nnz_bucket(nnz: int) -> int:
    """Power-of-two nnz bucket for the tuning key: the arm verdict is a
    function of geometry class, not the exact count — without bucketing
    every incremental graph would explore from scratch."""
    return int(nnz).bit_length()


def _tuning_key(A: DCSR_matrix, k: int, dt: str):
    """One sparsity geometry: shape, rhs columns, nnz bucket, slab
    capacity, dtype, mesh size."""
    n, ncols = A.shape
    return autotune.key(
        "spmv", "spmv_csr", n, ncols, k, _nnz_bucket(A.nnz),
        A._data.shape[1], dt, A.comm.size,
    )


def _site_cost(A: DCSR_matrix, k: int, dt: str) -> dict:
    """One cost-ledger program per arm (``kind="spmv_*"``, nnz-based
    FLOP/HBM models)."""
    n, ncols = A.shape
    nnz = A.nnz
    mesh = {"devices": A.comm.size}
    return {
        "dense": dict(
            sig=("spmv_dense", n, ncols, k, dt), kind="spmv_dense", ops=2,
            flops=2.0 * n * ncols * k,
            hbm_bytes=float((n * ncols + ncols * k + n * k) * 4),
            mesh=mesh, dtype=dt,
        ),
        "gather": dict(
            sig=("spmv_gather", n, ncols, k, nnz, dt), kind="spmv_gather",
            ops=1, flops=2.0 * nnz * k,
            hbm_bytes=float(nnz * 8 + ncols * k * 4 + n * k * 4),
            mesh=mesh, dtype=dt,
        ),
    }


def _dispatch(A: DCSR_matrix, x2: jax.Array) -> jax.Array:
    n, ncols = A.shape
    k = x2.shape[1]

    if not autotune.enabled():
        # static dispatch, bit-for-bit: no table touch, no decisions
        return _ARM_RUNNERS[_static_arm()](A, x2)

    # the dense arm materializes the (n, ncols) operand: where measured
    # free HBM says it cannot fit, there is nothing to explore
    shards = A.comm.size if A.is_distributed() else 1
    if memtrack.would_fit(n * ncols * x2.dtype.itemsize // shards) is False:
        return _run_gather(A, x2)

    dt = str(x2.dtype)
    return autotune.run(
        _tuning_key(A, k, dt),
        {arm: partial(_ARM_RUNNERS[arm], A, x2) for arm in SPMV_ARMS},
        prior="gather", desc=f"spmv {n}x{ncols} nnz={A.nnz} k={k} {dt}",
        site="spmv", cost=_site_cost(A, k, dt),
    )


# ------------------------------------------------------------- public API


def matmul(A: DCSR_matrix, x, out: Optional[DNDarray] = None) -> DNDarray:
    """``A @ x`` for a DCSR matrix against a dense vector/matrix.  The
    result is a dense DNDarray (row-split when ``A`` is distributed);
    dispatch is the two-arm autotune consult described in the module
    docstring."""
    if not isinstance(A, DCSR_matrix):
        raise TypeError(f"A must be a DCSR_matrix, got {type(A)}")
    xv = x.larray if isinstance(x, DNDarray) else jnp.asarray(x)
    if xv.ndim not in (1, 2):
        raise ValueError(f"x needs to be 1-D or 2-D, but was {xv.ndim}-D")
    if xv.shape[0] != A.shape[1]:
        raise ValueError(
            f"dimension mismatch: A is {A.shape}, x leads with {xv.shape[0]}"
        )
    cdt = jnp.promote_types(A.dtype.jax_type(), xv.dtype)
    if not jnp.issubdtype(cdt, jnp.inexact):
        cdt = jnp.float32
    vec = xv.ndim == 1
    x2 = (xv[:, None] if vec else xv).astype(cdt)

    y = _dispatch(A, x2)
    if vec:
        y = y.reshape(-1)
    split = 0 if A.split == 0 else None
    result = DNDarray(
        y, tuple(y.shape), types.canonical_heat_type(y.dtype),
        None, A.device, A.comm,
    )
    result = _ensure_split(result, split)
    if out is not None:
        from ..core import sanitation

        sanitation.sanitize_out(out, result.shape, result.split, result.device)
        out.larray = result.larray.astype(out.dtype.jax_type())
        return out
    return result


# --------------------------------------------------- chain (Lanczos) consult


@lru_cache(maxsize=None)
def _matvec_gather_sharded(mesh, axis_name, rows_per, n):
    spec = P(axis_name, None)

    def local(data, idx, ptr, v):
        return _gather_block(data[0], idx[0], ptr[0], v[:, None], rows_per)[:, 0]

    sm = shard_map_unchecked(
        local, mesh,
        in_specs=(spec, spec, spec, P(None)), out_specs=P(axis_name),
    )

    def apply(operands, v):
        return sm(*operands, v)[:n]

    return apply


@lru_cache(maxsize=None)
def _matvec_gather_local(rows, n):
    def apply(operands, v):
        data, idx, ptr = operands
        return _gather_block(data, idx, ptr, v[:, None], rows)[:n, 0]

    return apply


def matvec_program(A: DCSR_matrix):
    """Jit-static ``(apply_fn, operands)`` for ``v ↦ A @ v`` inside a
    fused loop: always the ``gather`` arm — a fused solve never explores
    and never densifies, so the ``dense`` arm is deliberately
    unreachable here.  An unresolved or ``dense`` table entry is recorded
    as a ``note_prior`` so the tuning report shows the chain ran on the
    prior."""
    n = A.shape[0]
    if autotune.enabled():
        key = _tuning_key(A, 1, str(jnp.dtype(jnp.float32)))
        if autotune.winner(key) != "gather":
            autotune.note_prior(key, "gather", site="lanczos")
    if A.is_distributed():
        fn = _matvec_gather_sharded(
            A.comm.mesh, A.comm.split_axis, A.rows_per_shard, n
        )
        return fn, (A._data, A._indices, A._lindptr)
    fn = _matvec_gather_local(A.shape[0], n)
    return fn, (A._data[0], A._indices[0], A._lindptr[0])
