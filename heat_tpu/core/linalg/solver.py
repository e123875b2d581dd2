"""Iterative solvers (reference: heat/core/linalg/solver.py, 274 LoC).

``cg`` (:14) and ``lanczos`` (:69) are built from distributed
matmuls/reductions exactly as in the reference, but each full iteration
loop is one on-device XLA program (``lax.while_loop``/``lax.fori_loop``):
the reference's per-iteration scalar readbacks (alpha/beta/rsnew ``.item()``
broadcasts) would stall the device every iteration.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import types
from ..dndarray import DNDarray

__all__ = ["cg", "lanczos"]


@jax.jit
def _cg_loop(A, b, x0, tol, max_iter):
    """CG iterations fused into one XLA program."""

    def cond(state):
        _, _, _, rsold, it = state
        return jnp.logical_and(it < max_iter, rsold > tol * tol)

    def body(state):
        x, r, p, rsold, it = state
        Ap = A @ p
        alpha = rsold / jnp.dot(p, Ap)
        x = x + alpha * p
        r = r - alpha * Ap
        rsnew = jnp.dot(r, r)
        p = r + (rsnew / rsold) * p
        return x, r, p, rsnew, it + 1

    r0 = b - A @ x0
    init = (x0, r0, r0, jnp.dot(r0, r0), 0)
    x, _, _, _, n_iter = jax.lax.while_loop(cond, body, init)
    return x, n_iter


def cg(A: DNDarray, b: DNDarray, x0: DNDarray, out: Optional[DNDarray] = None) -> DNDarray:
    """Conjugate gradients for SPD systems (reference: solver.py:14)."""
    if A.ndim != 2 or b.ndim != 1 or x0.ndim != 1:
        raise RuntimeError("A needs to be 2-D, b and x0 1-D")
    dtype = jnp.promote_types(
        jnp.promote_types(A.larray.dtype, b.larray.dtype), x0.larray.dtype
    )
    if not jnp.issubdtype(dtype, jnp.inexact):
        dtype = jnp.float32
    arr = A.larray.astype(dtype)
    bv = b.larray.astype(dtype)
    xv = x0.larray.astype(dtype)
    x, _ = _cg_loop(arr, bv, xv, jnp.asarray(1e-10, dtype), len(b))
    x_ht = DNDarray(
        x, tuple(x.shape), types.canonical_heat_type(x.dtype),
        b.split, b.device, b.comm,
    )
    from ..dndarray import _ensure_split

    x_ht = _ensure_split(x_ht, b.split)
    if out is not None:
        out.larray = x_ht.larray
        return out
    return x_ht


def _dense_apply(operands, v):
    """The dense operator: ``operands`` is the 1-tuple ``(A,)``."""
    return operands[0] @ v


@functools.partial(jax.jit, static_argnames=("m", "apply_fn"))
def _lanczos_loop_op(operands, v, m: int, apply_fn):
    """Three-term Lanczos recurrence with full reorthogonalization, fused
    into one XLA program.  The basis lives as a row-stacked (m, n) array so
    reorthogonalization is two matvecs against the filled prefix (masked by
    iteration index) instead of a Python loop over saved vectors.

    The operator is abstract: ``apply_fn(operands, v)`` computes ``A @ v``
    — the dense path passes ``(A,)`` with :func:`_dense_apply` (bit-for-bit
    the pre-refactor program), the sparse path passes the CSR/ELL slabs
    with the arm `sparse.matmul.matvec_program` consulted from the tuning
    table.  ``apply_fn`` must be a stable hashable (the lru-cached program
    factories guarantee it) since it keys this jit."""
    n = v.shape[0]
    dtype = v.dtype
    rows = jnp.arange(m)

    w0 = apply_fn(operands, v)
    a0 = jnp.dot(w0, v)
    state = (
        jnp.zeros((m, n), dtype).at[0].set(v),  # basis V (rows)
        jnp.zeros((m,), dtype).at[0].set(a0),  # diagonal of T
        jnp.zeros((max(m - 1, 1),), dtype),  # off-diagonal of T
        w0 - a0 * v,  # residual w
    )

    def body(i, state):
        V, alphas, betas, w = state
        beta = jnp.linalg.norm(w)
        breakdown = beta < 1e-10
        # happy breakdown: restart from a fixed vector; the shared
        # reorthogonalization below projects out the existing basis either way
        cand = jnp.where(
            breakdown, jnp.ones((n,), dtype) / jnp.sqrt(n), w / jnp.maximum(beta, 1e-30)
        )
        prefix = (rows < i)[:, None].astype(dtype)
        cand = cand - (V * prefix).T @ (V @ cand * (rows < i))
        v_next = cand / jnp.maximum(jnp.linalg.norm(cand), 1e-30)
        w_new = apply_fn(operands, v_next)
        alpha = jnp.dot(w_new, v_next)
        w_new = w_new - alpha * v_next - jnp.where(breakdown, 0.0, beta) * V[i - 1]
        return (
            V.at[i].set(v_next),
            alphas.at[i].set(alpha),
            betas.at[i - 1].set(beta),
            w_new,
        )

    V, alphas, betas, _ = jax.lax.fori_loop(1, m, body, state)
    return V.T, alphas, betas[: m - 1]


def _lanczos_loop(arr, v, m: int):
    """Dense-operand compatibility wrapper over :func:`_lanczos_loop_op`."""
    return _lanczos_loop_op((arr,), v, m, _dense_apply)


def lanczos(
    A,
    m: int,
    v0: Optional[DNDarray] = None,
    V_out: Optional[DNDarray] = None,
    T_out: Optional[DNDarray] = None,
) -> Tuple[DNDarray, DNDarray]:
    """Lanczos tridiagonalization: A ≈ V T V^T with V (n×m) orthonormal and T
    (m×m) tridiagonal (reference: solver.py:69). Basis of spectral clustering.

    ``A`` is a dense DNDarray or a ``sparse.DCSR_matrix`` — the sparse
    operand runs the whole recurrence over the tuned SpMV program
    (``sparse.matmul.matvec_program``): gather or Pallas-kernel matvecs
    inside ONE fused loop, zero densifications.
    """
    if A.ndim != 2 or A.shape[0] != A.shape[1]:
        raise RuntimeError(f"A needs to be a square matrix, got {A.shape}")
    n = A.shape[0]
    m = int(m)

    # lazy: core.linalg must not import the sparse package at module load
    from ...sparse.dcsr_matrix import DCSR_matrix

    sparse_op = isinstance(A, DCSR_matrix)
    if sparse_op:
        from ...sparse.matmul import matvec_program

        dtype = A.dtype.jax_type()
        if not jnp.issubdtype(dtype, jnp.inexact):
            dtype = jnp.float32
        apply_fn, operands = matvec_program(A)
    else:
        arr = A.larray
        if not jnp.issubdtype(arr.dtype, jnp.inexact):
            arr = arr.astype(jnp.float32)
        dtype = arr.dtype
        apply_fn, operands = _dense_apply, (arr,)

    if v0 is None:
        from .. import random as ht_random

        v = ht_random.rand(n, split=A.split, comm=A.comm, device=A.device).larray.astype(dtype)
        v = v / jnp.linalg.norm(v)
    else:
        v = v0.larray.astype(dtype)
        v = v / jnp.linalg.norm(v)

    Vm, T_alpha, T_beta = _lanczos_loop_op(operands, v, m, apply_fn)
    T = jnp.diag(jnp.asarray(T_alpha, dtype=dtype))
    if m > 1:
        off = jnp.asarray(T_beta, dtype=dtype)
        T = T + jnp.diag(off, 1) + jnp.diag(off, -1)

    V_ht = DNDarray(Vm, tuple(Vm.shape), types.canonical_heat_type(Vm.dtype), A.split, A.device, A.comm)
    from ..dndarray import _ensure_split

    V_ht = _ensure_split(V_ht, A.split)
    T_ht = DNDarray(T, tuple(T.shape), types.canonical_heat_type(T.dtype), None, A.device, A.comm)
    if V_out is not None and T_out is not None:
        V_out.larray = V_ht.larray
        T_out.larray = T_ht.larray
        return V_out, T_out
    return V_ht, T_ht
