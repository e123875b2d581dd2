"""What makes ``(Q, R)`` a reduced QR of ``A``, measured in float32 at
``highest`` precision in row blocks; and a plain CholeskyQR2 whose only use
is to stand in the program's place, one precision lower, as the control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference import block_rows
from jax.sharding import NamedSharding, PartitionSpec as P

BLOCK_ROWS = 1 << 16
BLOCK_BYTES = 1 << 26
HI = jax.lax.Precision.HIGHEST


def _properties_local(a, q, r, axis):
    rows, n = q.shape
    # blocks of at most 64 MiB: a block's Q, A, product and difference sit
    # beside the 8 GB of A and Q that the judge reads
    block = block_rows(rows, max(8, min(BLOCK_ROWS, BLOCK_BYTES // (4 * n))))

    def body(i, acc):
        gram, res2, a2 = acc
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=0).astype(jnp.float32)
        ab = jax.lax.dynamic_slice_in_dim(a, i * block, block, axis=0).astype(jnp.float32)
        gram = gram + jnp.matmul(qb.T, qb, precision=HI)
        diff = ab - jnp.matmul(qb, r, precision=HI)
        return gram, res2 + jnp.sum(diff * diff), a2 + jnp.sum(ab * ab)

    zero = (jnp.zeros((n, n), jnp.float32), jnp.zeros(()), jnp.zeros(()))
    acc = jax.lax.fori_loop(0, rows // block, body, zero)
    return jax.lax.psum(acc, axis) if axis is not None else acc


def properties(a, q, r):
    """``orth``: max |QtQ - I|.  ``resid``: |A - QR|_F / |A|_F over all rows.
    ``r_lower``: max |R| below the diagonal.  ``r_diag_nonpos``: how many
    diagonal entries of R are not positive.  Row-sharded ``a`` and ``q`` are
    read where they lie, each device its rows, and the sums are added up."""
    # one R for every device: what a reader of the replicated result sees.
    # (Each device judging its rows by its own copy would pass a run in which
    # the chips never exchanged their factors: perf/tests/test_faults.py.)
    r = jnp.asarray(np.asarray(r))
    sharding = q.sharding
    axis = sharding.spec[0] if isinstance(sharding, NamedSharding) and len(sharding.spec) else None
    mesh = sharding.mesh if axis is not None else None
    return _properties(a, q, r, mesh, axis)


@functools.partial(jax.jit, static_argnames=("mesh", "axis"))
def _properties(a, q, r, mesh, axis):
    n = q.shape[1]
    r = r.astype(jnp.float32)
    if axis is None:
        gram, res2, a2 = _properties_local(a, q, r, None)
    else:
        rows_spec = P(axis, None)
        gram, res2, a2 = jax.shard_map(
            functools.partial(_properties_local, axis=axis), mesh=mesh,
            in_specs=(rows_spec, rows_spec, P()), out_specs=P(), check_vma=False,
        )(a, q, r)
    return {
        "orth": jnp.max(jnp.abs(gram - jnp.eye(n, dtype=jnp.float32))),
        "resid": jnp.sqrt(res2 / a2),
        "r_lower": jnp.max(jnp.abs(jnp.tril(r, -1))),
        "r_diag_nonpos": jnp.sum(jnp.diagonal(r) <= 0),
    }


def _split(x):
    hi = jax.lax.reduce_precision(x, 8, 7)
    return hi, jax.lax.reduce_precision(x - hi, 8, 7)


def _matmul(a, b, precision: str):
    """``a @ b`` at ``highest``, or at ``high`` written out: each operand as
    two bfloat16 terms, three of the four products kept (what the TPU's
    three-pass float32 matmul does), the same on every backend."""
    if precision == "highest":
        return jnp.matmul(a, b, precision=HI)
    (a_hi, a_lo), (b_hi, b_lo) = _split(a), _split(b)
    return (jnp.matmul(a_hi, b_hi, precision=HI) + jnp.matmul(a_hi, b_lo, precision=HI)
            + jnp.matmul(a_lo, b_hi, precision=HI))


@functools.partial(jax.jit, static_argnames=("precision",))
def cholesky_qr2(a, precision: str = "highest"):
    """Plain CholeskyQR2 (Gram, Cholesky, apply the inverse; twice)."""
    eye = jnp.eye(a.shape[1], dtype=a.dtype)

    def once(x):
        low = jnp.linalg.cholesky(_matmul(x.T, x, precision))
        rinv = jax.lax.linalg.triangular_solve(low, eye, lower=True, left_side=True).T
        return _matmul(x, rinv, precision), low.T

    q1, r1 = once(a)
    q, r2 = once(q1)
    return q, _matmul(r2, r1, precision)
