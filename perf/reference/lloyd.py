"""Exact Lloyd iterations and label judging, block by block.

``fit`` follows heat's dense ``KMeans.fit`` semantics: ``iters`` iterations
from explicit initial centres (no tolerance stop); an empty cluster keeps its
centre; ``inertia`` is the sum of squared distances of the LAST iteration's
assignment, to the centres as they were before that iteration's update (what
``KMeans.inertia_`` reports on this path); the labels a fit returns are the
assignment to the FINAL centres, which ``label_gap`` judges.

Everything is float32 with matmuls at ``highest``.  ``operands`` names the
type the two GEMMs' operands are rounded to first: ``float32`` is the
reference; ``bfloat16`` is what the configuration states (JAX's default TPU
matmul precision), a witness that should read like the program;
``float8_e4m3`` is the control, one precision lower, which has to come out
as not correct.  The rounding is ``lax.reduce_precision``: a pair of
converts is "excess precision" to XLA, which may drop it (it did, on the
chip, in the small per-block programs; my chip run, PR 24).  Rows are read
with ``dynamic_slice``: a reshape of the tall array into blocks would copy
all of it (XLA keeps it rows-minor on the TPU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perf.reference import block_rows

BLOCK_ROWS = 1 << 19
HI = jax.lax.Precision.HIGHEST

# exponent and mantissa bits of the types an operand can be rounded to
_BITS = {"float32": None, "bfloat16": (8, 7), "float8_e4m3": (4, 3)}


def _rounded(a, operands: str):
    a = a.astype(jnp.float32)
    bits = _BITS[operands]
    return a if bits is None else jax.lax.reduce_precision(a, *bits)


def _sq_dists(xb, c, operands: str):
    """(b, k) squared distances by the quadratic expansion, float32."""
    xb = xb.astype(jnp.float32)
    cross = jnp.matmul(_rounded(xb, operands), _rounded(c, operands).T, precision=HI)
    xsq = jnp.sum(xb * xb, axis=1, keepdims=True)
    csq = jnp.sum(c * c, axis=1)[None, :]
    return jnp.maximum(xsq + csq - 2 * cross, 0)


def _over_blocks(x, body, init):
    """``body(acc, xb, i)`` over the row blocks of ``x``."""
    rows = x.shape[0]
    block = block_rows(rows, BLOCK_ROWS)

    def step(i, acc):
        return body(acc, jax.lax.dynamic_slice_in_dim(x, i * block, block, axis=0), i)

    return jax.lax.fori_loop(0, rows // block, step, init), block


@functools.partial(jax.jit, static_argnames=("iters", "operands"))
def fit(x, init, iters: int, operands: str = "float32"):
    """``(centres, inertia)`` after ``iters`` Lloyd iterations from ``init``."""
    feats = x.shape[1]
    k = init.shape[0]

    def iteration(_, carry):
        c, _ = carry

        def body(acc, xb, _i):
            sums, counts, inertia = acc
            d2 = _sq_dists(xb, c, operands)
            onehot = jax.nn.one_hot(jnp.argmin(d2, axis=1), k, dtype=jnp.float32)
            sums = sums + jnp.matmul(onehot.T, _rounded(xb, operands), precision=HI)
            return (sums, counts + jnp.sum(onehot, axis=0),
                    inertia + jnp.sum(jnp.min(d2, axis=1)))

        zero = (jnp.zeros((k, feats), jnp.float32), jnp.zeros((k,), jnp.float32),
                jnp.zeros((), jnp.float32))
        (sums, counts, inertia), _ = _over_blocks(x, body, zero)
        new = jnp.where(counts[:, None] > 0, sums / jnp.maximum(counts, 1)[:, None], c)
        return new, inertia

    return jax.lax.fori_loop(
        0, iters, iteration, (init.astype(jnp.float32), jnp.zeros((), jnp.float32))
    )


@functools.partial(jax.jit, static_argnames=("operands",))
def assign(x, centres, operands: str = "float32"):
    """Labels ``(rows,)`` int32 of the nearest centre (used by the control)."""
    rows = x.shape[0]

    def body(labels, xb, i):
        lab = jnp.argmin(_sq_dists(xb, centres, operands), axis=1).astype(jnp.int32)
        return jax.lax.dynamic_update_slice_in_dim(labels, lab, i * lab.shape[0], axis=0)

    labels, _ = _over_blocks(x, body, jnp.zeros((rows,), jnp.int32))
    return labels


@jax.jit
def label_gap(x, labels, centres):
    """How far the given labels are from the best, by the reference's own
    float32 distances: the widest and the mean of
    ``(d2[label] - min d2) / mean(min d2)`` over the rows, and the share of
    rows whose label is not the reference's.  A label outside ``0..k-1``
    reads as infinitely far."""
    rows = x.shape[0]
    k = centres.shape[0]
    labels = labels.reshape(rows).astype(jnp.int32)

    def body(acc, xb, i):
        worst, total, best_total, differ = acc
        lb = jax.lax.dynamic_slice_in_dim(labels, i * xb.shape[0], xb.shape[0])
        d2 = _sq_dists(xb, centres, "float32")
        best = jnp.min(d2, axis=1)
        valid = (lb >= 0) & (lb < k)
        mine = jnp.take_along_axis(d2, jnp.clip(lb, 0, k - 1)[:, None], axis=1)[:, 0]
        gap = jnp.where(valid, mine - best, jnp.inf)
        return (jnp.maximum(worst, jnp.max(gap)), total + jnp.sum(gap),
                best_total + jnp.sum(best),
                differ + jnp.sum(lb != jnp.argmin(d2, axis=1)))

    zero = (jnp.zeros(()), jnp.zeros(()), jnp.zeros(()), jnp.zeros((), jnp.int32))
    (worst, total, best_total, differ), _ = _over_blocks(x, body, zero)
    scale = best_total / rows
    return {"widest": worst / scale, "mean": total / rows / scale,
            "differ": differ / rows}
