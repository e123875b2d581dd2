"""Fused pairwise squared-Euclidean distance — KMeans' hot loop on the MXU.

The reference computes ``cdist`` via torch's kernel inside a hand-written MPI
ring (heat/spatial/distance.py:16-134, ``_quadratic_expand`` fast path).  On
TPU the ring is GSPMD's problem (see heat_tpu/spatial/distance.py); this
kernel fuses the quadratic expansion  ``|x|² + |y|² − 2·x·yᵀ``  so the norm
terms ride along with the MXU matmul instead of separate HBM passes, and the
sqrt happens before the tile leaves VMEM.

Dispatch: Pallas on TPU, jnp expansion otherwise,
``HEAT_TPU_PALLAS=interpret`` for interpreter-mode testing.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_common import mode as _mode
from ._pallas_common import pad_to as _pad_to

__all__ = ["cdist"]


def _cdist_kernel(x_ref, y_ref, o_ref, acc_ref, xn_ref, yn_ref, *, p_root: bool):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        xn_ref[:] = jnp.zeros_like(xn_ref)
        yn_ref[:] = jnp.zeros_like(yn_ref)

    x = x_ref[:].astype(jnp.float32)
    y = y_ref[:].astype(jnp.float32)
    acc_ref[:] += jax.lax.dot_general(
        x, y, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    xn_ref[:] += jnp.sum(x * x, axis=1, keepdims=True)
    yn_ref[:] += jnp.sum(y * y, axis=1, keepdims=True).T

    @pl.when(kk == pl.num_programs(2) - 1)
    def _():
        d2 = jnp.maximum(xn_ref[:] + yn_ref[:] - 2.0 * acc_ref[:], 0.0)
        o_ref[:] = (jnp.sqrt(d2) if p_root else d2).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sqrt", "block", "interpret"))
def _cdist_pallas(x, y, sqrt=True, block=256, interpret=False):
    m, d = x.shape
    n, _ = y.shape
    bm = min(block, max(8, m))
    bn = min(block, max(128, n))
    bk = min(512, max(128, d))
    x = _pad_to(x, (bm, bk))
    y = _pad_to(y, (bn, bk))
    mp, dp = x.shape
    np_, _ = y.shape
    out = pl.pallas_call(
        functools.partial(_cdist_kernel, p_root=sqrt),
        grid=(mp // bm, np_ // bn, dp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bn, bk), lambda i, j, kk: (j, kk)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((bm, 1), jnp.float32),
            pltpu.VMEM((1, bn), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * mp * np_ * dp,
            bytes_accessed=(mp * dp + np_ * dp + mp * np_) * 4,
            transcendentals=mp * np_,
        ),
        interpret=interpret,
    )(x, y)
    return out[:m, :n]


@jax.named_scope("ht.cdist")
def cdist(x: jax.Array, y: jax.Array, *, sqrt: bool = True) -> jax.Array:
    """Pairwise (squared if ``sqrt=False``) Euclidean distances, (m,d)×(n,d)→(m,n)."""
    if x.ndim != 2 or y.ndim != 2:
        raise ValueError("cdist expects 2-D inputs")
    mode = _mode()
    # the Pallas kernel pads m→8 and n→128 lane multiples; for skinny
    # operands (e.g. KMeans' n=k=8 centroids) the padded (m, 128) output
    # would dominate HBM (10 GB at m=2e7), so XLA's fused expansion wins.
    # An explicit HEAT_TPU_PALLAS=interpret/tpu override still reaches the
    # kernel (the kernel's own tests depend on that).
    forced = os.environ.get("HEAT_TPU_PALLAS", "") in ("interpret", "tpu")
    if not forced and (x.shape[0] < 8 or y.shape[0] < 128):
        mode = "off"
    if mode == "off":
        # never materialize an f32 copy of a half-precision operand — either
        # side can be the huge one (at 1e8x64 bf16 the cast alone is 25.6 GB).
        # The norms' casts fuse into their reductions; the cross term runs
        # the MXU on a common native dtype with an f32 accumulator.
        xsq = jnp.sum(jnp.square(x.astype(jnp.float32)), axis=1, keepdims=True)
        ysq = jnp.sum(jnp.square(y.astype(jnp.float32)), axis=1)[None, :]
        if x.dtype == y.dtype == jnp.float32:
            prod = x @ y.T
        else:
            # half/mixed dtypes: dot_general reads each operand in its
            # native dtype and accumulates in f32 — no array-sized upcast
            # copy of the big operand, and a higher-precision small
            # operand (f32 centroids against bf16 data) is never downcast
            prod = jax.lax.dot_general(
                x, y, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        d2 = jnp.maximum(xsq + ysq - 2.0 * prod, 0.0)
        return jnp.sqrt(d2) if sqrt else d2
    return _cdist_pallas(x, y, sqrt=sqrt, interpret=(mode == "interpret"))
