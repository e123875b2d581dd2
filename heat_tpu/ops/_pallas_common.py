"""Shared Pallas plumbing for every kernel in :mod:`heat_tpu.ops`.

The ``HEAT_TPU_PALLAS`` mode selection and the lane/sublane pad helpers
that the four kernels (cdist, attention, fused CholeskyQR2 panel, fused
lasso sweep) share.  Every kernel here has been compiled by Mosaic on a
v5e (``chip_smoke.py``); three that could not be (a GEMM with no caller,
a narrow-minor repack whose shape cast Mosaic refuses, an ELL SpMV whose
flat gather has no lowering) were deleted rather than wrapped.

Mode contract (unchanged from PR 4): ``HEAT_TPU_PALLAS`` forces
``interpret`` / ``tpu`` / ``off``; unset picks ``tpu`` on a TPU backend
and ``off`` elsewhere (tests run the kernels on CPU through the Pallas
interpreter by exporting ``HEAT_TPU_PALLAS=interpret``).

How a kernel enters the library: one that replaces a lowering on every
input it accepts is chosen by :func:`mode` alone (cdist, attention,
decode attention, the Lloyd pass); one that wins on some geometries only
is an arm (:data:`KERNEL_ARMS`) handed to ``autotune.run``, which measures both
(qr_panel, lasso_sweep); no kernel gets an environment switch of its own.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "KERNEL_ARMS",
    "LANE",
    "device_copy",
    "mode",
    "pad_to",
    "sublane",
]

# VPU/MXU lane width: the minor-most tile dimension on every TPU
# generation this library targets (pallas_guide: min tile (8,128) f32).
LANE = 128

# round 15: Pallas kernels join the explore set as per-site arm pairs —
# "classic" is whatever the site dispatched before this round (ROADMAP
# item 2 predicted exactly this extension); both are measured by the same
# explore/exploit machinery as ring-vs-GSPMD
KERNEL_ARMS = ("classic", "kernel")


def mode() -> str:
    """Pallas execution mode: ``tpu`` | ``interpret`` | ``off``."""
    forced = os.environ.get("HEAT_TPU_PALLAS", "")
    if forced in ("interpret", "tpu", "off"):
        return forced
    return "tpu" if jax.default_backend() == "tpu" else "off"


def sublane(dtype) -> int:
    """Minimum second-minor tile extent for ``dtype`` (pallas_guide:
    (8,128) f32, (16,128) bf16, (32,128) int8/fp8)."""
    dt = jnp.dtype(dtype)
    if dt.itemsize == 2:
        return 16
    if dt.itemsize == 1:
        return 32
    return 8


def pad_to(x: jax.Array, mults) -> jax.Array:
    """Zero-pad each dim of ``x`` up to a multiple of ``mults[d]``."""
    pads = [(0, (-d) % m) for d, m in zip(x.shape, mults)]
    if any(p[1] for p in pads):
        x = jnp.pad(x, pads)
    return x


def _copy_kernel(src_ref, *refs):
    dst_ref, sem = refs[-2:]  # with ``into`` its ref comes between: the output is its buffer
    copy = pltpu.make_async_copy(src_ref, dst_ref, sem)
    copy.start()
    copy.wait()


def device_copy(src: jax.Array, into: jax.Array | None = None) -> jax.Array:
    """A copy of ``src`` made where it lies, by one DMA from HBM to HBM (the
    kernel ``ht_device_copy``; ``jnp.copy`` where :func:`mode` is ``off``).
    ``into``: an array of the same shape and type that the caller gives up;
    the copy is written into its buffer (the kernel's output aliases it), so
    that copying a large state back over itself never holds a third copy.
    Unlike the copy XLA inserts for ``jnp.copy`` of a program's argument, the
    kernel carries the ``jax.named_scope`` it was called under."""
    how = mode()
    if how == "off":
        return jnp.copy(src)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    operands = (src,) if into is None else (src, into)
    return pl.pallas_call(
        _copy_kernel,
        in_specs=[anywhere] * len(operands),
        out_specs=anywhere,
        out_shape=jax.ShapeDtypeStruct(src.shape, src.dtype),
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        input_output_aliases={} if into is None else {1: 0},
        cost_estimate=pl.CostEstimate(flops=0, transcendentals=0,
                                      bytes_accessed=2 * src.size * src.dtype.itemsize),
        interpret=(how == "interpret"),
        name="ht_device_copy",
    )(*operands)
