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

__all__ = [
    "KERNEL_ARMS",
    "LANE",
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
