"""Attention of a few query rows over a long cache of keys and values.

Two entry points, both grouped (``G`` key/value groups, each read by its own
block of query rows) and both with float32 softmax and accumulation:

* :func:`masked_attention` — plain ``jax.numpy``, blockwise online softmax
  over the key axis.  Every query row and every key slot carries a position;
  a key is visible to a row when ``0 <= k_pos <= q_pos`` (and ``k_pos > q_pos
  - window`` under a sliding window).  Key slots may be in any order, so a
  rolling window kept as a ring buffer is read as it lies.  Prefill chunks and
  the window layers of a decode step go through it.
* :func:`decode_attention` — one query position against a cache whose slot
  ``t`` holds position ``t``, of which the first ``kv_len`` are visible: the
  read that a decode step of a long generation spends its time in.  On a TPU it
  is a Pallas kernel that streams the cache through VMEM once, in blocks of
  ``block`` keys, and never fetches a block past ``kv_len``; elsewhere it is
  :func:`masked_attention` (``HEAT_TPU_PALLAS=interpret`` runs the kernel on
  the CPU through the interpreter, as for the other kernels of this package).

Shapes: ``q`` is ``(batch, G, rows, Dk)``, ``k`` ``(batch, G, T, Dk)``, ``v``
``(batch, G, T, Dv)``; the result is ``(batch, G, rows, Dv)`` in float32.
Positions are shared by the batch: its sequences advance in lockstep.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_common import mode as _mode

__all__ = ["masked_attention", "decode_attention"]

# typed constants: a Python float inside a kernel becomes a float64 constant
# where x64 is on (the CPU tests), which Mosaic does not take
_NEG, _ZERO, _ONE = np.float32(-1e30), np.float32(0.0), np.float32(1.0)
_ROWS = 16  # query rows are padded to one bfloat16 sublane tile


def masked_attention(q, k, v, q_pos, k_pos, *, scale, window=None, kv_len=None,
                     block: int = 1024):
    """``softmax(q k^T * scale + mask) v`` with the mask from positions.

    ``q_pos``: ``(rows,)``, ``k_pos``: ``(T,)`` (a negative position marks an
    empty slot).  ``kv_len``, where given, says that no slot at or past it is
    visible, so the blocks beyond it are not read at all."""
    f32 = jnp.float32
    batch, groups, rows, _ = q.shape
    slots, dv = k.shape[2], v.shape[3]
    block = min(int(block), slots)
    if slots % block:
        raise ValueError(f"{slots} key slots do not divide into blocks of {block}")
    nblocks = slots // block
    q_pos = q_pos.astype(jnp.int32)
    k_pos = k_pos.astype(jnp.int32)

    def body(i, carry):
        m, l, acc = carry
        kb = jax.lax.dynamic_slice_in_dim(k, i * block, block, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(v, i * block, block, axis=2)
        kp = jax.lax.dynamic_slice_in_dim(k_pos, i * block, block)
        s = jnp.einsum("bgmd,bgtd->bgmt", q, kb, preferred_element_type=f32) * scale
        seen = (kp[None, :] <= q_pos[:, None]) & (kp[None, :] >= 0)
        if window is not None:
            seen &= kp[None, :] > q_pos[:, None] - window
        s = jnp.where(seen, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bgmt,bgte->bgme", p.astype(v.dtype), vb, preferred_element_type=f32)
        return m_new, l, acc

    init = (
        jnp.full((batch, groups, rows), _NEG, f32),
        jnp.zeros((batch, groups, rows), f32),
        jnp.zeros((batch, groups, rows, dv), f32),
    )
    if nblocks == 1:
        _, l, acc = body(0, init)
    else:
        trips = nblocks if kv_len is None else jnp.minimum(nblocks, -(-kv_len // block))
        _, l, acc = jax.lax.fori_loop(0, trips, body, init)
    return acc / jnp.where(l == 0.0, 1.0, l)[..., None]


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                   scale, block):
    t = pl.program_id(2)
    kv_len = len_ref[0]

    @pl.when(t == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t * np.int32(block) < kv_len)
    def _():
        s = jax.lax.dot_general(
            q_ref[...], k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * np.float32(scale)  # (rows, block)
        slot = t * np.int32(block) + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = slot < kv_len
        s = jnp.where(seen, s, _NEG)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), _ZERO)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...], preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(t == pl.num_programs(2) - np.int32(1))
    def _():
        l = l_ref[...]
        o_ref[...] = acc_ref[...] / jnp.where(l == _ZERO, _ONE, l)


def _decode_pallas(q, k, v, kv_len, *, scale, block, interpret):
    batch, groups, rows, dk = q.shape
    slots, dv = k.shape[2], v.shape[3]
    block = min(int(block), slots)
    if slots % block:
        raise ValueError(f"{slots} key slots do not divide into blocks of {block}")
    q = jnp.pad(q, ((0, 0), (0, 0), (0, _ROWS - rows), (0, 0)))

    def kv_block(b, g, t, len_ref):
        # past the last visible block the index stays put: nothing is fetched
        return b, g, jax.lax.min(t, jax.lax.div(len_ref[0] - np.int32(1), np.int32(block))), 0

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, groups, slots // block),
            in_specs=[
                pl.BlockSpec((None, None, _ROWS, dk), lambda b, g, t, n: (b, g, 0, 0)),
                pl.BlockSpec((None, None, block, dk), kv_block),
                pl.BlockSpec((None, None, block, dv), kv_block),
            ],
            out_specs=pl.BlockSpec((None, None, _ROWS, dv), lambda b, g, t, n: (b, g, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((_ROWS, 1), jnp.float32),
                pltpu.VMEM((_ROWS, 1), jnp.float32),
                pltpu.VMEM((_ROWS, dv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((batch, groups, _ROWS, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=2 * batch * groups * _ROWS * slots * (dk + dv),
            bytes_accessed=batch * groups * slots * (dk + dv) * k.dtype.itemsize,
            transcendentals=batch * groups * _ROWS * slots,
        ),
        interpret=interpret,
        name="ht_decode_attention",
    )(jnp.reshape(kv_len, (1,)).astype(jnp.int32), q, k, v)
    return out[:, :, :rows]


def decode_attention(q, k, v, kv_len, *, scale, block: int = 2048):
    """One query position (``rows`` <= 16 query rows a group) against the
    first ``kv_len`` slots of a position-ordered cache."""
    how = _mode()
    if how == "off":
        rows, slots = q.shape[2], k.shape[2]
        return masked_attention(
            q, k, v, jnp.broadcast_to(kv_len - 1, (rows,)), jnp.arange(slots),
            scale=scale, kv_len=kv_len, block=block)
    return _decode_pallas(q, k, v, kv_len, scale=scale, block=block,
                          interpret=(how == "interpret"))
