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
  ``block`` keys: the whole blocks below ``kv_len`` through the pipeline, and
  the block ``kv_len`` falls into as a copy of its first ``block // 8`` keys
  where those hold all that is visible (through the pipeline too where more
  of it is: a finer cut cost more in copies than it saved in bytes);
  nothing past that is fetched (:func:`keys_fetched` states the rule);
  elsewhere it is :func:`masked_attention` (``HEAT_TPU_PALLAS=interpret``
  runs the kernel on the CPU through the interpreter, as for the other
  kernels of this package).

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

__all__ = ["masked_attention", "decode_attention", "keys_fetched"]

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


def _piece(block: int) -> int:
    """Keys the copy of a barely visible last block brings: an eighth of a
    block, and whole bfloat16 tiles of 16 rows."""
    return min(block, max(_ROWS, block // 8))


def _tail_rule(nfull, rem, piece):
    """How the block that ``kv_len`` falls into comes, given the whole blocks
    below ``kv_len`` and the visible keys past them: ``(npipe, copied)``, the
    blocks the pipeline brings and whether a copy brings that block's first
    piece.  A piece where it holds all that is visible and the pipeline has a
    whole block to hide the copy behind; else the block, through the pipeline.
    Plain or traced integers."""
    copied = (nfull > 0) & (rem > 0) & (rem <= piece)
    return nfull + ((rem > 0) ^ copied), copied


def keys_fetched(kv_len: int, slots: int, block: int) -> int:
    """Key slots the decode kernel fetches from a stream of ``slots`` of which
    ``kv_len`` are visible: the pipeline's blocks and, where the last block
    holds no more than a piece of visible keys, that piece."""
    block = min(int(block), int(slots))
    piece = _piece(block)
    npipe, copied = _tail_rule(*divmod(int(kv_len), block), piece)
    return npipe * block + copied * piece


_PLAN = 5  # scalars a stream's plan opens with, before its table of blocks


def _stream_plan(kv_len, block, piece, steps):
    """``_tail_rule`` of a traced ``kv_len``, as the int32 vector the kernel
    and its index maps read: the first slot of the block ``kv_len`` falls
    into, whether a copy brings that block's first piece, the grid steps of a
    stream with nothing to fold, the step that closes its sum, the keys
    visible in the chunk that step masks (the piece, or the pipeline's last
    block), and then for each of the ``steps`` grid steps the block it holds
    in VMEM.  The pipeline brings one block at least (a pipeline fetches
    something).  The steps with nothing to fold come after the first block
    and hold the second: an empty step hides no fetch, and next to a stream's
    last block it would keep the next stream's second fetch waiting.  Worked
    out once a read, outside the kernel: a grid step evaluates its index maps
    four times, and a step with nothing to fold is as long as its scalar
    work, so an index map is one look into the table."""
    kv_len = jnp.asarray(kv_len, jnp.int32)
    nfull = kv_len // block
    npipe, copied = _tail_rule(nfull, kv_len - nfull * block, piece)
    npipe = jnp.maximum(npipe, 1)
    empty = steps - npipe
    t = jnp.arange(steps, dtype=jnp.int32)
    at = jnp.where(t == 0, 0, jnp.minimum(jnp.maximum(t - empty, 1), npipe - 1))
    head = jnp.stack([nfull * block, copied, empty, jnp.where(npipe > 1, steps - 1, 0),
                      kv_len - jnp.where(copied, nfull, npipe - 1) * block])
    return jnp.concatenate([head, at]).astype(jnp.int32)


def _decode_kernel(plan_ref, q_ref, k_ref, v_ref, k_hbm, v_hbm, o_ref,
                   m_ref, l_ref, acc_ref, k_tail, v_tail, sems, *, scale, piece):
    b, g, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    tail0, copied, seen = plan_ref[0], plan_ref[1] > 0, plan_ref[4]

    def tail_copies():
        rows = pl.ds(pl.multiple_of(tail0, piece), piece)
        return (pltpu.make_async_copy(k_hbm.at[b, g, rows], k_tail, sems.at[0]),
                pltpu.make_async_copy(v_hbm.at[b, g, rows], v_tail, sems.at[1]))

    def fold(*chunks):
        """One step of the online softmax over chunks ``(k, v, visible)`` of
        keys and values, of which the first ``visible`` can be seen (all of
        them where it is None)."""
        scored = []
        m_prev = m_new = m_ref[...]
        for k, v, visible in chunks:
            s = jax.lax.dot_general(
                q_ref[...], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * np.float32(scale)  # (rows, keys)
            seen = None
            if visible is not None:
                seen = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < visible
                s = jnp.where(seen, s, _NEG)
            m_new = jnp.maximum(m_new, jnp.max(s, axis=1, keepdims=True))
            scored.append((s, v, seen))
        corr = jnp.exp(m_prev - m_new)
        l, acc = l_ref[...] * corr, acc_ref[...] * corr
        for s, v, seen in scored:
            p = jnp.exp(s - m_new)
            if seen is not None:
                p = jnp.where(seen, p, _ZERO)
            l = l + jnp.sum(p, axis=1, keepdims=True)
            acc = acc + jnp.dot(p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...], l_ref[...], acc_ref[...] = m_new, l, acc

    @pl.when(t == 0)
    def _():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

        @pl.when(copied)
        def _():  # started here, the piece lands while the whole blocks stream
            for copy in tail_copies():
                copy.start()

    @pl.when((t == 0) | (t > plan_ref[2]))  # a step with a block to fold
    def _():
        closing = t == plan_ref[3]

        @pl.when(~closing)
        def _():
            fold((k_ref[...], v_ref[...], None))

        @pl.when(closing & ~copied)
        def _():
            fold((k_ref[...], v_ref[...], seen))

        @pl.when(closing & copied)
        def _():
            # the piece in one sum with the last whole block: a sum of its own
            # would be a second chain of products and reductions nothing hides
            for copy in tail_copies():
                copy.wait()
            fold((k_ref[...], v_ref[...], None), (k_tail[...], v_tail[...], seen))

        @pl.when(closing)
        def _():
            l = l_ref[...]
            o_ref[...] = acc_ref[...] / jnp.where(l == _ZERO, _ONE, l)


def _decode_pallas(q, k, v, kv_len, *, scale, block, interpret):
    batch, groups, rows, dk = q.shape
    slots, dv = k.shape[2], v.shape[3]
    block = min(int(block), slots)
    if slots % block:
        raise ValueError(f"{slots} key slots do not divide into blocks of {block}")
    piece = _piece(block)
    if block % piece:
        raise ValueError(f"blocks of {block} keys do not divide into pieces of {piece}")
    steps = slots // block
    q = jnp.pad(q, ((0, 0), (0, 0), (0, _ROWS - rows), (0, 0)))

    def kv_block(b, g, t, plan_ref):
        return b, g, plan_ref[_PLAN + t], 0

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, piece=piece),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, groups, steps),
            in_specs=[
                pl.BlockSpec((None, None, _ROWS, dk), lambda b, g, t, n: (b, g, 0, 0)),
                pl.BlockSpec((None, None, block, dk), kv_block),
                pl.BlockSpec((None, None, block, dv), kv_block),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, None, _ROWS, dv), lambda b, g, t, n: (b, g, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((_ROWS, 1), jnp.float32),
                pltpu.VMEM((_ROWS, 1), jnp.float32),
                pltpu.VMEM((_ROWS, dv), jnp.float32),
                pltpu.VMEM((piece, dk), k.dtype),
                pltpu.VMEM((piece, dv), v.dtype),
                pltpu.SemaphoreType.DMA((2,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((batch, groups, _ROWS, dv), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        # the capacity, not the visible keys: kv_len is traced, and this is
        # the bound the scheduler plans with, not a measurement
        cost_estimate=pl.CostEstimate(
            flops=2 * batch * groups * _ROWS * slots * (dk + dv),
            bytes_accessed=batch * groups * slots * (dk + dv) * k.dtype.itemsize,
            transcendentals=batch * groups * _ROWS * slots,
        ),
        interpret=interpret,
        name="ht_decode_attention",
    )(_stream_plan(kv_len, block, piece, steps), q, k, v, k, v)
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
