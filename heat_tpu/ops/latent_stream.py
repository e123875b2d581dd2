"""Latent attention read in full: a decode step of a model with a low-rank
key/value cache (MLA) and no selection, where every query reads every
visible row of its session's cache.

A session's cache is what :mod:`heat_tpu.ops.latent_attention` describes: the
latent rows ``c_kv`` ``(batch, capacity, rank)`` and the rotary keys
``(batch, capacity / ROPE_PACK, ROPE_PACK * rope)``, two neighbouring
positions' keys a row.  The caller has folded ``W_uk`` into the queries
(``q_lat = W_uk^T q_nope``) and folds ``W_uv`` into what comes back, so that a
query reads ``rank + rope`` numbers a key and not every head's keys and
values (the absorbed form).

A step feeds ``Q`` positions a session (a model that drafts: the pending
token and its draft), session ``b``'s at ``positions[b] + j``; query ``j``
reads the slots ``<= positions[b] + j``.  Sessions lie at positions of their
own.

On a TPU the read is one Pallas kernel, ``ht_latent_stream`` (grid: sessions x
blocks of up to four key blocks of
:data:`~heat_tpu.ops.latent_attention.KEY_BLOCK` slots, each session's
position scalar-prefetched): a block past the session's last visible slot is
neither fetched nor folded, the causal mask of the step's positions is applied
in the key block they fall into alone, and a running softmax in float32 folds
the key blocks in VMEM.  A key block's rope rows are read as stored: each slot
is given its pair's whole row (in bfloat16 by writing a row's 16 bits into
both halves of a 32-bit word, which is that row twice; else by a product with
a 0/1 table), the query's rotary part comes once for each position a row
holds, in that position's lanes and zeros elsewhere, and one product gives
the rotary logits against every position of a slot's pair, of which a select
by the slot's parity keeps its own.  It asks for no VMEM beyond what every
kernel has: it runs in a decode program whose other fusions keep theirs
(PERF.md section 6).  Elsewhere, and for shapes the kernel does not take
(:func:`stream_form`), the same attention is a masked softmax over the whole
cache in ``jax.numpy``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_common import LANE
from ._pallas_common import mode as _mode
from .latent_attention import KEY_BLOCK, ROPE_PACK, rope_rows

__all__ = ["latent_stream_attention", "stream_form"]

_F32 = jnp.float32
_NEG_F32, _ZERO_F32 = np.float32(-np.inf), np.float32(0)


def _fetch_blocks(capacity: int) -> int:
    """Key blocks a grid step fetches: as many of four, three, two as divide
    the capacity's key blocks (a grid step costs a fixed overhead beside its
    fetch)."""
    blocks = capacity // min(KEY_BLOCK, capacity)
    return next(f for f in (4, 3, 2, 1) if blocks % f == 0)


def stream_form(capacity: int, rank: int, rope: int) -> str:
    """Which form :func:`latent_stream_attention` takes: ``stream_kernel``
    (the Pallas kernel ``ht_latent_stream``) where the Pallas mode is ``tpu``
    or ``interpret``, the capacity is whole key blocks and, on a TPU, the
    latent and rope rows are whole lane tiles; else ``masked_jnp``."""
    how = _mode()
    block = min(KEY_BLOCK, capacity)
    whole = capacity % block == 0 and block % (8 * ROPE_PACK) == 0
    if how == "interpret" and whole:
        return "stream_kernel"
    if how == "tpu" and whole and rank % LANE == 0 and (ROPE_PACK * rope) % LANE == 0:
        return "stream_kernel"
    return "masked_jnp"


def _masked(q_lat, q_pe, latent_cache, rope_cache, positions, scale):
    batch, queries, heads, _ = q_lat.shape
    capacity = latent_cache.shape[1]
    dtype = latent_cache.dtype
    slots = jnp.broadcast_to(jnp.arange(capacity, dtype=jnp.int32), (batch, capacity))
    k_pe = rope_rows(rope_cache, slots)
    logit = (jnp.einsum("bqhc,bsc->bqhs", q_lat.astype(dtype), latent_cache,
                        preferred_element_type=_F32)
             + jnp.einsum("bqhr,bsr->bqhs", q_pe.astype(dtype), k_pe,
                          preferred_element_type=_F32)) * scale
    last = positions[:, None] + jnp.arange(queries, dtype=jnp.int32)[None, :]
    seen = slots[:, None, None, :] <= last[:, :, None, None]
    prob = jax.nn.softmax(jnp.where(seen, logit, -jnp.inf), axis=-1)
    return jnp.einsum("bqhs,bsc->bqhc", prob.astype(dtype), latent_cache,
                      preferred_element_type=_F32)


def _rows_by_words(dtype) -> bool:
    """Whether :func:`_pair_rows` doubles a row within 32-bit words (two
    positions a rope row, each 16-bit bfloat16) rather than by a product."""
    return jnp.dtype(dtype) == jnp.bfloat16 and ROPE_PACK == 2


def _pair_rows(rope_rows, block: int):
    """Each slot's row of a key block's rope rows ``(block / ROPE_PACK,
    ROPE_PACK * rope)``: ``(block, ROPE_PACK * rope)`` in the cache's type,
    slot ``s`` holding row ``s // ROPE_PACK`` whole, both its positions'
    keys."""
    dtype = rope_rows.dtype
    if _rows_by_words(dtype):
        # bfloat16 widened to float32 is its 16 bits shifted up: the same bits in both halves of
        # a word are the row twice, the two neighbouring rows a word of bfloat16 holds
        bits = pltpu.bitcast(rope_rows.astype(_F32), jnp.uint32)
        return pltpu.bitcast(bits | (bits >> np.uint32(16)), dtype)
    shape = (block, rope_rows.shape[0])
    table = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) // ROPE_PACK
             == jax.lax.broadcasted_iota(jnp.int32, shape, 1))
    # a product with a 0/1 table: exact
    return jnp.dot(table.astype(dtype), rope_rows, preferred_element_type=_F32).astype(dtype)


def _stream_kernel(pos_ref, q_ref, qpe_ref, ckv_ref, rope_ref, out_ref, top_ref, total_ref, *,
                   heads: int, queries: int, scale: float, block: int):
    """One session's fold of one fetched block, ``block`` slots at a time:
    grid ``(sessions, fetched blocks)``, the blocks in ascending order.
    ``q_ref`` ``(queries * heads, rank)``, ``qpe_ref`` ``(ROPE_PACK * queries
    * heads, ROPE_PACK * rope)`` (copy ``p`` of a query's rotary part in the
    lanes of a rope row's position ``p``, zeros elsewhere), ``out_ref``
    ``(queries * heads, rank)`` holds the running value sums and, after the
    session's last visible block, the attention; ``top_ref``, ``total_ref``
    ``(queries * heads, 1)`` the running max and total.  A block's rotary
    logits are one product of ``qpe_ref`` with each slot's pair row
    (:func:`_pair_rows`), copy ``s % ROPE_PACK`` kept for slot ``s``."""
    b, j = pl.program_id(0), pl.program_id(1)
    fetched = ckv_ref.shape[0]
    rows = q_ref.shape[0]
    first = pos_ref[b]                               # the step's first position in this session
    last = first + np.int32(queries - 1)             # the slot the step's last query reads up to
    last_fetch = last // np.int32(fetched)

    @pl.when(j == 0)
    def _():
        top_ref[...] = jnp.full(top_ref.shape, _NEG_F32, _F32)
        total_ref[...] = jnp.zeros(total_ref.shape, _F32)
        out_ref[...] = jnp.zeros(out_ref.shape, _F32)

    def fold(i: int, masked: bool):
        start = j * np.int32(fetched) + np.int32(i * block)
        c_kv = ckv_ref[i * block:(i + 1) * block, :]
        half = block // ROPE_PACK
        dtype = c_kv.dtype
        logit = jax.lax.dot_general(q_ref[...], c_kv, (((1,), (1,)), ((), ())),
                                    preferred_element_type=_F32)
        k_pair = _pair_rows(rope_ref[i * half:(i + 1) * half, :], block)
        pair = jax.lax.dot_general(qpe_ref[...], k_pair, (((1,), (1,)), ((), ())),
                                   preferred_element_type=_F32)
        parity = jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1) % ROPE_PACK
        rope_logit = pair[:rows]
        for p in range(1, ROPE_PACK):
            rope_logit = jnp.where(parity == p, pair[p * rows:(p + 1) * rows], rope_logit)
        logit = (logit + rope_logit) * np.float32(scale)
        if masked:
            key = start + jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1)
            query = first + jax.lax.broadcasted_iota(jnp.int32, (rows, block), 0) // np.int32(heads)
            logit = jnp.where(key <= query, logit, _NEG_F32)
            # rows past the last visible slot may hold anything: their weight is 0 and so is
            # their share of the value sum
            visible = start + jax.lax.broadcasted_iota(jnp.int32, (block, 1), 0) <= last
            c_kv = jnp.where(visible, c_kv, jnp.zeros_like(c_kv))
        top = top_ref[...]
        new_top = jnp.maximum(top, jnp.max(logit, axis=1, keepdims=True))
        safe = jnp.where(new_top > _NEG_F32, new_top, _ZERO_F32)
        weight = jnp.exp(logit - safe)
        fade = jnp.exp(top - safe)
        total_ref[...] = total_ref[...] * fade + jnp.sum(weight, axis=1, keepdims=True)
        out_ref[...] = out_ref[...] * fade + jnp.dot(weight.astype(dtype), c_kv,
                                                     preferred_element_type=_F32)
        top_ref[...] = new_top

    for i in range(fetched // block):
        start = j * np.int32(fetched) + np.int32(i * block)
        # key blocks wholly before the step's first position: every query sees every slot; past
        # the last visible slot: nothing to fold
        pl.when(start + np.int32(block) <= first)(functools.partial(fold, i, False))
        pl.when((start + np.int32(block) > first) & (start <= last))(
            functools.partial(fold, i, True))

    @pl.when(j == last_fetch)
    def _():
        out_ref[...] = out_ref[...] / total_ref[...]


def _stream_pallas(q_lat, q_pe, latent_cache, rope_cache, positions, scale, *, interpret: bool):
    batch, queries, heads, rank = q_lat.shape
    capacity = latent_cache.shape[1]
    rope = q_pe.shape[-1]
    dtype = latent_cache.dtype
    block = min(KEY_BLOCK, capacity)
    fetched = block * _fetch_blocks(capacity)
    rows = queries * heads
    width = ROPE_PACK * rope
    q = q_lat.astype(dtype).reshape(batch, rows, rank)
    q_pe = q_pe.astype(dtype).reshape(batch, rows, rope)
    q_pe = jnp.concatenate([jnp.pad(q_pe, ((0, 0), (0, 0), (p * rope, width - (p + 1) * rope)))
                            for p in range(ROPE_PACK)], axis=1)
    positions = jnp.asarray(positions, jnp.int32)
    expand = 0 if _rows_by_words(dtype) else block // ROPE_PACK * width   # _pair_rows' product

    def key(b, j, pos):
        """the block a step holds: past the session's last visible block the pipeline keeps its
        last, so nothing more is fetched"""
        return jnp.minimum(j, (pos[b] + np.int32(queries - 1)) // np.int32(fetched))
    return pl.pallas_call(
        functools.partial(_stream_kernel, heads=heads, queries=queries, scale=float(scale),
                          block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch, capacity // fetched),
            in_specs=[
                pl.BlockSpec((None, rows, rank), lambda b, j, pos: (b, 0, 0)),
                pl.BlockSpec((None, ROPE_PACK * rows, width), lambda b, j, pos: (b, 0, 0)),
                pl.BlockSpec((None, fetched, rank), lambda b, j, pos: (b, key(b, j, pos), 0)),
                pl.BlockSpec((None, fetched // ROPE_PACK, width),
                             lambda b, j, pos: (b, key(b, j, pos), 0)),
            ],
            out_specs=pl.BlockSpec((None, rows, rank), lambda b, j, pos: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((rows, 1), _F32), pltpu.VMEM((rows, 1), _F32)],
        ),
        out_shape=jax.ShapeDtypeStruct((batch, rows, rank), _F32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        # the capacity, not the visible rows (the positions are traced): the bound the scheduler
        # plans with
        cost_estimate=pl.CostEstimate(
            flops=2 * batch * capacity * (rows * (2 * rank + ROPE_PACK * width) + expand),
            transcendentals=batch * rows * capacity,
            bytes_accessed=batch * (capacity * (rank + rope) * dtype.itemsize
                                    + rows * (rank + ROPE_PACK * width) * dtype.itemsize
                                    + 4 * rows * rank)),
        interpret=interpret,
        name="ht_latent_stream",
    )(positions, q, q_pe, latent_cache, rope_cache).reshape(batch, queries, heads, rank)


def latent_stream_attention(q_lat, q_pe, latent_cache, rope_cache, positions, scale: float):
    """Attention of ``Q`` queries a session over every visible row of its
    latent cache, in the latent space.  ``q_lat`` ``(batch, Q, heads, rank)``
    (the no-position part of the query with ``W_uk`` folded in), ``q_pe``
    ``(batch, Q, heads, rope)``, ``latent_cache`` ``(batch, capacity, rank)``,
    ``rope_cache`` ``(batch, capacity / ROPE_PACK, ROPE_PACK * rope)``,
    ``positions`` int32 ``(batch,)``: query ``j`` of session ``b`` stands at
    ``positions[b] + j`` and reads the slots up to it.  Returns float32
    ``(batch, Q, heads, rank)``: the softmax-weighted sum of the visible
    ``c_kv``.

    Products take the cache's type and accumulate in float32; the logits
    ``(q_lat . c_kv + q_pe . k_pe) * scale``, the softmax's max and total are
    float32, the weights are rounded to the cache's type for the value sum
    (the kernel rounds a block's weights before it divides by the total, the
    ``jax.numpy`` form after)."""
    how = stream_form(latent_cache.shape[1], latent_cache.shape[2], q_pe.shape[-1])
    if how == "stream_kernel":
        return _stream_pallas(q_lat, q_pe, latent_cache, rope_cache, positions, scale,
                              interpret=(_mode() == "interpret"))
    return _masked(q_lat, q_pe, latent_cache, rope_cache, jnp.asarray(positions, jnp.int32), scale)
