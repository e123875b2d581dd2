"""Latent attention read through a learned sparse selection: what a decode
step and a prefill chunk of a model with a low-rank key/value cache and a
lightning indexer compute against their caches.

A token leaves three things in a layer's cache: the **latent row** ``c_kv`` (the
normed low-rank projection, read by every head as key *and* value), the
**rotary key** ``k_pe`` shared by all heads, and the **index key** ``k^I``.
The rotary keys of two neighbouring positions share one cache row
(:data:`ROPE_PACK`: at the published 64 lanes a row is one whole lane tile;
kept beside ``c_kv`` in a row of 576 the array's minor dimension would be no
whole number of tiles, and the TPU's default layout for such an array puts the
positions minor, which a row gather cannot read).  A query position ``t``
reads only the ``k`` positions ``s <= t`` of largest

    I_ts = sum_j w_tj * relu(q^I_tj . k^I_s)          (j over the index heads)

**A decode step** (one query a session):

- :func:`sparse_select` scores every visible slot of the index cache and takes
  the exact top ``k``: ``(batch, k)`` slot numbers, ``-1`` where fewer than
  ``k`` are visible;
- :func:`latent_decode_attention` attends in the latent space over those rows:
  the caller has folded ``W_uk`` into the queries (``q_lat = W_uk^T q_nope``)
  and folds ``W_uv`` into what comes back, so that a step reads ``rank + rope``
  numbers a chosen key and not every head's keys and values.

**A prefill chunk** (many queries of one session): :func:`index_scores_chunk`
fills the chunk's scores against the visible prefix block by block,
:func:`kth_largest` finds each row's cut (the selection is then the mask
``score >= cut``), and :func:`latent_prefill_attention` is the published
per-head form, blockwise with a running softmax: keys and values of a block of
positions are computed from its latent rows when the block is read, so no
``(chunk, context)`` array of all heads is ever alive.

All of it is ``jax.numpy`` here; products take their operands in the caches'
type and accumulate in float32.  Kernels, where this module gains them, are
entered by :func:`~heat_tpu.ops._pallas_common.mode` alone, as
``ops/decode_attention.py`` and ``ops/power_retention.py`` are: on every input
they accept they replace a lowering that moves more bytes, so there is no
classic body that could win somewhere and no autotune arm.  What XLA makes of
``top_k`` and of the row gather at a decode cell's shapes is in PERF.md
section 6 (PR 34).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["KEY_BLOCK", "ROPE_PACK", "index_scores", "index_scores_chunk", "kth_largest",
           "latent_decode_attention", "latent_prefill_attention", "rope_rows", "sparse_select"]

_F32 = jnp.float32
_NEG = -jnp.inf

# cache slots a prefill loop reads at once; a session's capacity is whole blocks
KEY_BLOCK = 256
# positions whose rotary keys share a row of the rope cache
ROPE_PACK = 2


def rope_rows(rope_cache, slots):
    """The rotary keys at ``slots`` ``(batch, k)`` of a rope cache ``(batch,
    capacity / ROPE_PACK, ROPE_PACK * rope)``: ``(batch, k, rope)``."""
    rows = jnp.take_along_axis(rope_cache, (slots // ROPE_PACK)[:, :, None], axis=1)
    rows = rows.reshape(rows.shape[:2] + (ROPE_PACK, -1))
    # which of the row's positions: a select, not a second gather (0.24 ms a layer and step
    # at 16 x 2,048 rows on a v5e, more than the gather of the rows themselves)
    which = (slots % ROPE_PACK)[:, :, None]
    out = rows[:, :, 0]
    for i in range(1, ROPE_PACK):
        out = jnp.where(which == i, rows[:, :, i], out)
    return out


# ----------------------------------------------------------------- decode step

def index_scores(q_idx, w, k_idx_cache, kv_len):
    """``I`` of one query a session against every slot: ``q_idx`` ``(batch,
    heads, width)``, ``w`` ``(batch, heads)``, ``k_idx_cache`` ``(batch,
    capacity, width)``.  Returns float32 ``(batch, capacity)``, ``-inf`` from
    slot ``kv_len`` on."""
    hit = jnp.einsum("bhd,bsd->bhs", q_idx.astype(k_idx_cache.dtype), k_idx_cache,
                     preferred_element_type=_F32)
    score = jnp.einsum("bhs,bh->bs", jax.nn.relu(hit), w.astype(_F32))
    return jnp.where(jnp.arange(k_idx_cache.shape[1])[None, :] < kv_len, score, _NEG)


def sparse_select(q_idx, w, k_idx_cache, kv_len, k: int):
    """The ``k`` visible slots of largest index score for each session's one
    query position, exact: int32 ``(batch, min(k, capacity))``, in no
    particular order, ``-1`` where fewer than ``k`` slots are visible."""
    score = index_scores(q_idx, w, k_idx_cache, kv_len)
    top, slot = jax.lax.top_k(score, min(int(k), score.shape[-1]))
    return jnp.where(top > _NEG, slot, -1).astype(jnp.int32)


def latent_decode_attention(q_lat, q_pe, latent_cache, rope_cache, chosen, scale: float):
    """Attention of one query a session over chosen rows of the latent cache,
    in the latent space.  ``q_lat`` ``(batch, heads, rank)`` (the no-position
    part of the query with ``W_uk`` folded in), ``q_pe`` ``(batch, heads,
    rope)``, ``latent_cache`` ``(batch, capacity, rank)``, ``rope_cache``
    ``(batch, capacity / ROPE_PACK, ROPE_PACK * rope)``, ``chosen`` ``(batch,
    k)`` as :func:`sparse_select` gives them.  Returns float32 ``(batch, heads,
    rank)``: the softmax-weighted sum of the chosen ``c_kv``."""
    dtype = latent_cache.dtype
    slots = jnp.maximum(chosen, 0)
    rows = jnp.take_along_axis(latent_cache, slots[:, :, None], axis=1)
    k_pe = rope_rows(rope_cache, slots)
    logit = (jnp.einsum("bhc,bkc->bhk", q_lat.astype(dtype), rows, preferred_element_type=_F32)
             + jnp.einsum("bhr,bkr->bhk", q_pe.astype(dtype), k_pe,
                          preferred_element_type=_F32)) * scale
    prob = jax.nn.softmax(jnp.where((chosen >= 0)[:, None, :], logit, _NEG), axis=-1)
    return jnp.einsum("bhk,bkc->bhc", prob.astype(dtype), rows, preferred_element_type=_F32)


# --------------------------------------------------------------- prefill chunk

def index_scores_chunk(q_idx, w, k_idx_cache, pos0):
    """``I`` of a chunk of one session's queries, at positions ``pos0 ..``,
    against the session's index cache (which already holds the chunk's own
    keys).  ``q_idx`` ``(chunk, heads, width)``, ``w`` ``(chunk, heads)``,
    ``k_idx_cache`` ``(capacity, width)``, ``capacity`` whole blocks of
    :data:`KEY_BLOCK`.  Returns
    float32 ``(chunk, capacity)``, ``-inf`` where the slot is later than the
    query; blocks past the chunk's last position are never computed."""
    chunk, capacity = q_idx.shape[0], k_idx_cache.shape[0]
    block = min(KEY_BLOCK, capacity)
    q = q_idx.astype(k_idx_cache.dtype)
    w = w.astype(_F32)
    position = pos0 + jnp.arange(chunk, dtype=jnp.int32)

    def one_block(j, scores):
        keys = jax.lax.dynamic_slice_in_dim(k_idx_cache, j * block, block)
        hit = jnp.einsum("thd,sd->ths", q, keys, preferred_element_type=_F32)
        score = jnp.einsum("ths,th->ts", jax.nn.relu(hit), w)
        slot = j * block + jnp.arange(block, dtype=jnp.int32)
        score = jnp.where(slot[None, :] <= position[:, None], score, _NEG)
        return jax.lax.dynamic_update_slice_in_dim(scores, score, j * block, axis=1)

    blocks = (pos0 + chunk + block - 1) // block
    return jax.lax.fori_loop(0, blocks, one_block, jnp.full((chunk, capacity), _NEG, _F32))


def _ordered_bits(x):
    """float32 -> uint32, order kept (``-inf`` lowest)."""
    bits = jax.lax.bitcast_convert_type(x.astype(_F32), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def kth_largest(scores, k: int):
    """Each row's ``k``-th largest value, exact, by bisection on the bits (32
    counting passes; no sort): ``(rows, n) -> (rows,)``.  A row with fewer
    than ``k`` values above ``-inf`` gives ``-inf``."""
    k = min(int(k), scores.shape[-1])
    bits = _ordered_bits(scores)

    def one_bit(i, cut):
        tried = cut | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(bits >= tried[:, None], axis=-1, dtype=jnp.int32) >= k
        return jnp.where(enough, tried, cut)

    cut = jax.lax.fori_loop(0, 32, one_bit, jnp.zeros(scores.shape[:1], jnp.uint32))
    back = jnp.where(cut >> 31 == 1, cut & jnp.uint32(0x7FFFFFFF), ~cut)
    return jax.lax.bitcast_convert_type(back, _F32)


def latent_prefill_attention(q_nope, q_pe, latent_cache, rope_cache, scores, cut, pos0,
                             w_uk, w_uv, scale: float):
    """The per-head attention of a chunk of one session's queries over the
    selection ``scores >= cut``.  ``q_nope`` ``(chunk, heads, nope)``, ``q_pe``
    ``(chunk, heads, rope)``, ``latent_cache`` ``(capacity, rank)``, ``rope_cache``
    ``(capacity / ROPE_PACK, ROPE_PACK * rope)``, ``scores`` ``(chunk, capacity)`` with ``-inf`` where a slot is not visible,
    ``cut`` ``(chunk,)``, ``w_uk`` ``(heads, nope, rank)``, ``w_uv`` ``(heads,
    rank, v)``.  Returns float32 ``(chunk, heads, v)``."""
    chunk, heads = q_nope.shape[:2]
    capacity, rope = latent_cache.shape[0], q_pe.shape[-1]
    block = min(KEY_BLOCK, capacity)
    dtype = latent_cache.dtype
    # one product a block: the two parts of a query side by side, the scale folded in
    q = (jnp.concatenate([q_nope, q_pe], axis=-1).astype(_F32) * scale).astype(dtype)

    def one_block(j, carry):
        top, total, acc = carry
        c_kv = jax.lax.dynamic_slice_in_dim(latent_cache, j * block, block)
        k_pe = jax.lax.dynamic_slice_in_dim(rope_cache, j * (block // ROPE_PACK),
                                            block // ROPE_PACK).reshape(block, rope)
        k_nope = jnp.einsum("sc,hnc->shn", c_kv, w_uk, preferred_element_type=_F32).astype(dtype)
        v = jnp.einsum("sc,hcv->shv", c_kv, w_uv, preferred_element_type=_F32).astype(dtype)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, None], (block, heads, rope))], -1)
        logit = jnp.einsum("thd,shd->hts", q, k, preferred_element_type=_F32)
        score = jax.lax.dynamic_slice_in_dim(scores, j * block, block, axis=1)
        read = (score >= cut[:, None]) & (score > _NEG)
        logit = jnp.where(read[None], logit, _NEG)
        new_top = jnp.maximum(top, jnp.max(logit, axis=-1))
        safe = jnp.where(new_top > _NEG, new_top, 0.0)
        weight = jnp.exp(logit - safe[..., None])
        fade = jnp.exp(top - safe)
        total = total * fade + jnp.sum(weight, axis=-1)
        acc = acc * fade[..., None] + jnp.einsum("hts,shv->htv", weight.astype(dtype), v,
                                                 preferred_element_type=_F32)
        return new_top, total, acc

    start = (jnp.full((heads, chunk), _NEG, _F32), jnp.zeros((heads, chunk), _F32),
             jnp.zeros((heads, chunk, w_uv.shape[-1]), _F32))
    blocks = (pos0 + chunk + block - 1) // block
    _, total, acc = jax.lax.fori_loop(0, blocks, one_block, start)
    return jnp.moveaxis(acc / total[..., None], 0, 1)
