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
  the exact top ``k``: ``(batch, k)`` slot numbers in slot order, ``-1`` where
  fewer than ``k`` are visible;
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

Products take their operands in the caches' type and accumulate in float32.
The scan, the gather and the prefill path are ``jax.numpy`` (XLA fuses the scan
into one pass over the index keys at 91% of its bytes' floor).  The selection
behind the scan is *cut, membership, compaction* and no sort
(:func:`largest_slots`): on a TPU one Pallas kernel, ``ht_sparse_cut``, entered
by :func:`~heat_tpu.ops._pallas_common.mode` alone, as
``ops/decode_attention.py`` and ``ops/power_retention.py`` are, and by what the
shapes show (:func:`selection_form`); elsewhere the same three parts as
``jax.numpy``.  There is no classic body that could win somewhere and no
autotune arm: XLA's ``top_k`` at a decode cell's shapes is a stable sort of
every score, ten times the kernel's time (PERF.md section 6, PRs 34 and 35,
which also has what XLA makes of the row gather).
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

__all__ = ["KEY_BLOCK", "ROPE_PACK", "index_scores", "index_scores_chunk", "kth_largest",
           "largest_slots", "latent_decode_attention", "latent_prefill_attention", "rope_rows",
           "selection_form", "sparse_select"]

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


# ---- the selection: cut, membership, compaction

# slots of one lane group: the compaction's two levels are the groups and the
# lanes inside one
_GROUP = LANE
_INT_MIN = np.int32(-2 ** 31)
# the kernel holds the call's scores, their integer image, a table of groups x
# groups and a session's working set in the VMEM every kernel has without
# asking (asking for more takes it from XLA, which keeps a layer's rope cache
# there for the gather that follows: PERF.md section 6, PR 35): scores of this
# many bytes and sessions of this many groups, or fewer
_VMEM_SCORES = 4 << 20
_VMEM_GROUPS = 1024


def _image(x):
    """float32 -> int32 whose signed order is the floats' (``-inf`` lowest,
    the two zeros one value)."""
    bits = jax.lax.bitcast_convert_type(jnp.where(x == 0, np.float32(0), x), jnp.int32)
    return bits ^ ((bits >> np.int32(31)) & np.int32(0x7FFFFFFF))


_NEG_IMAGE = np.int32(np.array(-np.inf, np.float32).view(np.int32) ^ 0x7FFFFFFF)


def _bisect(count_at_least, k, like):
    """The largest image ``c`` with ``count_at_least(c) >= k``: 32 counting
    passes, one bit of the order-preserving unsigned image a pass (kept as
    int32 bits; ``^ _INT_MIN`` is the signed image)."""
    def one_bit(i, cut):
        tried = cut | (np.int32(1) << (np.int32(31) - i))
        return jnp.where(count_at_least(tried ^ _INT_MIN) >= k, tried, cut)

    return jax.lax.fori_loop(np.int32(0), np.int32(32), one_bit, jnp.zeros_like(like)) ^ _INT_MIN


def _select_jnp(score, k: int):
    """The three parts as ``jax.numpy``: ``score`` ``(batch, groups * 128)``
    float32, ``k`` below the row length."""
    batch = score.shape[0]
    groups = score.shape[1] // _GROUP
    img = _image(score)
    # 1. the cut: each row's k-th largest, and how many lie strictly above it
    cut = _bisect(lambda c: jnp.sum(img >= c, axis=-1, keepdims=True, dtype=jnp.int32), k,
                  img[:, :1])
    wanted = k - jnp.sum(img > cut, axis=-1, keepdims=True, dtype=jnp.int32)
    # 2. membership: of the scores equal to the cut, the lowest slots
    img = img.reshape(batch, groups, _GROUP)
    cut, wanted = cut[:, :, None], wanted[:, :, None]

    def before(mask):
        """(count inside the group up to and with each lane, each group's total,
        the total of the groups before it)"""
        incl = jnp.cumsum(mask, axis=-1, dtype=jnp.int32)
        total = incl[:, :, -1]
        return incl, total, jnp.cumsum(total, axis=-1) - total

    tied = (img == cut) & (img > _NEG_IMAGE)
    incl, _, passed = before(tied)
    member = (img > cut) | (tied & (incl - 1 + passed[:, :, None] < wanted))
    # 3. compaction: place j holds the slot of the member of rank j
    incl, total, passed = before(member)
    place = jnp.arange(k, dtype=jnp.int32)[None, :, None]
    through = (passed + total)[:, None, :] <= place                     # (batch, k, groups)
    group = jnp.sum(through, axis=-1, dtype=jnp.int32)                   # (batch, k)
    rank = place[:, :, 0] - jnp.sum(jnp.where(through, total[:, None, :], 0), axis=-1)
    one_hot = group[:, :, None] == jnp.arange(groups, dtype=jnp.int32)
    counts = jnp.einsum("bkg,bgl->bkl", one_hot.astype(jnp.bfloat16), incl.astype(jnp.bfloat16),
                        preferred_element_type=_F32)                      # at most 128: exact
    lane = jnp.sum(counts <= rank[:, :, None].astype(_F32), axis=-1, dtype=jnp.int32)
    return jnp.where(group < groups, group * _GROUP + lane, -1)


def _cut_kernel(tri_ref, low_ref, score_ref, out_ref, img_ref, cut_ref, wanted_ref, *,
                k, rows, width):
    """All of :func:`largest_slots` on scores ``(batch, groups, 128)``: a
    session's groups along the sublanes, a group's slots along the lanes.
    ``rows`` groups (a whole number of sublane tiles) hold every real slot, the
    rest is ``-inf`` up to whole lane tiles of groups, the contraction width of
    the compaction's products.  ``tri_ref`` ``(128, 384)``: lane ``l'`` of the
    first block counts the lanes ``l <= l'``, the second every lane, the third
    is the first turned round; ``low_ref`` ``(groups, groups)``: row ``g``
    counts the groups before ``g``.  ``out_ref`` ``(batch, places)``, ``width``
    places at a time."""
    batch, groups, _ = score_ref.shape
    tile = 8
    bf16 = jnp.bfloat16

    # ---- 1. the cut, every session at once: a pass is rows / 8 compares and adds a session
    for t in range(rows // tile):
        at = pl.ds(t * tile, tile)
        img_ref[:, at, :] = _image(score_ref[:, at, :])
    if groups > rows:
        img_ref[:, rows:, :] = jnp.full((batch, groups - rows, _GROUP), _NEG_IMAGE, jnp.int32)

    def count(reaches):
        acc = jnp.zeros((batch, tile, _GROUP), jnp.int32)
        for t in range(rows // tile):
            acc = acc + reaches(img_ref[:, pl.ds(t * tile, tile), :]).astype(jnp.int32)
        acc = jnp.sum(acc.astype(_F32), axis=1, keepdims=True)
        return jnp.sum(acc, axis=2, keepdims=True).astype(jnp.int32)

    cut = _bisect(lambda c: count(lambda x: x >= c), k, jnp.zeros((batch, 1, 1), jnp.int32))
    wanted = k - count(lambda x: x > cut)
    cut_ref[...] = jnp.broadcast_to(cut, cut_ref.shape)
    wanted_ref[...] = jnp.broadcast_to(wanted, wanted_ref.shape).astype(_F32)

    # ---- 2 and 3, a session at a time
    group_id = jax.lax.broadcasted_iota(jnp.int32, (groups, width), 0)
    place_id = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)

    def counted(mask):
        """(the mask as numbers, its count inside the group up to and with each
        lane, each group's total in every lane, the total of the groups before)"""
        m = mask.astype(_F32).astype(bf16)
        both = jnp.dot(m, tri_ref[:, :2 * _GROUP], preferred_element_type=_F32)
        incl, total = both[:, :_GROUP], both[:, _GROUP:]
        passed = jnp.dot(low_ref[...], total.astype(bf16), preferred_element_type=_F32)
        return m, incl, total, passed

    def one_session(b, carry):
        img = img_ref[b]
        cut = cut_ref[b][:1, :]
        tied = (img == cut) & (img > _NEG_IMAGE)
        _, incl, _, passed = counted(tied)
        member = (img > cut) | (tied & (incl - 1.0 + passed < wanted_ref[b][:1, :]))
        m, _, total, passed = counted(member)
        # the running counts with the lanes along the sublanes: (128, groups)
        running = jax.lax.dot_general(tri_ref[:, 2 * _GROUP:], m, (((1,), (1,)), ((), ())),
                                      preferred_element_type=_F32).astype(bf16)
        through = (passed + total)[:rows]
        through = jnp.concatenate([through] * (width // _GROUP), axis=1)
        total = jnp.concatenate([total[:rows]] * (width // _GROUP), axis=1)
        for c in range(out_ref.shape[1] // width):
            place = (place_id + np.int32(c * width)).astype(_F32)
            done = through <= place                                            # (rows, width)
            group = jnp.sum(done.astype(_F32), axis=0, keepdims=True)
            rank = place - jnp.sum(jnp.where(done, total, 0.0), axis=0, keepdims=True)
            one_hot = (group_id == group.astype(jnp.int32)).astype(_F32).astype(bf16)
            counts = jnp.dot(running, one_hot, preferred_element_type=_F32)    # (128, width)
            lane = jnp.sum((counts <= rank).astype(_F32), axis=0, keepdims=True)
            slot = jnp.where(group < rows, group * _GROUP + lane, -1.0).astype(jnp.int32)
            out_ref[pl.ds(b, 1), pl.ds(c * width, width)] = slot
        return carry

    jax.lax.fori_loop(np.int32(0), np.int32(batch), one_session, np.int32(0))


@functools.lru_cache(maxsize=None)
def _count_tables(groups: int):
    lane = np.arange(_GROUP)
    upper = lane[:, None] <= lane[None, :]
    tri = np.concatenate([upper, np.ones_like(upper), upper.T], axis=1)
    group = np.arange(groups)
    return tri.astype(np.float32), (group[None, :] < group[:, None]).astype(np.float32)


def _select_pallas(score, k: int, *, interpret: bool):
    batch, capacity = score.shape
    rows = -(-capacity // (8 * _GROUP)) * 8           # groups that hold a real slot, whole sublane tiles
    groups = -(-rows // LANE) * LANE                  # the products' contraction width: whole lane tiles
    places = -(-k // _GROUP) * _GROUP
    width = next(w for w in (512, 256, 128) if places % w == 0)
    score = jnp.pad(score, ((0, 0), (0, groups * _GROUP - capacity)), constant_values=_NEG)
    tri, low = (jnp.asarray(t, jnp.bfloat16) for t in _count_tables(groups))
    whole = pl.BlockSpec(memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        functools.partial(_cut_kernel, k=np.int32(k), rows=rows, width=width),
        in_specs=[whole, whole, whole],
        out_specs=whole,
        out_shape=jax.ShapeDtypeStruct((batch, places), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((batch, groups, _GROUP), jnp.int32),
            pltpu.VMEM((batch, 8, _GROUP), jnp.int32),
            pltpu.VMEM((batch, 8, _GROUP), _F32),
        ],
        cost_estimate=pl.CostEstimate(
            flops=batch * (34 * 3 * rows * _GROUP + 2 * places * (groups + rows) * _GROUP),
            bytes_accessed=4 * batch * (groups * _GROUP + places),
            transcendentals=0,
        ),
        interpret=interpret,
        name="ht_sparse_cut",
    )(tri, low, score.reshape(batch, groups, _GROUP))
    return out[:, :k]


def _kernel_takes(batch: int, capacity: int, how: str) -> bool:
    return how == "interpret" or (how == "tpu" and capacity % _GROUP == 0
                                  and capacity <= _VMEM_GROUPS * _GROUP
                                  and batch * capacity * 4 <= _VMEM_SCORES)


def selection_form(batch: int, capacity: int, k: int) -> str:
    """Which lowering :func:`sparse_select` takes at these shapes:
    ``cut_kernel`` (the Pallas kernel ``ht_sparse_cut``), ``cut_jnp`` (the same
    three parts as ``jax.numpy``), ``all`` (``k`` reaches the capacity: every
    visible slot, nothing to select)."""
    if k >= capacity:
        return "all"
    return "cut_kernel" if _kernel_takes(batch, capacity, _mode()) else "cut_jnp"


def largest_slots(score, k: int):
    """The slots of each row's ``k`` largest scores, exact, by *cut,
    membership, compaction* and no sort: ``score`` ``(batch, capacity)``
    float32 with ``-inf`` where a slot is not visible.  Returns int32 ``(batch,
    min(k, capacity))`` in slot order, ``-1`` from the number of visible slots
    on.  It is the set ``jax.lax.top_k`` returns: of equal scores the lowest
    slots win, the two zeros are equal, ``-inf`` is never chosen.  NaN scores
    are outside the contract.

    1. The cut: the row's ``k``-th largest value by bisection on the
       order-preserving integer image of the float bits, 32 counting passes,
       and the count of scores strictly above it.
    2. Membership: ``score > cut``, or ``score == cut`` and fewer than ``k -
       above`` equal scores lie at lower slots.
    3. Compaction, two levels over lane groups of 128 slots: the running count
       of members inside each group and the groups' running totals; output
       place ``j`` finds its group by comparing ``j`` with the totals, fetches
       the group's 128 running counts by a one-hot product and finds its lane
       by comparing them with its rank in the group.  Dense compares and small
       products only: no sort, no scatter, no gather.

    On a TPU all of it is one Pallas kernel, ``ht_sparse_cut``, which holds the
    call's scores in VMEM; elsewhere, and for shapes the kernel does not take
    (:func:`selection_form`), the same parts run as ``jax.numpy`` on scores
    padded with ``-inf`` to whole groups."""
    batch, capacity = score.shape
    k = int(k)
    form = selection_form(batch, capacity, k)
    if form == "all":
        slot = jnp.arange(capacity, dtype=jnp.int32)[None, :]
        return jnp.where(score > _NEG, slot, -1)
    score = score.astype(_F32)
    if form == "cut_kernel":
        return _select_pallas(score, k, interpret=(_mode() == "interpret"))
    pad = (-capacity) % _GROUP
    if pad:
        score = jnp.pad(score, ((0, 0), (0, pad)), constant_values=_NEG)
    return _select_jnp(score, k)


def sparse_select(q_idx, w, k_idx_cache, kv_len, k: int):
    """The ``k`` visible slots of largest index score for each session's one
    query position, exact: int32 ``(batch, min(k, capacity))``, in slot order,
    ``-1`` where fewer than ``k`` slots are visible.  The scan is
    :func:`index_scores`, the selection :func:`largest_slots`."""
    return largest_slots(index_scores(q_idx, w, k_idx_cache, kv_len), k)


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
