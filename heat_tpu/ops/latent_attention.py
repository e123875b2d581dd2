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
``(chunk, context)`` array of all heads is ever alive.  On a TPU that form is
one Pallas kernel, ``ht_sparse_prefill_attn``, which keeps a block's logits,
mask and weights in VMEM (:func:`prefill_form` says where it is taken).

Products take their operands in the caches' type and accumulate in float32.
The scan and the prefill's scores and cut are ``jax.numpy`` (XLA fuses the
scan into one pass over the index keys at 91% of its bytes' floor).  The
selection behind the scan is *cut, membership, compaction* and no sort
(:func:`largest_slots`): on a TPU one Pallas kernel, ``ht_sparse_cut``, entered
by :func:`~heat_tpu.ops._pallas_common.mode` alone, as
``ops/decode_attention.py`` and ``ops/power_retention.py`` are, and by what the
shapes show (:func:`selection_form`); elsewhere the same three parts as
``jax.numpy``.  There is no classic body that could win somewhere and no
autotune arm: XLA's ``top_k`` at a decode cell's shapes is a stable sort of
every score, ten times the kernel's time (PERF.md section 6).

The read of the chosen rows is, on a TPU, one Pallas kernel too,
``ht_sparse_latent_read``, entered the same way (:func:`read_form`), in place
of XLA's two row gathers.  A kernel's copy takes whole tiles of a cache's
layout, eight rows, so each chosen slot's tile of latent rows is copied (a
chunk's copies all in flight at once, the next chunk's started before this
one's rows are picked) and the slot's own row is picked from it in VMEM; a
session's whole rope cache (4.2 MB at the published widths) is one copy, from
which each slot's rope row is picked.  The logits, the softmax and the value
sum follow in VMEM.  Elsewhere the rows are gathered by ``jax.numpy``.
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

__all__ = ["KEY_BLOCK", "ROPE_PACK", "index_scores", "index_scores_chunk", "key_blocks",
           "kth_largest", "largest_slots", "latent_decode_attention", "latent_prefill_attention",
           "prefill_form", "read_form", "rope_rows", "selection_form", "sparse_select"]

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


# ---- the read of the chosen rows as one kernel: the rows fetched by copies, the math in VMEM

# rows of a cache that one copy fetches: the caches lie in tiles of eight rows on a TPU, and a
# kernel's copy takes whole tiles, so a chosen slot's tile is copied and its row picked in VMEM
_TILE_ROWS = 8
# chosen slots whose tiles one of the two buffers holds, and the logits' columns a product takes
_READ_CHUNK = 128
_READ_COLUMNS = 512
# pairs of rows a step of the pick loop moves: its scalar work is what bounds it
_PICK_UNROLL = 8
# the VMEM every kernel has without asking, which this one keeps to: the decode program's
# fusions keep theirs (PERF.md section 6)
_VMEM_READ = 16 << 20
_ROPE_SHIFT = ROPE_PACK.bit_length() - 1


def _shr(x, bits: int):
    """``x // 2**bits`` of a nonnegative int32, as one shift."""
    return jax.lax.shift_right_logical(x, np.int32(bits))


def _read_plan(capacity: int, k: int, heads: int, rank: int, width: int, itemsize: int):
    """``(chosen slots padded to whole chunks, a chunk's slots, VMEM bytes)``
    of :func:`_read_pallas` at these shapes."""
    chunk = min(_READ_CHUNK, -(-k // 8) * 8)
    places = -(-k // chunk) * chunk
    columns = min(_READ_COLUMNS, places)
    held = ((2 * chunk * _TILE_ROWS * rank                         # the tiles' two buffers
             + capacity // ROPE_PACK * width                       # a session's rope rows
             + places * (rank + width)) * itemsize                 # the picked rows
            + places * heads * 4                                   # the logits
            + 2 * (heads * (rank + ROPE_PACK * width) * itemsize   # the queries, two buffers
                   + places * 4 + heads * rank * 4)                # the slots, the output
            + columns * heads * (2 + 3 * 4))                       # a product's temporaries
    return places, chunk, held


def _pick_rows(word_of, rows, first, count: int, pack: int):
    """Row ``first + i`` of ``rows`` (``uint32`` ``(places / pack, width)``,
    ``pack`` rows of the cache's type a word) for ``i < count``:
    ``word_of(i)`` is ``(the 32-bit row that holds slot i's row, which half
    of each word it is)``; a word of 16-bit rows holds an even row in its low
    half and the next row in its high half."""
    if pack == 1:
        def one_row(i, carry):
            rows[pl.ds(first + i, 1), :] = word_of(i)[0]
            return carry

        jax.lax.fori_loop(np.int32(0), np.int32(count), one_row, np.int32(0))
        return

    unroll = next(u for u in (_PICK_UNROLL, 4, 2, 1) if count // 2 % u == 0)

    def some_pairs(n, carry):
        base = pl.multiple_of(_shr(first, 1) + n * np.int32(unroll), unroll)
        for u in range(unroll):
            i = (n * np.int32(unroll) + u) * 2
            (low, even), (high, odd) = word_of(i), word_of(i + 1)
            low = low >> (even << 4).astype(jnp.uint32)
            high = high << ((odd ^ 1) << 4).astype(jnp.uint32)
            low, high = low & np.uint32(0xFFFF), high & np.uint32(0xFFFF0000)
            rows[pl.ds(base + u, 1), :] = low | high
        return carry

    jax.lax.fori_loop(np.int32(0), np.int32(count // 2 // unroll), some_pairs, np.int32(0))


def _read_kernel(slot_ref, q_ref, qpe_ref, chosen_ref, latent_hbm, rope_hbm, out_ref,
                 tiles, rope_all, rows, rope_rows, logit_ref, sems, *, chunk, scale):
    """One session's read: grid ``(sessions,)``, in order.  ``slot_ref``
    (scalar-prefetched) ``(batch * places,)`` the chosen slots clamped to the
    capacity; ``chosen_ref`` ``(1, places)`` the same with ``-1`` where
    nothing is chosen; ``q_ref`` ``(heads, rank)``, ``qpe_ref`` ``(ROPE_PACK
    * heads, ROPE_PACK * rope)`` (copy ``p`` of the rotary part in the lanes
    of a rope row's position ``p``); ``latent_hbm`` ``(batch, capacity / 8,
    8, rank)`` in HBM, ``rope_hbm`` the rope cache in HBM.

    The copies of a chunk's tiles, one a chosen slot, are all started into
    one of the two buffers ``tiles`` before the other buffer's rows are
    picked; a session's last chunk starts the next session's first before
    the math, and the next session's whole rope cache is copied into
    ``rope_all`` once this session's rope rows are picked."""
    b = pl.program_id(0)
    batch = pl.num_programs(0)
    heads, rank = q_ref.shape
    places = chosen_ref.shape[1]
    chunks = places // chunk
    dtype = q_ref.dtype
    pack = 4 // dtype.itemsize

    def slot(s, j):
        return slot_ref[s * np.int32(places) + j]

    def start(s, c, buffer):
        """the copies of chunk ``c`` of session ``s``'s tiles into buffer ``buffer``"""
        def eight(n, carry):
            for u in range(8):
                i = n * np.int32(8) + u
                tile = _shr(slot(s, c * np.int32(chunk) + i), 3)
                pltpu.make_async_copy(latent_hbm.at[s, tile], tiles.at[buffer, i],
                                      sems.at[buffer]).start()
            return carry

        jax.lax.fori_loop(np.int32(0), np.int32(chunk // 8), eight, np.int32(0))

    def rope_copy(s):
        return pltpu.make_async_copy(rope_hbm.at[s], rope_all, sems.at[2])

    @pl.when(b == 0)
    def _():
        rope_copy(0).start()
        start(0, np.int32(0), 0)

    tile_words = tiles.bitcast(jnp.uint32)            # (2, chunk, 8 / pack, rank)
    rope_words = rope_all.bitcast(jnp.uint32)         # (capacity / ROPE_PACK / pack, width)

    def one_chunk(c, carry):
        buffer = (b * np.int32(chunks) + c) % 2

        @pl.when(c + 1 < chunks)
        def _():
            start(b, c + 1, 1 - buffer)

        @pl.when((c + 1 == chunks) & (b + 1 < batch))
        def _():
            start(b + 1, np.int32(0), 1 - buffer)

        pltpu.make_async_copy(tiles.at[buffer], tiles.at[buffer], sems.at[buffer]).wait()
        first = c * np.int32(chunk)

        def latent_word(i):
            at = slot(b, first + i)
            word = _shr(at, pack - 1) & np.int32(_TILE_ROWS // pack - 1)
            return tile_words[buffer, i, pl.ds(word, 1), :], at & 1

        def rope_word(i):
            row = _shr(slot(b, first + i), _ROPE_SHIFT)
            return rope_words[pl.ds(_shr(row, pack - 1), 1), :], row & 1

        _pick_rows(latent_word, rows, first, chunk, pack)

        @pl.when(c == 0)
        def _():
            rope_copy(b).wait()

        _pick_rows(rope_word, rope_rows, first, chunk, pack)
        return carry

    jax.lax.fori_loop(np.int32(0), np.int32(chunks), one_chunk, np.int32(0))

    @pl.when(b + 1 < batch)
    def _():
        rope_copy(b + 1).start()

    # the math of the jax.numpy form, a block of columns at a time
    columns = min(_READ_COLUMNS, places)
    q, qpe = q_ref[...], qpe_ref[...]

    def picked(ref, j):
        return pltpu.bitcast(ref[j * columns // pack:(j + 1) * columns // pack, :], dtype)

    top = jnp.full((heads, 1), _NEG_F32, _F32)
    for j in range(places // columns):
        at = slice(j * columns, (j + 1) * columns)
        logit = jax.lax.dot_general(q, picked(rows, j), (((1,), (1,)), ((), ())),
                                    preferred_element_type=_F32)
        # the rotary logits against both positions of each slot's rope row, the slot's own kept
        pair = jax.lax.dot_general(qpe, picked(rope_rows, j), (((1,), (1,)), ((), ())),
                                   preferred_element_type=_F32)
        chosen = jnp.broadcast_to(chosen_ref[:, at], (heads, columns))
        rope_logit = pair[:heads]
        for p in range(1, ROPE_PACK):
            rope_logit = jnp.where(chosen % ROPE_PACK == p, pair[p * heads:(p + 1) * heads],
                                   rope_logit)
        logit = jnp.where(chosen >= 0, (logit + rope_logit) * np.float32(scale), _NEG_F32)
        logit_ref[:, at] = logit
        top = jnp.maximum(top, jnp.max(logit, axis=1, keepdims=True))
    total = jnp.zeros((heads, 1), _F32)
    for j in range(places // columns):
        at = slice(j * columns, (j + 1) * columns)
        weight = jnp.exp(logit_ref[:, at] - top)
        logit_ref[:, at] = weight
        total = total + jnp.sum(weight, axis=1, keepdims=True)
    acc = jnp.zeros((heads, rank), _F32)
    for j in range(places // columns):
        at = slice(j * columns, (j + 1) * columns)
        prob = (logit_ref[:, at] / total).astype(dtype)
        acc = acc + jnp.dot(prob, picked(rows, j), preferred_element_type=_F32)
    out_ref[...] = acc


def _read_pallas(q_lat, q_pe, latent_cache, rope_cache, chosen, scale, *, interpret: bool):
    batch, heads, rank = q_lat.shape
    capacity, rope = latent_cache.shape[1], q_pe.shape[-1]
    dtype = latent_cache.dtype
    pack = 4 // dtype.itemsize
    width = ROPE_PACK * rope
    places, chunk, _ = _read_plan(capacity, chosen.shape[1], heads, rank, width, dtype.itemsize)
    chosen = jnp.pad(chosen.astype(jnp.int32), ((0, 0), (0, places - chosen.shape[1])),
                     constant_values=-1)
    q = q_lat.astype(dtype)
    q_pe = q_pe.astype(dtype)
    q_pe = jnp.concatenate([jnp.pad(q_pe, ((0, 0), (0, 0), (p * rope, width - (p + 1) * rope)))
                            for p in range(ROPE_PACK)], axis=1)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        functools.partial(_read_kernel, chunk=chunk, scale=float(scale)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(batch,),
            in_specs=[
                pl.BlockSpec((None, heads, rank), lambda b, s: (b, 0, 0)),
                pl.BlockSpec((None, ROPE_PACK * heads, width), lambda b, s: (b, 0, 0)),
                pl.BlockSpec((None, 1, places), lambda b, s: (b, 0, 0)),
                any_space, any_space,
            ],
            out_specs=pl.BlockSpec((None, heads, rank), lambda b, s: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, chunk, _TILE_ROWS, rank), dtype),
                pltpu.VMEM((capacity // ROPE_PACK, width), dtype),
                pltpu.VMEM((places // pack, rank), jnp.uint32),
                pltpu.VMEM((places // pack, width), jnp.uint32),
                pltpu.VMEM((heads, places), _F32),
                pltpu.SemaphoreType.DMA((3,)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((batch, heads, rank), _F32),
        # the slots are clamped to the capacity below: every copy and pick lies in bounds
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",),
                                             disable_bounds_checks=True),
        cost_estimate=pl.CostEstimate(
            flops=2 * batch * heads * places * (2 * rank + ROPE_PACK * width),
            transcendentals=batch * heads * places,
            bytes_accessed=batch * (places * _TILE_ROWS * rank * dtype.itemsize
                                    + capacity // ROPE_PACK * width * dtype.itemsize
                                    + heads * (rank + ROPE_PACK * width) * dtype.itemsize
                                    + 4 * heads * rank)),
        interpret=interpret,
        name="ht_sparse_latent_read",
    )(jnp.clip(chosen, 0, capacity - 1).reshape(-1), q, q_pe, chosen[:, None, :],
      latent_cache.reshape(batch, capacity // _TILE_ROWS, _TILE_ROWS, rank), rope_cache)


def read_form(batch: int, capacity: int, k: int, rank: int, rope: int, itemsize: int = 2,
              heads: int = 128) -> str:
    """Which form :func:`latent_decode_attention` takes at these shapes (the
    caches' ``itemsize``, the queries' ``heads``): ``gather_kernel`` (the Pallas kernel
    ``ht_sparse_latent_read``) where the Pallas mode is ``tpu`` or
    ``interpret``, fewer slots are chosen than the capacity holds, the
    capacity is whole tiles of rows and of rope rows, the caches' type 16 or
    32 bits and, on a TPU, the latent row is whole lane tiles, a rope row one
    lane tile and the kernel's buffers within the VMEM every kernel has; else
    ``gather_jnp`` (the rows gathered by ``jax.numpy``)."""
    del batch
    how = _mode()
    if (how not in ("tpu", "interpret") or k >= capacity or itemsize not in (2, 4)
            or capacity % (ROPE_PACK * _TILE_ROWS)):
        return "gather_jnp"
    if how == "interpret":
        return "gather_kernel"
    width = ROPE_PACK * rope
    *_, held = _read_plan(capacity, k, heads, rank, width, itemsize)
    if rank % LANE or width != LANE or held > _VMEM_READ:
        return "gather_jnp"
    return "gather_kernel"


def latent_decode_attention(q_lat, q_pe, latent_cache, rope_cache, chosen, scale: float):
    """Attention of one query a session over chosen rows of the latent cache,
    in the latent space.  ``q_lat`` ``(batch, heads, rank)`` (the no-position
    part of the query with ``W_uk`` folded in), ``q_pe`` ``(batch, heads,
    rope)``, ``latent_cache`` ``(batch, capacity, rank)``, ``rope_cache``
    ``(batch, capacity / ROPE_PACK, ROPE_PACK * rope)``, ``chosen`` ``(batch,
    k)`` as :func:`sparse_select` gives them (any order).  Returns float32
    ``(batch, heads, rank)``: the softmax-weighted sum of the chosen ``c_kv``.

    The queries and products take the cache's type; the logits ``(q_lat .
    c_kv + q_pe . k_pe) * scale`` and the softmax are float32, ``-1`` slots
    masked to ``-inf``; the weights are rounded to the cache's type for the
    value sum, which accumulates in float32.  On a TPU it is the kernel
    ``ht_sparse_latent_read`` where :func:`read_form` says so, else the rows
    are gathered by ``jax.numpy``."""
    batch, capacity, rank = latent_cache.shape
    if read_form(batch, capacity, chosen.shape[1], rank, q_pe.shape[-1],
                 latent_cache.dtype.itemsize, q_lat.shape[1]) == "gather_kernel":
        return _read_pallas(q_lat, q_pe, latent_cache, rope_cache, chosen, scale,
                            interpret=(_mode() == "interpret"))
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

    return jax.lax.fori_loop(0, key_blocks(pos0, chunk, capacity), one_block,
                             jnp.full((chunk, capacity), _NEG, _F32))


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


def key_blocks(pos0, chunk: int, capacity: int):
    """Blocks of :data:`KEY_BLOCK` slots a chunk of ``chunk`` positions from
    ``pos0`` reads: those up to the one that holds its last position (plain or
    traced ``pos0``)."""
    block = min(KEY_BLOCK, capacity)
    return (pos0 + chunk + block - 1) // block


# ---- the prefill attention as one kernel: a key block's logits, mask and weights stay in VMEM

# query rows a fold takes at once, and heads a grid step holds (all of the chunk's queries are
# resident, so that a head's keys and values are computed from the latent rows once a block)
_QUERY_TILE = 256
_HEADS_A_STEP = 4
# the VMEM the kernel asks for: it runs in the prefill program alone, never in the decode
# program, where XLA keeps the layers' rope caches in VMEM (PERF.md section 6)
_VMEM_PREFILL = 64 << 20
_NEG_F32, _ZERO_F32 = np.float32(-np.inf), np.float32(0)


def _depth(nope: int, rope: int) -> int:
    """A query's no-position part and its rotary part once for each position
    a rope row holds, padded to whole lane tiles."""
    return -(-(nope + ROPE_PACK * rope) // LANE) * LANE


def _prefill_tiles(chunk: int, capacity: int, heads: int, widths, itemsize: int):
    """``(key block, query rows a fold, heads a grid step, VMEM bytes)`` of the
    kernel at these shapes; ``widths`` is ``(nope, rope, v, rank)``."""
    nope, rope, v, rank = widths
    block = min(KEY_BLOCK, capacity)
    rows = next((r for r in (_QUERY_TILE, 128, 64, 32, 16) if chunk % r == 0), 0)
    group = next(g for g in (_HEADS_A_STEP, 2, 1) if heads % g == 0)
    depth = _depth(nope, rope)                                   # a query's two parts, whole lane tiles
    fetched = (chunk * group * depth * itemsize                  # queries
               + chunk * (LANE + block) * 4                      # cut, scores
               + block * (rank + rope) * itemsize                # latent, rope rows
               + group * rank * (nope + v) * itemsize)           # W_uk, W_uv
    held = (2 * (fetched + chunk * group * v * 4)               # two buffers each
            + 2 * group * chunk * LANE * 4                       # running max, total
            + group * block * (depth + v) * itemsize             # keys (turned), values
            + 8 * rows * block * 4)                              # a fold's values
    return block, rows, group, held


def prefill_form(chunk: int, capacity: int, heads: int, widths, itemsize: int) -> str:
    """Which form :func:`latent_prefill_attention` takes at these shapes
    (``widths``: ``(nope, rope, v, rank)``): ``flash_kernel`` (the Pallas
    kernel ``ht_sparse_prefill_attn``) where the Pallas mode is ``tpu`` or
    ``interpret``, the chunk is whole query tiles and, on a TPU, the widths
    are whole lane tiles (the no-position part whole sublane tiles: the two
    parts side by side are padded to lanes) and the tiles fit the kernel's
    VMEM; else ``blockwise_jnp`` (the same blocks and rounding in
    ``jax.numpy``)."""
    how = _mode()
    block, rows, _, held = _prefill_tiles(chunk, capacity, heads, widths, itemsize)
    whole = rows > 0 and capacity % block == 0 and block % ROPE_PACK == 0
    nope, rope, v, rank = widths
    if how == "interpret" and whole:
        return "flash_kernel"
    if (how == "tpu" and whole and held <= _VMEM_PREFILL
            and nope % 16 == 0
            and all(n % LANE == 0 for n in (v, rank, block, ROPE_PACK * rope))):
        return "flash_kernel"
    return "blockwise_jnp"


def _prefill_kernel(plan_ref, q_ref, *refs, rows, rope, causal):
    """One head group's fold of one key block: grid ``(head groups, key
    blocks)``, the blocks in ascending order, none past ``plan_ref[0]``
    (``plan_ref[1]`` is the chunk's first position).
    ``q_ref`` ``(group, chunk, depth)``: a query's no-position part, then its
    rotary part once for each position a rope row holds, then zeros to whole
    lane tiles; ``kt_ref`` ``(group, depth, block)`` the keys turned the same
    way, a slot's rotary key in its own lanes and zeros in its neighbours'.
    ``out_ref`` ``(chunk, group * v)`` holds the running value sums and, after
    the last block, the attention; ``top_ref``, ``total_ref`` ``(group, chunk,
    1)`` the running max and total.  A query reads the selection ``score >=
    cut`` (``cut_ref``, ``score_ref``), or ``causal``: every slot up to its
    own position (there are no such refs)."""
    if causal:
        cut_ref = score_ref = None
    else:
        cut_ref, score_ref, *refs = refs
    ckv_ref, rope_ref, wuk_ref, wuv_ref, out_ref, top_ref, total_ref, kt_ref, v_ref = refs
    j = pl.program_id(1)
    group, chunk, _ = top_ref.shape
    block = ckv_ref.shape[0]
    nope, v = wuk_ref.shape[0] // group, v_ref.shape[1] // group
    width = ROPE_PACK * rope
    pad = kt_ref.shape[1] - nope - width
    dtype = ckv_ref.dtype
    tiles = np.int32(chunk // rows)

    def rows_of(t):
        return pl.ds(pl.multiple_of(t * np.int32(rows), rows), rows)

    @pl.when(j == 0)
    def _():
        top_ref[...] = jnp.full(top_ref.shape, _NEG_F32, _F32)
        total_ref[...] = jnp.zeros(total_ref.shape, _F32)
        out_ref[...] = jnp.zeros(out_ref.shape, _F32)

    @pl.when(j < plan_ref[0])
    def _():
        # the group's keys and values from the block's latent rows, rounded as the cache is
        c_kv = ckv_ref[...]
        k_nope = jax.lax.dot_general(wuk_ref[...], c_kv, (((1,), (1,)), ((), ())),
                                     preferred_element_type=_F32).astype(dtype)
        v_ref[...] = jnp.dot(c_kv, wuv_ref[...], preferred_element_type=_F32).astype(dtype)
        # each slot's row of the rope block (a product with a 0/1 table: exact), its own lanes
        # kept and its neighbour's zeroed
        shape = (block, block // ROPE_PACK)
        slot = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
        row = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
        lane = jax.lax.broadcasted_iota(jnp.int32, (block, width), 1)
        k_pe = jnp.zeros((block, width), _F32)
        for i in range(ROPE_PACK):
            spread = jnp.dot((slot == row * ROPE_PACK + i).astype(dtype), rope_ref[...],
                             preferred_element_type=_F32)
            k_pe = k_pe + jnp.where((lane >= i * rope) & (lane < (i + 1) * rope), spread, _ZERO_F32)
        k_pe = k_pe.T.astype(dtype)
        for h in range(group):
            kt_ref[h, :nope, :] = k_nope[h * nope:(h + 1) * nope]
            kt_ref[h, nope:nope + width, :] = k_pe
            if pad:
                kt_ref[h, nope + width:, :] = jnp.zeros((pad, block), dtype)

        def one_tile(t, carry):
            at = rows_of(t)
            if causal:
                slot = j * np.int32(block) + jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1)
                position = plan_ref[1] + t * np.int32(rows) + jax.lax.broadcasted_iota(
                    jnp.int32, (rows, block), 0)
                read = slot <= position
            else:
                score = score_ref[at, :]
                read = (score >= cut_ref[at, :]) & (score > _NEG_F32)
            for h in range(group):
                logit = jnp.dot(q_ref[h, at, :], kt_ref[h], preferred_element_type=_F32)
                logit = jnp.where(read, logit, _NEG_F32)
                top = top_ref[h, at, :]
                new_top = jnp.maximum(top, jnp.max(logit, axis=1, keepdims=True))
                safe = jnp.where(new_top > _NEG_F32, new_top, _ZERO_F32)
                weight = jnp.exp(logit - safe)
                fade = jnp.exp(top - safe)
                total_ref[h, at, :] = (total_ref[h, at, :] * fade
                                       + jnp.sum(weight, axis=1, keepdims=True))
                lanes = slice(h * v, (h + 1) * v)
                out_ref[at, lanes] = out_ref[at, lanes] * fade + jnp.dot(
                    weight.astype(dtype), v_ref[:, lanes], preferred_element_type=_F32)
                top_ref[h, at, :] = new_top
            return carry

        # query tiles wholly before the block's first slot see none of it: the fold would add
        # exactly nothing to them
        first = jnp.maximum(j * np.int32(block) - plan_ref[1], 0) // np.int32(rows)
        jax.lax.fori_loop(first, tiles, one_tile, np.int32(0))

    @pl.when(j == plan_ref[0] - 1)
    def _():
        def one_tile(t, carry):
            at = rows_of(t)
            for h in range(group):
                lanes = slice(h * v, (h + 1) * v)
                out_ref[at, lanes] = out_ref[at, lanes] / total_ref[h, at, :]
            return carry

        jax.lax.fori_loop(np.int32(0), tiles, one_tile, np.int32(0))


def _prefill_pallas(q_nope, q_pe, latent_cache, rope_cache, scores, cut, pos0, w_uk, w_uv, *,
                    interpret: bool):
    chunk, heads, nope = q_nope.shape
    capacity, rank = latent_cache.shape
    rope, v = q_pe.shape[-1], w_uv.shape[-1]
    dtype = latent_cache.dtype
    block, rows, group, _ = _prefill_tiles(chunk, capacity, heads, (nope, rope, v, rank),
                                           dtype.itemsize)
    width = ROPE_PACK * rope
    depth = _depth(nope, rope)
    q = jnp.concatenate([q_nope, jnp.tile(q_pe, (1, 1, ROPE_PACK))]
                        + ([jnp.zeros((chunk, heads, depth - nope - width), q_nope.dtype)]
                           if depth > nope + width else []), axis=-1)
    q = jnp.moveaxis(q, 1, 0)
    pos0 = jnp.asarray(pos0, jnp.int32)
    causal = scores is None
    plan = jnp.stack([key_blocks(pos0, chunk, capacity), pos0])

    def key(g, j, n):
        """the block a step holds: past the chunk's last block the pipeline keeps its last"""
        return jnp.minimum(j, n[0] - 1)

    passes = heads // group
    selection = [] if causal else [
        pl.BlockSpec((chunk, 1), lambda g, j, n: (0, 0)),
        pl.BlockSpec((chunk, block), lambda g, j, n: (0, key(g, j, n)))]
    operands = [] if causal else [cut[:, None], scores]
    return pl.pallas_call(
        functools.partial(_prefill_kernel, rows=rows, rope=rope, causal=causal),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(passes, capacity // block),
            in_specs=[
                pl.BlockSpec((group, chunk, depth), lambda g, j, n: (g, 0, 0)),
            ] + selection + [
                pl.BlockSpec((block, rank), lambda g, j, n: (key(g, j, n), 0)),
                pl.BlockSpec((block // ROPE_PACK, ROPE_PACK * rope),
                             lambda g, j, n: (key(g, j, n), 0)),
                pl.BlockSpec((group * nope, rank), lambda g, j, n: (g, 0)),
                pl.BlockSpec((rank, group * v), lambda g, j, n: (0, g)),
            ],
            out_specs=pl.BlockSpec((chunk, group * v), lambda g, j, n: (0, g)),
            scratch_shapes=[
                pltpu.VMEM((group, chunk, 1), _F32),
                pltpu.VMEM((group, chunk, 1), _F32),
                pltpu.VMEM((group, depth, block), dtype),
                pltpu.VMEM((block, group * v), dtype),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((chunk, heads * v), _F32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"),
                                             vmem_limit_bytes=_VMEM_PREFILL),
        # the capacity, not the blocks a chunk reads (pos0 is traced): the bound the scheduler
        # plans with
        cost_estimate=pl.CostEstimate(
            flops=2 * heads * capacity * (chunk * (depth + v) + rank * (nope + v)),
            transcendentals=heads * chunk * capacity,
            bytes_accessed=(passes * ((0 if causal else 4 * chunk * capacity)
                                      + capacity * (rank + rope) * dtype.itemsize)
                            + (chunk * depth + rank * (nope + v)) * heads * dtype.itemsize
                            + 4 * chunk * heads * v)),
        interpret=interpret,
        name="ht_sparse_prefill_attn",
    )(plan, q, *operands, latent_cache, rope_cache, w_uk.reshape(heads * nope, rank),
      jnp.moveaxis(w_uv, 0, 1).reshape(rank, heads * v)).reshape(chunk, heads, v)


def latent_prefill_attention(q_nope, q_pe, latent_cache, rope_cache, scores, cut, pos0,
                             w_uk, w_uv, scale: float):
    """The per-head attention of a chunk of one session's queries over the
    selection ``scores >= cut`` (``scores`` None: over every slot up to the
    query's position, ``pos0 + row``; no scores are read).  ``q_nope`` ``(chunk, heads, nope)``, ``q_pe``
    ``(chunk, heads, rope)``, ``latent_cache`` ``(capacity, rank)``, ``rope_cache``
    ``(capacity / ROPE_PACK, ROPE_PACK * rope)``, ``scores`` ``(chunk, capacity)`` with ``-inf`` where a slot is not visible,
    ``cut`` ``(chunk,)``, ``w_uk`` ``(heads, nope, rank)``, ``w_uv`` ``(heads,
    rank, v)``.  Returns float32 ``(chunk, heads, v)``.

    Blockwise with a running softmax, the blocks up to the chunk's last
    position in ascending order: a block's keys and values computed from its
    latent rows and rounded to the cache's type, float32 logits of the queries
    (the scale folded in, rounded likewise) masked by the selection, running
    max, fade and total in float32 (a row with nothing selected in a block adds
    exactly 0), the weights rounded to the cache's type for the value product.
    On a TPU it is one Pallas kernel, ``ht_sparse_prefill_attn``, that keeps
    the logits in VMEM; the same steps in ``jax.numpy`` elsewhere
    (:func:`prefill_form`)."""
    chunk, heads, nope = q_nope.shape
    capacity, rank = latent_cache.shape
    rope, dtype = q_pe.shape[-1], latent_cache.dtype
    # the scale folded into the queries, each part rounded as the two side by side would be
    q_nope, q_pe = ((q.astype(_F32) * scale).astype(dtype) for q in (q_nope, q_pe))
    how = prefill_form(chunk, capacity, heads, (nope, rope, w_uv.shape[-1], rank), dtype.itemsize)
    if how == "flash_kernel":
        return _prefill_pallas(q_nope, q_pe, latent_cache, rope_cache, scores, cut, pos0, w_uk,
                               w_uv, interpret=(_mode() == "interpret"))
    block = min(KEY_BLOCK, capacity)
    # one product a block: the two parts of a query side by side
    q = jnp.concatenate([q_nope, q_pe], axis=-1)

    def one_block(j, carry):
        top, total, acc = carry
        c_kv = jax.lax.dynamic_slice_in_dim(latent_cache, j * block, block)
        k_pe = jax.lax.dynamic_slice_in_dim(rope_cache, j * (block // ROPE_PACK),
                                            block // ROPE_PACK).reshape(block, rope)
        k_nope = jnp.einsum("sc,hnc->shn", c_kv, w_uk, preferred_element_type=_F32).astype(dtype)
        v = jnp.einsum("sc,hcv->shv", c_kv, w_uv, preferred_element_type=_F32).astype(dtype)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, None], (block, heads, rope))], -1)
        logit = jnp.einsum("thd,shd->hts", q, k, preferred_element_type=_F32)
        if scores is None:
            slot = j * block + jnp.arange(block, dtype=jnp.int32)
            read = slot[None, :] <= pos0 + jnp.arange(chunk, dtype=jnp.int32)[:, None]
        else:
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
    _, total, acc = jax.lax.fori_loop(0, key_blocks(pos0, chunk, capacity), one_block, start)
    return jnp.moveaxis(acc / total[..., None], 0, 1)
