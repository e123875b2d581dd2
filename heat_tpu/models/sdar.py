"""SDAR: a Qwen3-MoE decoder that generates by block diffusion, and what a
session needs to serve it.

The layer is Qwen3-MoE's (``config.json`` of JetLM/SDAR-30B-A3B-Chat,
``model_type`` ``sdar_moe``; the equations are written out in
``perf/reference/sdar.py``): pre-norm residual blocks ``x += Attn(RMSNorm(x))``
then ``x += MoE(RMSNorm(x))``, a final RMSNorm and an untied head.

- **Attention.**  Grouped queries (``num_attention_heads`` over
  ``num_key_value_heads``), no bias; ``q`` and ``k`` normed a head
  (``q_norm``, ``k_norm``), then Qwen3's rotary embedding over the whole head
  (lane ``i`` with ``i + head_dim / 2``); scale ``head_dim^-1/2``.
- **The mask is block-causal.**  Position ``i`` sees ``j`` where ``j //
  block_length <= i // block_length``: inside a block every position sees
  every other.  A prefill chunk reads through
  :func:`~heat_tpu.ops.decode_attention.masked_attention` with each row's
  position the last of its block.
- **Experts.**  ``num_experts`` routed experts a layer, held whole, no shared
  one: ``softmax(h W_r)`` in float32 over all of them, the top
  ``num_experts_per_tok``, renormalised (``norm_topk_prob``)
  (:func:`heat_tpu.parallel.expert.softmax_topk_routing`, then
  :func:`~heat_tpu.parallel.expert.held_experts_sum`; no token is dropped).

**Generation** (SDAR's generate loop, greedy, static low-confidence
remasking).  A session's next block holds ``block_length`` positions; prompt
tokens that fall into it stay fixed and the others start as
``mask_token_id``.  A *denoising pass* runs the block through the model: its
rows attend to the committed cache and to the whole block, and the logits at a
position predict that position's token (no shift).  At each masked position
the confidence is the largest softmax probability and the candidate its
argmax; the ``k_s`` most confident masked positions take their candidates,
``k_s`` from the schedule that spreads the block's masks evenly over
``denoising_steps`` passes, the remainder to the first passes.  After the last
pass a *commit pass* runs the finished block once more and writes its keys and
values (those of the denoising passes were made from mask embeddings): it
needs no head, and of the last layer nothing but its keys and values.  A block
costs ``denoising_steps + 1`` passes, and then the next block starts.

A :class:`~heat_tpu.models.session.DecodeSession` serves it, the class that
serves every model here: ``SDAR(cfg).session(batch, max_context)``, then
``prefill(tokens, lengths)`` (each session's whole blocks of its prompt, in
programs of :data:`PREFILL_ROWS` token rows; the prompt's tail waits in the
first block), ``decode(blocks)`` (a ``lax.scan`` over blocks, the passes of a
block inside it, the caches updated in place, one readback) and ``save`` /
``rewind`` (each session's position and its pending block).  Weights and
caches are ``cfg.dtype`` (bfloat16) and every product accumulates in float32;
the stream, norms, the rotary embedding, softmax and the router are float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import telemetry
from ..ops.decode_attention import decode_attention, masked_attention
from ..parallel.expert import held_experts_sum, softmax_topk_routing
from ._lm import (dot as _dot, embed as _embed, greedy as _greedy, init_tree,
                  rms_norm as _rms_norm, rope_half, spec_size)
from .session import DecodeSession, tree_bytes

__all__ = ["SDAR", "SDARConfig"]

_F32 = jnp.float32

# token rows (sessions x positions) one prefill program walks: a chunk of this
# many positions of one session, or as many sessions' shorter prompts
PREFILL_ROWS = 2048
# keys a grid step of the stream kernel folds; the cache holds whole blocks of them
ATTN_BLOCK = 2048
# keys a step of the prefill's masked attention scores at once
PREFILL_KEY_BLOCK = 1024


@dataclasses.dataclass(frozen=True)
class SDARConfig:
    """Sizes of one model.  The defaults are SDAR-30B-A3B-Chat's
    ``config.json``; ``block_length``, ``denoising_steps`` and
    ``mask_token_id`` are what it leaves to the generate loop (the
    configuration file's ``assumed`` says why each)."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    moe_intermediate_size: int = 768
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    max_position_embeddings: int = 32768
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    use_sliding_window: bool = False
    block_length: int = 4
    denoising_steps: int = 2
    mask_token_id: int = 151669
    dtype: str = "bfloat16"

    def __post_init__(self):
        object.__setattr__(self, "mlp_only_layers", tuple(self.mlp_only_layers))
        if self.tie_word_embeddings or self.attention_bias or self.use_sliding_window:
            raise ValueError("SDAR has an untied head, no bias and no sliding window")
        if not self.norm_topk_prob:
            raise ValueError("the chosen experts' weights are renormalised (norm_topk_prob)")
        if self.decoder_sparse_step != 1 or self.mlp_only_layers:
            raise ValueError("every layer is an expert layer (decoder_sparse_step 1, "
                             "no mlp_only_layers)")
        if self.num_attention_heads % self.num_key_value_heads or self.head_dim % 2:
            raise ValueError("a key/value head serves a whole number of query heads, "
                             "and the rotary embedding pairs the halves of a head")
        if not 1 <= self.denoising_steps <= self.block_length:
            raise ValueError("a block's masks are spread over 1 .. block_length passes")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError("the mask id is a row of the embedding")

    @classmethod
    def from_dict(cls, published: dict, **assumed) -> "SDARConfig":
        """From a published ``config.json`` (keys it does not know are
        ignored; ``rope_scaling`` must be null and ``sliding_window`` unused)
        and what the publication leaves to the generate loop: ``block_length``,
        ``denoising_steps``, ``mask_token_id``, a cut in depth."""
        if published.get("rope_scaling") is not None:
            raise ValueError("SDAR's rotary embedding is the plain one (rope_scaling null)")
        names = {f.name for f in dataclasses.fields(cls)}
        known = {k: v for k, v in published.items() if k in names}
        known.update(assumed)
        return cls(**known)

    q_width = property(lambda self: self.num_attention_heads * self.head_dim)
    kv_width = property(lambda self: self.num_key_value_heads * self.head_dim)
    group = property(lambda self: self.num_attention_heads // self.num_key_value_heads)
    # bytes a position holds in one layer's cache: its key and its value
    row_bytes = property(lambda self: 2 * self.kv_width * jnp.dtype(self.dtype).itemsize)


# ------------------------------------------------------------------ parameters

def param_spec(cfg: SDARConfig) -> dict:
    """The parameter tree as ``(shape, init)`` leaves, from shapes alone."""
    d, f, hd, experts = cfg.hidden_size, cfg.moe_intermediate_size, cfg.head_dim, cfg.num_experts
    out = 1.0 / math.sqrt(2.0 * cfg.num_hidden_layers)
    norm = {"w": ((d,), "ones")}
    layer = {
        "norm1": norm,
        "attn": {"w_q": ((d, cfg.q_width), d ** -0.5), "w_k": ((d, cfg.kv_width), d ** -0.5),
                 "w_v": ((d, cfg.kv_width), d ** -0.5),
                 "q_norm": ((hd,), "ones"), "k_norm": ((hd,), "ones"),
                 "w_o": ((cfg.q_width, d), out * cfg.q_width ** -0.5)},
        "norm2": norm,
        "moe": {"router": ((d, experts), d ** -0.5),
                "experts": {"w_gate": ((experts, d, f), d ** -0.5),
                            "w_up": ((experts, d, f), d ** -0.5),
                            "w_down": ((experts, f, d), out * f ** -0.5)}},
    }
    return {"embed": ((cfg.vocab_size, d), 1.0), "layers": [layer] * cfg.num_hidden_layers,
            "final_norm": norm, "head": ((cfg.vocab_size, d), d ** -0.5)}


def param_count(cfg: SDARConfig) -> dict:
    """Parameters of an attention block, of one expert, of a layer (every
    expert), of the embedding and head and of the whole model; shapes only."""
    spec = param_spec(cfg)
    return {"attention": spec_size(spec["layers"][0]["attn"]),
            "expert": 3 * cfg.hidden_size * cfg.moe_intermediate_size,
            "layer": spec_size(spec["layers"][0]),
            "embed_head": spec_size(spec["embed"]) + spec_size(spec["head"]),
            "total": spec_size(spec)}


def init_params(cfg: SDARConfig, key, sharding=None) -> dict:
    """Seeded parameters, made on the device leaf by leaf as the other served
    models' are: matrices ``N(0, 1/fan_in)``, those that write into the
    residual stream scaled by ``1/sqrt(2 L)`` besides, norms at one."""
    return init_tree(param_spec(cfg), jnp.dtype(cfg.dtype), key, sharding)


# ---------------------------------------------------------------------- layers

def _qkv(cfg, p, u, positions):
    """Queries ``(batch, seq, heads, head_dim)`` and the keys and values to
    store ``(batch, key/value heads, seq, head_dim)`` of the normed stream
    ``u`` of ``(batch, seq, d)``: ``q`` and ``k`` normed a head and turned."""
    batch, seq = u.shape[:2]
    hd, dtype = cfg.head_dim, jnp.dtype(cfg.dtype)
    q = _dot(u, p["w_q"]).reshape(batch, seq, cfg.num_attention_heads, hd)
    k = _dot(u, p["w_k"]).reshape(batch, seq, cfg.num_key_value_heads, hd)
    v = _dot(u, p["w_v"]).reshape(batch, seq, cfg.num_key_value_heads, hd)
    q = rope_half(_rms_norm(q, p["q_norm"], cfg.rms_norm_eps), positions, cfg.rope_theta)
    k = rope_half(_rms_norm(k, p["k_norm"], cfg.rms_norm_eps), positions, cfg.rope_theta)
    return q, jnp.moveaxis(k, 1, 2).astype(dtype), jnp.moveaxis(v, 1, 2).astype(dtype)


def _query_rows(cfg, q):
    """``(batch, seq, heads, hd)`` to the rows a key/value head reads,
    ``(batch, kv heads, seq x group, hd)``: position-major, query head ``h``
    in the group ``h // group`` (Qwen3's ``repeat_kv``)."""
    batch, seq = q.shape[:2]
    rows = q.reshape(batch, seq, cfg.num_key_value_heads, cfg.group, cfg.head_dim)
    return jnp.moveaxis(rows, 1, 2).reshape(batch, cfg.num_key_value_heads, -1, cfg.head_dim)


def _attention_out(cfg, p, att, seq):
    """The rows' outputs back to ``(batch, seq, d)`` through ``W_o``."""
    batch = att.shape[0]
    o = att.reshape(batch, cfg.num_key_value_heads, seq, cfg.group, cfg.head_dim)
    return _dot(jnp.moveaxis(o, 2, 1).reshape(batch, seq, cfg.q_width), p["w_o"])


def _block_attention(cfg, q, k_cache, v_cache, pos):
    """A block's query rows ``(batch, seq, heads, hd)``, session ``b``'s at
    ``pos[b] ..``, against its cache up to the block's end: every row of a
    block sees the whole block and everything before it (the stream kernel,
    each session at its own length)."""
    seq = q.shape[1]
    block = min(ATTN_BLOCK, k_cache.shape[2])
    return decode_attention(_query_rows(cfg, q).astype(k_cache.dtype), k_cache, v_cache,
                            pos + seq, scale=cfg.head_dim ** -0.5, block=block)


def _write_block(cache, rows, pos):
    """``rows`` ``(batch, kv heads, B, hd)`` into the slots ``pos[b] .. pos[b]
    + B - 1`` of each session."""
    batch, heads, width = rows.shape[:3]
    b = jnp.arange(batch, dtype=jnp.int32)[:, None, None]
    g = jnp.arange(heads, dtype=jnp.int32)[None, :, None]
    t = (pos[:, None] + jnp.arange(width, dtype=jnp.int32))[:, None, :]
    return cache.at[b, g, t].set(rows, unique_indices=True)


def _moe(cfg, p, x):
    """``x + MoE(RMSNorm(x))`` on the stream ``(batch, seq, d)``."""
    with jax.named_scope("ht.lm.moe"):
        h = _rms_norm(x, p["norm2"]["w"], cfg.rms_norm_eps).reshape(-1, cfg.hidden_size)
        weights, chosen = softmax_topk_routing(h, p["moe"]["router"],
                                               top_k=cfg.num_experts_per_tok)
        routed, _ = held_experts_sum(h, weights, chosen, p["moe"]["experts"],
                                     held=(0, cfg.num_experts))
        return x + routed.reshape(x.shape)


def _block_layers(cfg, params, shared, tokens, pos, *, commit: bool):
    """A block of ``(batch, B)`` tokens, session ``b``'s at ``pos[b] ..``,
    through every layer: each layer writes the block's keys and values, then
    reads.  ``commit``: a commit pass, which stops at the last layer's keys
    and values (its output reaches nothing).  Returns the caches and the
    stream ``(batch, B, d)`` (None of a commit)."""
    width = tokens.shape[1]
    positions = pos[:, None] + jnp.arange(width, dtype=jnp.int32)
    ks, vs = list(shared["k"]), list(shared["v"])
    x = _embed(params, tokens)
    for i, p in enumerate(params["layers"]):
        with jax.named_scope("ht.lm.attn"):
            q, k, v = _qkv(cfg, p["attn"], _rms_norm(x, p["norm1"]["w"], cfg.rms_norm_eps),
                           positions)
            ks[i], vs[i] = _write_block(ks[i], k, pos), _write_block(vs[i], v, pos)
        if commit and i == len(params["layers"]) - 1:
            return {"k": tuple(ks), "v": tuple(vs)}, None
        with jax.named_scope("ht.lm.block_attn"):
            att = _block_attention(cfg, q, ks[i], vs[i], pos)
        with jax.named_scope("ht.lm.attn"):
            x = x + _attention_out(cfg, p["attn"], att, width)
        x = _moe(cfg, p, x)
    return {"k": tuple(ks), "v": tuple(vs)}, x


def _commit(cfg, params, shared, tokens, pos):
    """The commit pass: the finished block's own keys and values into every
    layer's cache."""
    return _block_layers(cfg, params, shared, tokens, pos, commit=True)[0]


def _logits(cfg, params, x):
    """Float32 logits ``(batch, B, vocab)`` of the stream ``(batch, B, d)``,
    and their argmax, the candidates."""
    batch, width, d = x.shape
    with jax.named_scope("ht.lm.head"):
        candidates, logits = _greedy(
            _rms_norm(x, params["final_norm"]["w"], cfg.rms_norm_eps).reshape(-1, d),
            params["head"])
    return candidates.reshape(batch, width), logits.reshape(batch, width, -1)


def _unmask(tokens, fixed, candidates, logits, count):
    """One pass's choice: the confidence of every position (the largest
    softmax probability; ``-inf`` where the position is already fixed), and
    the ``count[b]`` most confident masked positions of session ``b`` take
    their candidates (of equal confidences the earlier position first).
    Returns ``(tokens, fixed, taken, confidence)``."""
    top = jnp.max(logits, axis=-1)
    confidence = 1.0 / jnp.sum(jnp.exp(logits - top[..., None]), axis=-1)
    confidence = jnp.where(fixed, -jnp.inf, confidence)
    width = tokens.shape[1]
    at = jnp.arange(width, dtype=jnp.int32)
    mine, other = confidence[:, :, None], confidence[:, None, :]
    ahead = (other > mine) | ((other == mine) & (at[None, None, :] < at[None, :, None]))
    rank = jnp.sum(ahead, axis=-1, dtype=jnp.int32)
    taken = ~fixed & (rank < count[:, None])
    return jnp.where(taken, candidates, tokens), fixed | taken, taken, confidence


def _pass_counts(masks, steps: int, s):
    """Positions pass ``s`` fixes of ``masks`` spread over ``steps`` passes,
    the remainder to the first (``get_num_transfer_tokens``)."""
    return masks // steps + (s < masks % steps).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
@telemetry.module_name("ht_lm_block_prefill_chunk")
def _prefill_chunk(cfg, params, shared, tokens, first, pos0):
    """``tokens`` of ``(sessions, chunk)`` (whole blocks), the sessions ``first
    ..`` at the positions ``pos0 ..``, through every layer under the
    block-causal mask; their keys and values into the caches."""
    sessions, chunk = tokens.shape
    width = cfg.block_length
    positions = pos0 + jnp.arange(chunk, dtype=jnp.int32)
    # a row sees its whole block: its position is the block's last
    q_pos = jnp.repeat(positions // width * width + width - 1, cfg.group)
    ks, vs = list(shared["k"]), list(shared["v"])
    x = _embed(params, tokens)
    zero = jnp.int32(0)
    for i, p in enumerate(params["layers"]):
        with jax.named_scope("ht.lm.attn"):
            q, k, v = _qkv(cfg, p["attn"], _rms_norm(x, p["norm1"]["w"], cfg.rms_norm_eps),
                           positions)
            ks[i] = jax.lax.dynamic_update_slice(ks[i], k, (first, zero, pos0, zero))
            vs[i] = jax.lax.dynamic_update_slice(vs[i], v, (first, zero, pos0, zero))
            mine_k = jax.lax.dynamic_slice_in_dim(ks[i], first, sessions)
            mine_v = jax.lax.dynamic_slice_in_dim(vs[i], first, sessions)
            slots = mine_k.shape[2]
            att = masked_attention(
                _query_rows(cfg, q).astype(mine_k.dtype), mine_k, mine_v, q_pos,
                jnp.arange(slots, dtype=jnp.int32), scale=cfg.head_dim ** -0.5,
                kv_len=pos0 + chunk, block=min(PREFILL_KEY_BLOCK, slots))
            x = x + _attention_out(cfg, p["attn"], att, chunk)
        x = _moe(cfg, p, x)
    return {"k": tuple(ks), "v": tuple(vs)}


@functools.partial(jax.jit, static_argnames=("cfg", "blocks"), donate_argnums=(2, 3))
@telemetry.module_name("ht_lm_block_decode")
def _decode_blocks(cfg, params, shared, pending, pos, *, blocks):
    """``blocks`` blocks of every session, session ``b``'s first at
    ``pos[b]``: ``denoising_steps`` passes that fix positions by confidence,
    then the commit pass.  ``pending`` is the first block ``(tokens, fixed)``
    (a prompt's tail fixed in it).

    Returns the caches, the next pending block, the tokens ``(batch, blocks x
    B)`` (-1 where a prompt token sat), the logits of the pass that fixed each
    position ``(batch, blocks x B, vocab)`` (zero at a prompt's), each pass's
    confidence ``(batch, blocks, passes, B)`` and the device's count of the
    positions the passes fixed."""
    width, steps, mask = cfg.block_length, cfg.denoising_steps, cfg.mask_token_id
    batch = pos.shape[0]

    def one_pass(carry, s):
        shared, tokens, fixed, chosen, masks, pos = carry
        shared, x = _block_layers(cfg, params, shared, tokens, pos, commit=False)
        candidates, logits = _logits(cfg, params, x)
        with jax.named_scope("ht.lm.denoise"):
            tokens, fixed, taken, confidence = _unmask(
                tokens, fixed, candidates, logits, _pass_counts(masks, steps, s))
            chosen = jnp.where(taken[..., None], logits, chosen)
        return (shared, tokens, fixed, chosen, masks, pos), confidence

    def one_block(carry, _):
        shared, tokens, fixed, pos, unmasked = carry
        prompt = fixed
        masks = width - jnp.sum(fixed, axis=1, dtype=jnp.int32)
        chosen = jnp.zeros(tokens.shape + (params["head"].shape[0],), _F32)
        (shared, tokens, fixed, chosen, _, _), confidence = jax.lax.scan(
            one_pass, (shared, tokens, fixed, chosen, masks, pos),
            jnp.arange(steps, dtype=jnp.int32))
        with jax.named_scope("ht.lm.commit"):
            shared = _commit(cfg, params, shared, tokens, pos)
        unmasked = unmasked + jnp.sum(fixed & ~prompt, dtype=jnp.int32)
        made = jnp.where(prompt, -1, tokens)
        fresh = (jnp.full((batch, width), mask, jnp.int32), jnp.zeros((batch, width), bool))
        return (shared, *fresh, pos + width, unmasked), (made, chosen, jnp.moveaxis(confidence, 0, 1))

    tokens, fixed = pending
    (shared, tokens, fixed, _, unmasked), (made, chosen, confidence) = jax.lax.scan(
        one_block, (shared, tokens, fixed, pos, jnp.int32(0)), None, length=blocks)
    vocab = chosen.shape[-1]
    return (shared, (tokens, fixed), jnp.moveaxis(made, 0, 1).reshape(batch, blocks * width),
            jnp.moveaxis(chosen, 0, 1).reshape(batch, blocks * width, vocab),
            jnp.moveaxis(confidence, 0, 1), {"tokens_unmasked": unmasked})


# ----------------------------------------------------------------------- model

class SDAR:
    """The model: a configuration and its parameters.

    ``SDAR(cfg)`` draws seeded parameters (:func:`init_params`); ``SDAR(cfg,
    params)`` takes a tree of the same layout.  Serving goes through
    :meth:`session`."""

    def __init__(self, cfg: SDARConfig, params: Optional[dict] = None, *, seed: int = 0,
                 comm=None):
        from ..parallel.mesh import get_comm

        self.cfg = cfg
        self.comm = comm or get_comm()
        self._placement = self.comm.replicated(0)
        if params is None:
            params = init_params(cfg, jax.random.key(seed), self._placement)
        self.params = params

    def session(self, batch: int, max_context: int) -> DecodeSession:
        return DecodeSession(self, batch, max_context)

    prefill_chunk = property(lambda self: PREFILL_ROWS)
    # positions a decode step (a block) generates a session
    block_length = property(lambda self: self.cfg.block_length)

    def serve_cache(self, batch: int, max_context: int):
        """All of the cache grows with the context: a key and a value a
        position, key/value head and layer, ``(batch, kv heads, capacity,
        head_dim)`` a layer, in whole blocks of :data:`ATTN_BLOCK` positions
        (a context shorter than one: a power of two).  No constant-size
        state: a snapshot copies nothing but the pending blocks."""
        cfg = self.cfg
        if max_context > cfg.max_position_embeddings:
            raise ValueError(f"{max_context} positions pass the model's "
                             f"{cfg.max_position_embeddings}")
        block = min(ATTN_BLOCK, 1 << max(4, (max_context - 1).bit_length()))
        capacity = -(-max_context // block) * block
        shape = (batch, cfg.num_key_value_heads, capacity, cfg.head_dim)

        def zeros():
            return tuple(jnp.zeros(shape, jnp.dtype(cfg.dtype), device=self._placement)
                         for _ in range(cfg.num_hidden_layers))

        return capacity, {"k": zeros(), "v": zeros()}, ()

    def serve_bytes(self, shared, state) -> dict:
        return {"shared": tree_bytes(shared)}

    def serve_prefill(self, shared, state, ids, position: int, lengths):
        """Session ``b``'s prompt is ``ids[b, :lengths[b]]``: its whole blocks
        are prefilled, in programs of ``chunk`` positions of a group of
        sessions (the last starts ``chunk`` before the group's furthest end;
        a position written past a session's own end is written again before
        anything reads it), and its tail waits in the first block.  Returns
        the pending block ``(tokens, fixed)`` and no logits."""
        cfg, params = self.cfg, self.params
        width = cfg.block_length
        batch, n = int(ids.shape[0]), int(ids.shape[1])
        lengths = np.asarray(lengths)
        whole = lengths - lengths % width
        furthest = int(whole.max())
        if furthest:
            chunk = min(furthest, PREFILL_ROWS - PREFILL_ROWS % width)
            group = max(1, min(batch, PREFILL_ROWS // chunk))
            starts = list(range(0, furthest - chunk + 1, chunk))
            if starts[-1] + chunk < furthest:
                starts.append(furthest - chunk)
            for first in range(0, batch, group):
                rows = slice(first, first + group)
                for start in starts:
                    if start >= int(whole[rows].max()):
                        continue
                    shared = _prefill_chunk(cfg, params, shared, ids[rows, start:start + chunk],
                                            np.int32(first), np.int32(position + start))
        at = jnp.asarray(whole[:, None] + np.arange(width), jnp.int32)
        fixed = jnp.asarray(at < lengths[:, None])
        tail = jnp.take_along_axis(ids, jnp.minimum(at, n - 1), axis=1)
        pending = jax.device_put((jnp.where(fixed, tail, cfg.mask_token_id).astype(jnp.int32),
                                  fixed), self._placement)
        return shared, state, pending, None

    def serve_notes(self, session: DecodeSession, blocks: int):
        """The ``lm.decode`` span's shapes, and what a call adds to the ``lm``
        counters from the positions alone: the passes, and the key/value rows
        the stream read (layers x sessions x visible rows, every pass; a
        commit reads with every layer but the last)."""
        cfg = self.cfg
        width, steps, layers = cfg.block_length, cfg.denoising_steps, cfg.num_hidden_layers
        ends = session.positions[:, None] + width * np.arange(1, blocks + 1)
        streamed = int((steps * layers + layers - 1) * ends.sum())
        itemsize = jnp.dtype(cfg.dtype).itemsize
        notes = dict(batch=session.batch, context=session.position,
                     first=int(session.positions.min()), blocks=blocks, block_length=width,
                     denoising_steps=steps, layers=layers, row_bytes=cfg.row_bytes,
                     kv_rows_streamed=streamed, experts=cfg.num_experts,
                     expert_bytes=3 * cfg.hidden_size * cfg.moe_intermediate_size * itemsize)
        return notes, {"denoise_passes": steps * blocks, "commit_passes": blocks,
                       "kv_rows_streamed": streamed}

    def serve_decode(self, shared, state, pending, positions, blocks: int):
        """``blocks`` blocks; returns as ``chosen`` the tokens and each pass's
        confidence."""
        shared, pending, tokens, logits, confidence, counted = _decode_blocks(
            self.cfg, self.params, shared, pending, jnp.asarray(positions, jnp.int32),
            blocks=blocks)
        return shared, state, pending, (tokens, confidence), logits, counted
