"""DeepSeek-V3.2 as one chip's share of an expert-parallel deployment: latent
attention (MLA) read through a learned sparse selection, and sigmoid-routed
experts of which this chip holds some, beside a shared expert.  The same
module serves GLM-4.7-Flash (``model_type`` ``glm4_moe_lite``: DeepSeek-V3's
layer with no selection, one routing group, plain RoPE) with its
multi-token-prediction module drafting.

The architecture (``config.json`` of deepseek-ai/DeepSeek-V3.2; the layers'
equations are written out in ``perf/reference/deepseek.py``): pre-norm
residual layers ``x += Attention(RMSNorm(x))``, then ``x += MLP(RMSNorm(x))``
in the first ``first_k_dense_replace`` layers and ``x += Shared(h) + sum over
the chosen experts g_e Expert_e(h)`` in the others; a final RMSNorm and an
untied head.

- **Latent attention.**  A token leaves ``kv_lora_rank + qk_rope_head_dim``
  numbers a layer in the cache (the normed low-rank projection ``c_kv`` and
  one rotary key shared by all heads), read by every head as key and value.
  A prefill chunk computes keys and values a head from those rows (the
  published form; on a TPU one Pallas kernel, ``ht_sparse_prefill_attn``); a
  decode step folds ``W_uk`` into the query and ``W_uv`` into the output and
  attends in the latent space (:mod:`heat_tpu.ops.latent_attention`).
- **The selection.**  A second cache holds one index key a token and layer;
  ``index_n_heads`` small heads score every visible position and a query
  reads only the ``index_topk`` positions of largest score: an exact top-k at
  a decode step, a threshold at the k-th largest score a row in a prefill
  chunk.
- **Experts held as a share.**  The router is over all ``n_routed_experts``;
  ``experts_held = (first, count)`` says which consecutive experts lie here,
  and the layer adds their part of the routed sum to the shared expert's
  (:func:`heat_tpu.parallel.expert.held_experts_ffn`; no token is dropped, no
  exchange is run and nothing stands in for the absent chips).  Likewise
  ``vocab_held``: the rows of the embedding and of the head that lie here;
  token ids are drawn from, and chosen among, that slice.
- **No selection** (``index_topk`` 0: a configuration without an indexer).
  A decode step reads every visible latent row of its session
  (:mod:`heat_tpu.ops.latent_stream`, each session at its own position), a
  prefill chunk every slot up to each query (the prefill kernel's causal
  form, which reads no scores).
- **The MTP module** (``num_nextn_predict_layers`` 1; DeepSeek-V3 report
  section 2.2): ``h'_i = W_eh [RMSNorm(Emb(t_{i+1})) ; RMSNorm(h_i)]``, ``h_i``
  the main model's stream after its final norm, one decoder layer with its
  own latent cache, a norm and the shared head.  It drafts: a decode step
  feeds each session its pending token and the draft, checks both
  positions and emits one token or two (:func:`_decode_drafted`); the
  prefill fills the module's cache over the prompt shifted by one token.

A :class:`~heat_tpu.models.session.DecodeSession` serves it, the class that
serves every model here: ``DeepSeek(cfg).session(batch, max_context)``, then
``prefill`` (programs of :data:`PREFILL_ROWS` token rows: a chunk of one
session's positions, or several sessions' short prompts), ``decode`` (greedy
steps as one ``lax.scan``, the caches updated in place, one readback) and
``save`` / ``rewind``.  All of this model's cache grows with the context, so
a snapshot is the positions and the pending tokens (and drafts): rows past a
saved position are simply overwritten later.  Weights and activations entering a matrix
product are ``cfg.dtype`` (bfloat16) with float32 accumulation, the caches are
``cfg.dtype``; the stream, norms, the rotary embedding, softmax, the index
scores' sum over heads and the router are float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import telemetry
from ..ops.latent_attention import (KEY_BLOCK, ROPE_PACK, index_scores_chunk, key_blocks,
                                    kth_largest, latent_decode_attention,
                                    latent_prefill_attention, prefill_form, read_form,
                                    selection_form, sparse_select)
from ..ops.latent_stream import latent_stream_attention, stream_form
from ..parallel.expert import held_experts_ffn
from ._lm import (dot as _dot, embed as _embed, gated_mlp as _gated_mlp, greedy as _greedy,
                  init_tree, rms_norm as _rms_norm, spec_size, yarn_frequencies)
from .session import DecodeSession, tree_bytes

__all__ = ["DeepSeek", "DeepSeekConfig"]

_F32 = jnp.float32
_ZERO = np.int32(0)

# token rows (sessions x positions) one prefill program walks: a chunk of this
# many positions of one session, or as many sessions' shorter prompts
PREFILL_ROWS = 2048


@dataclasses.dataclass(frozen=True)
class DeepSeekConfig:
    """Sizes of one model.  The defaults are DeepSeek-V3.2's ``config.json``;
    ``experts_held``, ``vocab_held`` say what of it lies on this chip (all of
    it by default), ``layer_norm_eps`` is the index keys' LayerNorm's."""

    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_hidden_layers: int = 61
    first_k_dense_replace: int = 3
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    n_routed_experts: int = 256
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    rms_norm_eps: float = 1e-6
    layer_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    max_position_embeddings: int = 163840
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    num_nextn_predict_layers: int = 0
    experts_held: Optional[Tuple[int, int]] = None
    vocab_held: Optional[int] = None
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.experts_held is None:
            object.__setattr__(self, "experts_held", (0, self.n_routed_experts))
        else:
            object.__setattr__(self, "experts_held", tuple(int(v) for v in self.experts_held))
        if self.vocab_held is None:
            object.__setattr__(self, "vocab_held", self.vocab_size)
        first, count = self.experts_held
        if first < 0 or count < 1 or first + count > self.n_routed_experts:
            raise ValueError(f"experts {first} .. {first + count} are not among the "
                             f"router's {self.n_routed_experts}")
        if not 0 < self.vocab_held <= self.vocab_size:
            raise ValueError("the vocabulary held is a slice of the vocabulary")
        if self.scoring_func != "sigmoid" or not self.norm_topk_prob or self.n_shared_experts != 1:
            raise ValueError("the router is sigmoid-scored with normalised weights, "
                             "beside one shared expert")
        if self.tie_word_embeddings or self.attention_bias:
            raise ValueError("DeepSeek-V3.2 has an untied head and no bias in attention")
        if self.n_routed_experts % self.n_group or self.qk_rope_head_dim % 2:
            raise ValueError("groups hold equal numbers of experts, and the rotary "
                             "embedding pairs lanes")
        if self.sparse and self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError("the index key's rotary lanes are its first qk_rope_head_dim")
        if self.index_topk < 0 or self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("index_topk 0 reads every visible row; one MTP module or none")

    @classmethod
    def from_dict(cls, published: dict, **assumed) -> "DeepSeekConfig":
        """From a published ``config.json`` (keys it does not know are
        ignored; ``rope_scaling`` is read key by key, and ``null`` is the plain
        embedding; a configuration without ``index_topk`` has no indexer) and
        what the publication leaves to the deployment: ``experts_held``,
        ``vocab_held``, a cut in depth."""
        names = {f.name for f in dataclasses.fields(cls)}
        known = {k: v for k, v in published.items() if k in names}
        if "index_topk" not in published:
            known["index_topk"] = 0
        if "rope_scaling" in published and published["rope_scaling"] is None:
            known["rope_factor"] = 1.0
        scaling = published.get("rope_scaling") or {}
        for key, name in (("factor", "rope_factor"), ("beta_fast", "rope_beta_fast"),
                          ("original_max_position_embeddings", "rope_original"),
                          ("beta_slow", "rope_beta_slow"), ("mscale", "rope_mscale"),
                          ("mscale_all_dim", "rope_mscale_all_dim")):
            if key in scaling:
                known[name] = scaling[key]
        known.update(assumed)
        return cls(**known)

    qk_head_dim = property(lambda self: self.qk_nope_head_dim + self.qk_rope_head_dim)
    latent_width = property(lambda self: self.kv_lora_rank + self.qk_rope_head_dim)
    moe_layers = property(lambda self: max(0, self.num_hidden_layers - self.first_k_dense_replace))
    # a learned selection, or every visible row
    sparse = property(lambda self: self.index_topk > 0)
    # layers that hold a latent cache: the main layers, then the MTP module's
    cached_layers = property(lambda self: self.num_hidden_layers + self.num_nextn_predict_layers)

    @property
    def softmax_scale(self) -> float:
        """``qk_head_dim^-1/2`` times YaRN's ``(0.1 mscale_all_dim ln factor + 1)^2``."""
        m = 1.0
        if float(self.rope_factor) != 1.0:
            m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1.0
        return self.qk_head_dim ** -0.5 * m * m

    def rope_frequencies(self) -> np.ndarray:
        return yarn_frequencies(self.qk_rope_head_dim, self.rope_theta, self.rope_factor,
                                self.rope_original, self.rope_beta_fast, self.rope_beta_slow)


# ------------------------------------------------------------------ parameters

def _attention_spec(cfg: DeepSeekConfig, out: float) -> dict:
    d, heads, rank = cfg.hidden_size, cfg.num_attention_heads, cfg.kv_lora_rank
    q_rank, nope, vd = cfg.q_lora_rank, cfg.qk_nope_head_dim, cfg.v_head_dim
    width = cfg.index_head_dim
    spec = {
        "w_dq": ((d, q_rank), d ** -0.5), "q_norm": ((q_rank,), "ones"),
        "w_uq": ((q_rank, heads * cfg.qk_head_dim), q_rank ** -0.5),
        "w_dkv": ((d, cfg.latent_width), d ** -0.5), "kv_norm": ((rank,), "ones"),
        # W_ukv a head: keys' part (heads, nope, rank), values' part (heads, rank, v)
        "w_uk": ((heads, nope, rank), rank ** -0.5), "w_uv": ((heads, rank, vd), rank ** -0.5),
        "w_o": ((heads * vd, d), out * (heads * vd) ** -0.5),
    }
    if cfg.sparse:
        spec.update({"w_iq": ((q_rank, cfg.index_n_heads * width), q_rank ** -0.5),
                     "w_ik": ((d, width), d ** -0.5),
                     "ik_norm_w": ((width,), "ones"), "ik_norm_b": ((width,), "zeros"),
                     "w_iw": ((d, cfg.index_n_heads), d ** -0.5)})
    return spec


def _mlp_spec(d: int, f: int, out: float, lead=()) -> dict:
    return {"w_gate": (lead + (d, f), d ** -0.5), "w_up": (lead + (d, f), d ** -0.5),
            "w_down": (lead + (f, d), out * f ** -0.5)}


def param_spec(cfg: DeepSeekConfig) -> dict:
    """The parameter tree as ``(shape, init)`` leaves, from shapes alone."""
    d, f = cfg.hidden_size, cfg.moe_intermediate_size
    out = 1.0 / math.sqrt(2.0 * cfg.num_hidden_layers)
    norm = {"w": ((d,), "ones")}

    def layer(i):
        made = {"norm1": norm, "attn": _attention_spec(cfg, out), "norm2": norm}
        if i < cfg.first_k_dense_replace:
            made["mlp"] = _mlp_spec(d, cfg.intermediate_size, out)
        else:
            made["moe"] = {"router": ((d, cfg.n_routed_experts), d ** -0.5),
                           "bias": ((cfg.n_routed_experts,), "zeros"),
                           "shared": _mlp_spec(d, f, out),
                           "experts": _mlp_spec(d, f, out, (cfg.experts_held[1],))}
        return made

    spec = {"embed": ((cfg.vocab_held, d), 1.0),
            "layers": [layer(i) for i in range(cfg.num_hidden_layers)], "final_norm": norm,
            "head": ((cfg.vocab_held, d), d ** -0.5)}
    if cfg.num_nextn_predict_layers:
        # DeepSeek-V3's MTP module: the next token's embedding and the stream normed apart,
        # W_eh over the two side by side, one decoder layer (the index after the main ones),
        # and a norm before the head it shares with the main model, as it shares the embedding
        spec["mtp"] = {"enorm": norm, "hnorm": norm, "eh_proj": ((2 * d, d), (2 * d) ** -0.5),
                       "layer": layer(cfg.num_hidden_layers), "head_norm": norm}
    return spec


def param_count(cfg: DeepSeekConfig) -> dict:
    """Parameters from shapes alone: of an attention block (the indexer
    included), of a dense layer, of an expert layer as held here and with
    every expert of the router, of the embedding, the head, the MTP module
    (0 without one) and the whole of what lies here."""
    spec = param_spec(cfg)
    d, f = cfg.hidden_size, cfg.moe_intermediate_size
    attention = spec_size(_attention_spec(cfg, 1.0))
    expert = 3 * d * f
    outside = attention + 2 * d + d * cfg.n_routed_experts + cfg.n_routed_experts + expert
    return {"attention": attention, "expert": expert,
            "dense_layer": attention + 2 * d + 3 * d * cfg.intermediate_size,
            "expert_layer": outside + cfg.experts_held[1] * expert,
            "expert_layer_uncut": outside + cfg.n_routed_experts * expert,
            "embed": spec_size(spec["embed"]), "head": spec_size(spec["head"]),
            "mtp": spec_size(spec.get("mtp", {})), "total": spec_size(spec)}


def init_params(cfg: DeepSeekConfig, key, sharding=None) -> dict:
    """Seeded parameters, made on the device leaf by leaf as the other served
    models' are: matrices ``N(0, 1/fan_in)``, those that write into the
    residual stream scaled by ``1/sqrt(2 L)`` besides, norms at one, the
    balancing bias at zero."""
    return init_tree(param_spec(cfg), jnp.dtype(cfg.dtype), key, sharding)


# ---------------------------------------------------------------------- layers

def _layer_norm(x, w, b, eps):
    x = x.astype(_F32)
    centred = x - jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(centred), axis=-1, keepdims=True)
    return centred * jax.lax.rsqrt(var + eps) * w.astype(_F32) + b.astype(_F32)


def _turns(cfg, positions, heads_axis: bool):
    """``cos`` and ``sin`` of every position's angles, shaped to multiply
    ``(..., positions, [heads,] rope / 2)``."""
    angle = jnp.asarray(positions, _F32)[..., None] * cfg.rope_frequencies()
    if heads_axis:
        angle = angle[..., None, :]
    return jnp.cos(angle), jnp.sin(angle)


def _rope_interleaved(x, cos, sin):
    """Lanes ``(2i, 2i + 1)`` a pair, as the published attention pairs them."""
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def _rope_half(x, cos, sin):
    """The first ``rope`` lanes of an index query or key, lane ``i`` paired
    with ``i + rope / 2`` as the published indexer pairs them; the lanes past
    them pass."""
    half = cos.shape[-1]
    lo, hi, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin, rest], axis=-1)


def _projections(cfg, p, u, positions):
    """What an attention block makes of the normed stream ``u`` of ``(...,
    positions, d)`` before any cache is read: the queries' two parts, the
    latent row, the rotary key and the index key to store, the index queries
    and the index heads' weights (the last three None without an indexer)."""
    heads, nope, rank = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    lead, dtype = u.shape[:-1], jnp.dtype(cfg.dtype)
    c_q = _rms_norm(_dot(u, p["w_dq"]), p["q_norm"], cfg.rms_norm_eps)
    q = _dot(c_q, p["w_uq"]).reshape(lead + (heads, cfg.qk_head_dim))
    q_pe = _rope_interleaved(q[..., nope:], *_turns(cfg, positions, True))
    kv = _dot(u, p["w_dkv"])
    c_kv = _rms_norm(kv[..., :rank], p["kv_norm"], cfg.rms_norm_eps)
    k_pe = _rope_interleaved(kv[..., rank:], *_turns(cfg, positions, False))
    c_kv, k_pe = c_kv.astype(dtype), k_pe.astype(dtype)
    if not cfg.sparse:
        return q[..., :nope], q_pe, c_kv, k_pe, None, None, None
    with jax.named_scope("ht.lm.sparse_select"):
        q_idx = _dot(c_q, p["w_iq"]).reshape(lead + (cfg.index_n_heads, cfg.index_head_dim))
        q_idx = _rope_half(q_idx, *_turns(cfg, positions, True))
        k_idx = _layer_norm(_dot(u, p["w_ik"]), p["ik_norm_w"], p["ik_norm_b"], cfg.layer_norm_eps)
        k_idx = _rope_half(k_idx, *_turns(cfg, positions, False)).astype(dtype)
        w_idx = _dot(u, p["w_iw"]) * cfg.index_n_heads ** -0.5 * cfg.index_head_dim ** -0.5
    return q[..., :nope], q_pe, c_kv, k_pe, q_idx, k_idx, w_idx


def _attention_step(cfg, p, x, pos, caches):
    """One position a session.  ``x``: ``(batch, d)``; the layer's caches
    ``(latent, rope, index)`` take the position's rows first, then are read.
    Returns the block's output, the caches and the slots the attention read."""
    batch = x.shape[0]
    dtype = jnp.dtype(cfg.dtype)
    latent, rope, index = caches
    with jax.named_scope("ht.lm.latent_attn"):
        u = _rms_norm(x, p["norm1"]["w"], cfg.rms_norm_eps)
        q_nope, q_pe, c_kv, k_pe, q_idx, k_idx, w_idx = _projections(cfg, p["attn"], u, pos)
        latent = jax.lax.dynamic_update_slice(latent, c_kv[:, None], (_ZERO, pos, _ZERO))
        rope = jax.lax.dynamic_update_slice(
            rope, k_pe[:, None],
            (_ZERO, pos // ROPE_PACK, (pos % ROPE_PACK) * cfg.qk_rope_head_dim))
        with jax.named_scope("ht.lm.sparse_select"):
            index = jax.lax.dynamic_update_slice(index, k_idx[:, None], (_ZERO, pos, _ZERO))
            chosen = sparse_select(q_idx, w_idx, index, pos + 1, cfg.index_topk)
        q_lat = jnp.einsum("bhn,hnc->bhc", q_nope.astype(dtype), p["attn"]["w_uk"],
                           preferred_element_type=_F32)
        with jax.named_scope("ht.lm.latent_read"):
            o_lat = latent_decode_attention(q_lat, q_pe, latent, rope, chosen,
                                            cfg.softmax_scale)
        o = jnp.einsum("bhc,hcv->bhv", o_lat.astype(dtype), p["attn"]["w_uv"],
                       preferred_element_type=_F32)
        return _dot(o.reshape(batch, -1), p["attn"]["w_o"]), (latent, rope, index), chosen


def _write_rows(latent, rope, c_kv, k_pe, positions):
    """A step's latent rows and rotary keys into the caches: ``c_kv`` ``(batch,
    Q, rank)`` and ``k_pe`` ``(batch, Q, rope)`` at ``positions`` ``(batch,
    Q)``, each session at its own (two positions of a session may share a row
    of the rope cache, or not: its keys are scattered)."""
    batch, _, width = k_pe.shape
    b = jnp.arange(batch, dtype=jnp.int32)[:, None]
    latent = latent.at[b, positions].set(c_kv, unique_indices=True)
    lanes = ((positions % ROPE_PACK) * width)[..., None] + jnp.arange(width, dtype=jnp.int32)
    rope = rope.at[b[..., None], (positions // ROPE_PACK)[..., None], lanes].set(
        k_pe, unique_indices=True)
    return latent, rope


def _attention_stream(cfg, p, x, pos, caches):
    """``Q`` positions a session, session ``b``'s at ``pos[b] + j``, read
    against every visible row (a model with no selection).  ``x``: ``(batch,
    Q, d)``; the layer's caches ``(latent, rope)`` take the positions' rows
    first, then are read by :func:`latent_stream_attention`."""
    batch, queries = x.shape[:2]
    dtype = jnp.dtype(cfg.dtype)
    latent, rope = caches
    with jax.named_scope("ht.lm.latent_attn"):
        u = _rms_norm(x, p["norm1"]["w"], cfg.rms_norm_eps)
        positions = pos[:, None] + jnp.arange(queries, dtype=jnp.int32)[None, :]
        q_nope, q_pe, c_kv, k_pe, *_ = _projections(cfg, p["attn"], u, positions)
        latent, rope = _write_rows(latent, rope, c_kv, k_pe, positions)
        q_lat = jnp.einsum("bqhn,hnc->bqhc", q_nope.astype(dtype), p["attn"]["w_uk"],
                           preferred_element_type=_F32)
        with jax.named_scope("ht.lm.latent_stream"):
            o_lat = latent_stream_attention(q_lat, q_pe, latent, rope, pos, cfg.softmax_scale)
        o = jnp.einsum("bqhc,hcv->bqhv", o_lat.astype(dtype), p["attn"]["w_uv"],
                       preferred_element_type=_F32)
        return _dot(o.reshape(batch, queries, -1), p["attn"]["w_o"]), (latent, rope)


def _attention_chunk(cfg, p, x, first, pos0, caches):
    """A chunk of positions ``pos0 ..`` of the sessions ``first ..``.  ``x``:
    ``(sessions, chunk, d)``.  Without an indexer a query reads every slot up
    to its own (the prefill attention's causal form, which reads no scores)."""
    sessions, chunk = x.shape[:2]
    latent, rope, index = caches if cfg.sparse else caches + (None,)
    with jax.named_scope("ht.lm.latent_attn"):
        u = _rms_norm(x, p["norm1"]["w"], cfg.rms_norm_eps)
        positions = pos0 + jnp.arange(chunk, dtype=jnp.int32)
        q_nope, q_pe, c_kv, k_pe, q_idx, k_idx, w_idx = _projections(cfg, p["attn"], u, positions)
        latent = jax.lax.dynamic_update_slice(latent, c_kv, (first, pos0, _ZERO))
        if cfg.sparse:
            index = jax.lax.dynamic_update_slice(index, k_idx, (first, pos0, _ZERO))
        # a chunk may begin or end inside a row of the rope cache: its keys are scattered
        lanes = ((positions % ROPE_PACK) * cfg.qk_rope_head_dim)[:, None] + jnp.arange(
            cfg.qk_rope_head_dim, dtype=jnp.int32)
        rope = rope.at[(first + jnp.arange(sessions, dtype=jnp.int32))[:, None, None],
                       (positions // ROPE_PACK)[None, :, None], lanes[None]].set(k_pe)

        def one_session(xs):
            q_nope, q_pe, b, *selecting = xs
            rows = jax.lax.dynamic_index_in_dim(latent, first + b, keepdims=False)
            turned = jax.lax.dynamic_index_in_dim(rope, first + b, keepdims=False)
            scores = cut = None
            if selecting:
                q_idx, w_idx = selecting
                keys = jax.lax.dynamic_index_in_dim(index, first + b, keepdims=False)
                with jax.named_scope("ht.lm.sparse_select"):
                    scores = index_scores_chunk(q_idx, w_idx, keys, pos0)
                    cut = kth_largest(scores, cfg.index_topk)
            with jax.named_scope("ht.lm.latent_read"):
                return latent_prefill_attention(q_nope, q_pe, rows, turned, scores, cut, pos0,
                                                p["attn"]["w_uk"], p["attn"]["w_uv"],
                                                cfg.softmax_scale)

        o = jax.lax.map(one_session, (q_nope, q_pe, jnp.arange(sessions, dtype=jnp.int32))
                        + ((q_idx, w_idx) if cfg.sparse else ()))
        caches = (latent, rope, index) if cfg.sparse else (latent, rope)
        return _dot(o.reshape(sessions, chunk, -1), p["attn"]["w_o"]), caches


def _mlp(cfg, p, x):
    """The layer's second half on token rows ``x`` of ``(tokens, d)``: the
    dense MLP, or the shared expert and the held experts' part of the routed
    sum.  Returns the stream and the routing's counts (None of a dense
    layer)."""
    if "mlp" in p:
        with jax.named_scope("ht.lm.mlp"):
            return x + _gated_mlp(p["mlp"], _rms_norm(x, p["norm2"]["w"], cfg.rms_norm_eps)), None
    with jax.named_scope("ht.lm.moe"):
        h = _rms_norm(x, p["norm2"]["w"], cfg.rms_norm_eps)
        moe = p["moe"]
        routed, counts = held_experts_ffn(
            h, moe["router"], moe["experts"], held=cfg.experts_held,
            top_k=cfg.num_experts_per_tok, n_group=cfg.n_group, topk_group=cfg.topk_group,
            scale=cfg.routed_scaling_factor, bias=moe["bias"])
        return x + _gated_mlp(moe["shared"], h) + routed, counts


def _head(cfg, params, x):
    """Greedy token and float32 logits of ``x`` of ``(batch, d)``, over the
    rows of the vocabulary held."""
    with jax.named_scope("ht.lm.head"):
        return _greedy(_rms_norm(x, params["final_norm"]["w"], cfg.rms_norm_eps), params["head"])


_KINDS = ("latent", "rope", "index")


def _kinds(cfg) -> tuple:
    """The caches a layer holds: latent rows and rotary keys, and index keys
    where there is an indexer."""
    return _KINDS if cfg.sparse else _KINDS[:2]


def _by_layer(shared) -> list:
    """A session's caches as one tuple of kinds a layer (the main layers, then
    the MTP module's)."""
    return [tuple(kind) for kind in zip(*(shared[name] for name in _KINDS if name in shared))]


def _by_kind(cfg, caches) -> dict:
    return {name: tuple(layer[i] for layer in caches) for i, name in enumerate(_kinds(cfg))}


def _counted(counted, counts):
    """The routing's counts of one more expert layer added (None of a dense
    layer)."""
    if counts is None:
        return counted
    return (counted[0] + counts["pairs"], counted[1] + counts["hit"]) + tuple(counted[2:])


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
@telemetry.module_name("ht_lm_sparse_prefill_chunk")
def _prefill_chunk(cfg, params, shared, tokens, first, pos0):
    """``tokens`` of ``(sessions, chunk)``, the sessions ``first ..`` at the
    positions ``pos0 ..``, through every layer.  Returns the caches and the
    stream at the chunk's last position."""
    sessions, chunk = tokens.shape
    caches = _by_layer(shared)
    x = _embed(params, tokens)
    for i, p in enumerate(params["layers"]):
        out, caches[i] = _attention_chunk(cfg, p, x, first, pos0, caches[i])
        x = _mlp(cfg, p, (x + out).reshape(sessions * chunk, -1))[0].reshape(x.shape)
    return _by_kind(cfg, caches), x[:, -1]


@functools.partial(jax.jit, static_argnames=("cfg",))
@telemetry.module_name("ht_lm_sparse_prefill_finish")
def _prefill_finish(cfg, params, x):
    return _head(cfg, params, x)


@functools.partial(jax.jit, static_argnames=("cfg", "steps"), donate_argnums=(2, 3))
@telemetry.module_name("ht_lm_sparse_decode")
def _decode(cfg, params, shared, token, pos, *, steps):
    """``steps`` greedy steps: the token fed at position ``pos + j`` gives the
    logits of that position and, by their argmax, the next token.  Also
    returns the slots the last step's attention read, a layer (none to return
    without a selection: every visible slot is read), and the routing's counts
    summed over steps and expert layers."""
    batch = token.shape[0]
    picked = min(cfg.index_topk, shared["latent"][0].shape[1])

    def step(carry, _):
        shared, token, pos, selected, counted = carry
        caches = _by_layer(shared)
        x, chosen_by_layer = _embed(params, token), []
        for i, p in enumerate(params["layers"]):
            if cfg.sparse:
                out, caches[i], chosen = _attention_step(cfg, p, x, pos, caches[i])
                chosen_by_layer.append(chosen)
            else:
                out, caches[i] = _attention_stream(
                    cfg, p, x[:, None], jnp.full((batch,), pos, jnp.int32), caches[i])
                out = out[:, 0]
            x, counts = _mlp(cfg, p, x + out)
            counted = _counted(counted, counts)
        chosen, logits = _head(cfg, params, x)
        if chosen_by_layer:
            selected = jnp.stack(chosen_by_layer)
        return (_by_kind(cfg, caches), chosen, pos + 1, selected, counted), (chosen, logits)

    start = (shared, token, pos, jnp.full((cfg.num_hidden_layers, batch, picked), -1, jnp.int32),
             (jnp.int32(0), jnp.int32(0)))
    (shared, token, _, selected, (pairs, hit)), (chosen, logits) = jax.lax.scan(
        step, start, None, length=steps)
    return shared, token, chosen.T, jnp.moveaxis(logits, 0, 1), selected, pairs, hit


# ---- the MTP module drafting one token a step

def _mtp_input(cfg, params, h, nexts):
    """The MTP module's rows ``W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]``:
    ``h`` ``(sessions, Q, d)`` the main model's stream after its final norm at
    the positions ``i``, ``nexts`` ``(sessions, Q)`` the tokens at ``i + 1``."""
    m = params["mtp"]
    e = _rms_norm(_embed(params, nexts), m["enorm"]["w"], cfg.rms_norm_eps)
    h = _rms_norm(h, m["hnorm"]["w"], cfg.rms_norm_eps)
    return _dot(jnp.concatenate([e, h], axis=-1), m["eh_proj"])


def _mtp_head(cfg, params, x):
    """The draft and its float32 logits of the MTP layer's output ``x`` of
    ``(rows, d)``: its own norm, then the head it shares."""
    return _greedy(_rms_norm(x, params["mtp"]["head_norm"]["w"], cfg.rms_norm_eps),
                   params["head"])


def _accepted(checked, draft):
    """Greedy acceptance: the draft stands where it is the token the main model
    chose at the pending token's position."""
    return checked == draft


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2, 8, 9))
@telemetry.module_name("ht_lm_mtp_prefill_chunk")
def _prefill_chunk_drafted(cfg, params, shared, tokens, nexts, last, first, pos0, logits,
                           drafted):
    """``tokens`` of ``(sessions, chunk)``, the sessions ``first ..`` at the
    positions ``pos0 ..``, through the main layers, then the MTP layer on the
    rows ``(h_i, t_{i+1})`` (``nexts``: the prompt shifted by one; at a
    session's last prompt position ``last`` the greedy token of its logits).
    ``logits`` and ``drafted`` ``(batch, vocab)``: the first token's and the
    first draft's logits of every session, updated for the sessions whose last
    prompt position lies in the chunk."""
    sessions, chunk = tokens.shape
    main = cfg.num_hidden_layers
    caches = _by_layer(shared)
    x = _embed(params, tokens)
    for i, p in enumerate(params["layers"]):
        out, caches[i] = _attention_chunk(cfg, p, x, first, pos0, caches[i])
        x = _mlp(cfg, p, (x + out).reshape(sessions * chunk, -1))[0].reshape(x.shape)
    at = last - pos0
    inside = (at >= 0) & (at < chunk)
    at = jnp.clip(at, 0, chunk - 1)
    rows = jnp.arange(sessions, dtype=jnp.int32)
    vocab = params["head"].shape[0]
    nothing = (jnp.zeros((sessions,), jnp.int32), jnp.zeros((sessions, vocab), _F32))
    with jax.named_scope("ht.lm.head"):
        h = _rms_norm(x, params["final_norm"]["w"], cfg.rms_norm_eps)
        token, first_logits = jax.lax.cond(
            jnp.any(inside), lambda: _greedy(h[rows, at], params["head"]), lambda: nothing)
    positions = pos0 + jnp.arange(chunk, dtype=jnp.int32)
    nexts = jnp.where(positions[None, :] == last[:, None], token[:, None], nexts)
    with jax.named_scope("ht.lm.mtp"):
        m = params["mtp"]
        x = _mtp_input(cfg, params, h, nexts)
        out, caches[main] = _attention_chunk(cfg, m["layer"], x, first, pos0, caches[main])
        x = _mlp(cfg, m["layer"], (x + out).reshape(sessions * chunk, -1))[0].reshape(x.shape)
        _, draft_logits = jax.lax.cond(
            jnp.any(inside), lambda: _mtp_head(cfg, params, x[rows, at]), lambda: nothing)

    def kept(held, new):
        old = jax.lax.dynamic_slice_in_dim(held, first, sessions)
        return jax.lax.dynamic_update_slice_in_dim(
            held, jnp.where(inside[:, None], new, old), first, axis=0)

    return _by_kind(cfg, caches), kept(logits, first_logits), kept(drafted, draft_logits)


@functools.partial(jax.jit, static_argnames=("cfg", "steps"), donate_argnums=(2, 3))
@telemetry.module_name("ht_lm_mtp_decode")
def _decode_drafted(cfg, params, shared, pending, pos, *, steps):
    """``steps`` speculative steps.  A step feeds session ``b`` its pending
    token at ``pos[b]`` and its draft at ``pos[b] + 1``: the main layers write
    both positions' cache rows and give both positions' logits; their argmax
    ``g1`` is emitted, and where ``g1`` is the draft the second one, ``g2``,
    too, and the session advances by two, else by one (the row of the draft is
    overwritten later).  The MTP module then takes the rows ``(h_pos, g1)`` and
    ``(h_pos+1, g2)``, writes its own cache rows, and drafts from the row of
    the session's last accepted position.

    Returns the caches, the pending ``(token, draft)``, the emitted tokens
    ``(batch, 2 steps)`` (each session's in order, then -1), the acceptance
    ``(batch, steps)``, the drafts fed ``(batch, steps)``, the logits of the
    positions checked ``(batch, steps, 2, vocab)``, the drafts' logits
    ``(batch, steps, vocab)`` and the device's counts: the routing's, the
    drafts accepted, the latent rows the stream read (layers x sessions x
    visible rows, summed over steps)."""
    batch = pos.shape[0]
    main = cfg.num_hidden_layers

    def step(carry, _):
        shared, token, draft, pos, counted = carry
        caches = _by_layer(shared)
        x = _embed(params, jnp.stack([token, draft], axis=1))
        for i, p in enumerate(params["layers"]):
            out, caches[i] = _attention_stream(cfg, p, x, pos, caches[i])
            x, counts = _mlp(cfg, p, (x + out).reshape(2 * batch, -1))
            x = x.reshape(batch, 2, -1)
            counted = _counted(counted, counts)
        with jax.named_scope("ht.lm.head"):
            h = _rms_norm(x, params["final_norm"]["w"], cfg.rms_norm_eps)
            checked, logits = _greedy(h.reshape(2 * batch, -1), params["head"])
        checked, logits = checked.reshape(batch, 2), logits.reshape(batch, 2, -1)
        accept = _accepted(checked[:, 0], draft)
        taken = accept.astype(jnp.int32)
        with jax.named_scope("ht.lm.mtp"):
            m = params["mtp"]
            x = _mtp_input(cfg, params, h, checked)
            out, caches[main] = _attention_stream(cfg, m["layer"], x, pos, caches[main])
            x, counts = _mlp(cfg, m["layer"], (x + out).reshape(2 * batch, -1))
            counted = _counted(counted, counts)
            last = jnp.take_along_axis(x.reshape(batch, 2, -1), taken[:, None, None], axis=1)
            new_draft, draft_logits = _mtp_head(cfg, params, last[:, 0])
        # the rows the full reads visited: each session's last query sees pos + 2 slots
        streamed = cfg.cached_layers * jnp.sum(pos + 2, dtype=jnp.int32)
        counted = counted[:2] + (counted[2] + jnp.sum(taken, dtype=jnp.int32),
                               counted[3] + streamed)
        token = jnp.where(accept, checked[:, 1], checked[:, 0])
        return ((_by_kind(cfg, caches), token, new_draft, pos + 1 + taken, counted),
                (checked, accept, draft, logits, draft_logits))

    zero = jnp.int32(0)
    token, draft = pending
    start = (shared, token, draft, pos, (zero,) * 4)
    (shared, token, draft, _, (pairs, hit, accepted, streamed)), made = jax.lax.scan(
        step, start, None, length=steps)
    checked, accept, fed, logits, draft_logits = made
    taken = accept.astype(jnp.int32)                                   # (steps, batch)
    at = jnp.cumsum(1 + taken, axis=0) - 1 - taken                     # a step's first token's place
    b = jnp.arange(batch, dtype=jnp.int32)[None, :]
    emitted = jnp.full((batch, 2 * steps + 1), -1, jnp.int32)
    emitted = emitted.at[b, at].set(checked[:, :, 0])
    emitted = emitted.at[b, jnp.where(accept, at + 1, 2 * steps)].set(checked[:, :, 1])
    counted = {"expert_pairs": pairs, "experts_hit": hit, "drafts_accepted": accepted,
               "latent_rows_streamed": streamed}
    return (shared, (token, draft), emitted[:, :2 * steps], accept.T, fed.T,
            jnp.moveaxis(logits, 0, 1), jnp.moveaxis(draft_logits, 0, 1), counted)


# ----------------------------------------------------------------------- model

class DeepSeek:
    """The model: a configuration and its parameters.

    ``DeepSeek(cfg)`` draws seeded parameters (:func:`init_params`);
    ``DeepSeek(cfg, params)`` takes a tree of the same layout.  Serving goes
    through :meth:`session`.  ``last_selection`` is what the newest decode
    call's last step read: int32 ``(layers, batch, index_topk)``, the cache
    slots each session's attention was given, ``-1`` where fewer were
    visible (the array the attention itself gathered by, on the device).
    ``last_drafts`` is what the newest decode call of a model that drafts
    (``drafts`` 1: its MTP module) fed and made: ``fed`` ``(batch, steps)``,
    the draft fed at each step, and ``logits`` ``(batch, steps, vocab)``,
    those of the draft each step made."""

    def __init__(self, cfg: DeepSeekConfig, params: Optional[dict] = None, *, seed: int = 0,
                 comm=None):
        from ..parallel.mesh import get_comm

        self.cfg = cfg
        self.comm = comm or get_comm()
        self._placement = self.comm.replicated(0)
        if params is None:
            params = init_params(cfg, jax.random.key(seed), self._placement)
        self.params = params
        self.last_selection = None
        self.last_drafts = None

    def session(self, batch: int, max_context: int) -> DecodeSession:
        return DecodeSession(self, batch, max_context)

    prefill_chunk = property(lambda self: PREFILL_ROWS)
    # tokens a decode step drafts a session: those of the MTP module, or none
    drafts = property(lambda self: self.cfg.num_nextn_predict_layers)

    def serve_cache(self, batch: int, max_context: int):
        """All of the cache grows with the context: a latent row, a rotary key
        (two positions' in a row) and, with an indexer, an index key a
        position, layer (the MTP module's too) and session, in whole blocks of
        :data:`KEY_BLOCK` positions.  There is no constant-size state: a
        snapshot copies nothing but the pending token (and draft)."""
        cfg = self.cfg
        if max_context > cfg.max_position_embeddings:
            raise ValueError(f"{max_context} positions pass the model's "
                             f"{cfg.max_position_embeddings}")
        if cfg.sparse and self.drafts:
            raise ValueError("drafting through a sparse selection is not served: a step of "
                             "two positions reads every visible row (num_nextn_predict_layers=0 "
                             "serves the main model alone)")
        capacity = -(-max_context // KEY_BLOCK) * KEY_BLOCK
        dtype = jnp.dtype(cfg.dtype)

        def zeros(rows, width):
            return tuple(jnp.zeros((batch, rows, width), dtype, device=self._placement)
                         for _ in range(cfg.cached_layers))

        shared = {"latent": zeros(capacity, cfg.kv_lora_rank),
                  "rope": zeros(capacity // ROPE_PACK, ROPE_PACK * cfg.qk_rope_head_dim)}
        if cfg.sparse:
            shared["index"] = zeros(capacity, cfg.index_head_dim)
        return capacity, shared, ()

    def serve_bytes(self, shared, state) -> dict:
        return {"shared": tree_bytes(shared)}

    def _forms(self, chunk, capacity):
        cfg = self.cfg
        widths = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank)
        return prefill_form(chunk, capacity, cfg.num_attention_heads, widths,
                            jnp.dtype(cfg.dtype).itemsize)

    def serve_prefill(self, shared, state, ids, position: int, lengths=None):
        """The prompt in programs of :data:`PREFILL_ROWS` token rows.  Also
        returns the ``lm.prefill`` span's ``attend`` (the form of the chunks'
        attention: ``flash_kernel``, ``blockwise_jnp``, or ``mixed`` where a
        short last chunk differs) and ``prefill_blocks_by_kernel``, the layers x
        key blocks the kernel folded for the sessions.  A model that drafts
        takes each session's prompt length (``lengths``) and leaves the first
        token and the first draft pending (:meth:`_prefill_drafted`)."""
        if self.drafts:
            return self._prefill_drafted(shared, state, ids, position, lengths)
        cfg, params = self.cfg, self.params
        batch, n = int(ids.shape[0]), int(ids.shape[1])
        capacity = int(shared["latent"][0].shape[1])
        chunk = min(n, PREFILL_ROWS)
        group = max(1, min(batch, PREFILL_ROWS // chunk))
        forms, folded = set(), 0
        for start in range(0, n, chunk):
            rows = min(chunk, n - start)
            form = self._forms(rows, capacity)
            forms.add(form)
            if form == "flash_kernel":
                folded += batch * cfg.num_hidden_layers * key_blocks(position + start, rows, capacity)
            last = []
            for first in range(0, batch, group):
                shared, x = _prefill_chunk(
                    cfg, params, shared, ids[first:first + group, start:start + chunk],
                    np.int32(first), np.int32(position + start))
                last.append(x)
        token, logits = _prefill_finish(cfg, params, jnp.concatenate(last))
        told = {"attend": forms.pop() if len(forms) == 1 else "mixed"}
        return shared, state, token, logits, (told, {"prefill_blocks_by_kernel": folded})

    def _prefill_drafted(self, shared, state, ids, position: int, lengths):
        """Session ``b``'s prompt is ``ids[b, :lengths[b]]`` (the rest is
        written and overwritten later).  Every chunk is one program of
        ``chunk`` positions: the last starts ``chunk`` before the longest
        prompt's end (its positions before the previous chunk's end are
        computed again, alike), and a group of sessions skips a chunk wholly
        past its prompts.  The MTP layer fills its cache over the prompt
        shifted by one token, the first token at each session's last position.
        Returns the pending ``(token, draft)`` and the first token's logits."""
        cfg, params = self.cfg, self.params
        batch, n = int(ids.shape[0]), int(ids.shape[1])
        lengths = np.full(batch, n) if lengths is None else np.asarray(lengths)
        capacity = int(shared["latent"][0].shape[1])
        chunk = min(n, PREFILL_ROWS)
        group = max(1, min(batch, PREFILL_ROWS // chunk))
        nexts = jnp.concatenate([ids[:, 1:], ids[:, -1:]], axis=1)
        last = jnp.asarray(position + lengths - 1, jnp.int32)
        starts = list(range(0, n - chunk + 1, chunk))
        if starts[-1] + chunk < n:
            starts.append(n - chunk)
        form = self._forms(chunk, capacity)
        logits = jnp.zeros((batch, cfg.vocab_held), _F32, device=self._placement)
        drafted = jnp.zeros((batch, cfg.vocab_held), _F32, device=self._placement)
        folded = 0
        for start in starts:
            for first in range(0, batch, group):
                rows = slice(first, first + group)
                if start >= int(lengths[rows].max()):
                    continue
                if form == "flash_kernel":
                    folded += (len(lengths[rows]) * cfg.cached_layers
                               * key_blocks(position + start, chunk, capacity))
                window = slice(start, start + chunk)
                shared, logits, drafted = _prefill_chunk_drafted(
                    cfg, params, shared, ids[rows, window], nexts[rows, window], last[rows],
                    np.int32(first), np.int32(position + start), logits, drafted)
        pending = (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                   jnp.argmax(drafted, axis=-1).astype(jnp.int32))
        return shared, state, pending, logits, ({"attend": form},
                                                {"prefill_blocks_by_kernel": folded})

    def serve_notes(self, session: DecodeSession, steps: int):
        cfg = self.cfg
        itemsize = jnp.dtype(cfg.dtype).itemsize
        notes = dict(batch=session.batch, context=session.position, steps=steps,
                     layers=cfg.num_hidden_layers, moe_layers=cfg.moe_layers,
                     latent_bytes=cfg.latent_width * itemsize, experts_held=cfg.experts_held[1],
                     expert_bytes=3 * cfg.hidden_size * cfg.moe_intermediate_size * itemsize)
        if not cfg.sparse:
            queries = 1 + self.drafts
            notes.update(queries=queries, mtp_layers=cfg.num_nextn_predict_layers,
                         first=int(session.positions.min()),
                         stream=stream_form(session.capacity, cfg.kv_lora_rank,
                                            cfg.qk_rope_head_dim))
            return notes, ({"drafts_made": session.batch * steps} if self.drafts else {})
        seen = [session.position + j + 1 for j in range(steps)]
        each = session.batch * cfg.num_hidden_layers
        select = selection_form(session.batch, session.capacity, cfg.index_topk)
        read = read_form(session.batch, session.capacity, min(cfg.index_topk, session.capacity),
                         cfg.kv_lora_rank, cfg.qk_rope_head_dim, itemsize,
                         cfg.num_attention_heads)
        notes.update(select=select, read=read, selected=cfg.index_topk,
                     index_bytes=cfg.index_head_dim * itemsize)
        layer_steps = steps * cfg.num_hidden_layers
        return notes, {"index_keys_scanned": each * sum(seen),
                       "latent_rows_read": each * sum(min(cfg.index_topk, s) for s in seen),
                       "selections_by_cut": layer_steps if select.startswith("cut") else 0,
                       "latent_reads_by_kernel": layer_steps if read == "gather_kernel" else 0}

    def serve_decode(self, shared, state, token, position, steps: int):
        """Greedy steps; a model that drafts takes each session's position and
        returns as ``chosen`` the emitted tokens and the acceptance."""
        if self.drafts:
            shared, token, emitted, accepted, fed, logits, draft_logits, counted = _decode_drafted(
                self.cfg, self.params, shared, token, jnp.asarray(position, jnp.int32),
                steps=steps)
            self.last_drafts = {"fed": fed, "logits": draft_logits}
            return shared, state, token, (emitted, accepted), logits, counted
        shared, token, chosen, logits, selection, pairs, hit = _decode(
            self.cfg, self.params, shared, token, np.int32(position), steps=steps)
        if self.cfg.sparse:
            self.last_selection = selection
        return shared, state, token, chosen, logits, {"expert_pairs": pairs, "experts_hit": hit}
