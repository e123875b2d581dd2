"""DeepSeek-V3.2 as one chip's share of an expert-parallel deployment: latent
attention (MLA) read through a learned sparse selection, and sigmoid-routed
experts of which this chip holds some, beside a shared expert.

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

A :class:`~heat_tpu.models.session.DecodeSession` serves it, the class that
serves every model here: ``DeepSeek(cfg).session(batch, max_context)``, then
``prefill`` (programs of :data:`PREFILL_ROWS` token rows: a chunk of one
session's positions, or several sessions' short prompts), ``decode`` (greedy
steps as one ``lax.scan``, the caches updated in place, one readback) and
``save`` / ``rewind``.  All of this model's cache grows with the context, so
a snapshot is a position and the pending token: rows past a saved position
are simply overwritten later.  Weights and activations entering a matrix
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
                                    latent_prefill_attention, prefill_form, selection_form,
                                    sparse_select)
from ..parallel.expert import held_experts_ffn
from ._lm import (dot as _dot, embed as _embed, gated_mlp as _gated_mlp, greedy as _greedy,
                  init_tree, spec_size, yarn_frequencies)
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
        if self.qk_rope_head_dim > self.index_head_dim:
            raise ValueError("the index key's rotary lanes are its first qk_rope_head_dim")

    @classmethod
    def from_dict(cls, published: dict, **assumed) -> "DeepSeekConfig":
        """From a published ``config.json`` (keys it does not know are
        ignored; ``rope_scaling`` is read key by key) and what the publication
        leaves to the deployment: ``experts_held``, ``vocab_held``, a cut in
        depth."""
        names = {f.name for f in dataclasses.fields(cls)}
        known = {k: v for k, v in published.items() if k in names}
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
    return {
        "w_dq": ((d, q_rank), d ** -0.5), "q_norm": ((q_rank,), "ones"),
        "w_uq": ((q_rank, heads * cfg.qk_head_dim), q_rank ** -0.5),
        "w_dkv": ((d, cfg.latent_width), d ** -0.5), "kv_norm": ((rank,), "ones"),
        # W_ukv a head: keys' part (heads, nope, rank), values' part (heads, rank, v)
        "w_uk": ((heads, nope, rank), rank ** -0.5), "w_uv": ((heads, rank, vd), rank ** -0.5),
        "w_o": ((heads * vd, d), out * (heads * vd) ** -0.5),
        "w_iq": ((q_rank, cfg.index_n_heads * width), q_rank ** -0.5),
        "w_ik": ((d, width), d ** -0.5),
        "ik_norm_w": ((width,), "ones"), "ik_norm_b": ((width,), "zeros"),
        "w_iw": ((d, cfg.index_n_heads), d ** -0.5),
    }


def _mlp_spec(d: int, f: int, out: float, lead=()) -> dict:
    return {"w_gate": (lead + (d, f), d ** -0.5), "w_up": (lead + (d, f), d ** -0.5),
            "w_down": (lead + (f, d), out * f ** -0.5)}


def param_spec(cfg: DeepSeekConfig) -> dict:
    """The parameter tree as ``(shape, init)`` leaves, from shapes alone."""
    d, f = cfg.hidden_size, cfg.moe_intermediate_size
    out = 1.0 / math.sqrt(2.0 * cfg.num_hidden_layers)
    norm = {"w": ((d,), "ones")}
    layers = []
    for i in range(cfg.num_hidden_layers):
        layer = {"norm1": norm, "attn": _attention_spec(cfg, out), "norm2": norm}
        if i < cfg.first_k_dense_replace:
            layer["mlp"] = _mlp_spec(d, cfg.intermediate_size, out)
        else:
            layer["moe"] = {"router": ((d, cfg.n_routed_experts), d ** -0.5),
                            "bias": ((cfg.n_routed_experts,), "zeros"),
                            "shared": _mlp_spec(d, f, out),
                            "experts": _mlp_spec(d, f, out, (cfg.experts_held[1],))}
        layers.append(layer)
    return {"embed": ((cfg.vocab_held, d), 1.0), "layers": layers, "final_norm": norm,
            "head": ((cfg.vocab_held, d), d ** -0.5)}


def param_count(cfg: DeepSeekConfig) -> dict:
    """Parameters from shapes alone: of an attention block (the indexer
    included), of a dense layer, of an expert layer as held here and with
    every expert of the router, of the embedding, the head and the whole of
    what lies here."""
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
            "total": spec_size(spec)}


def init_params(cfg: DeepSeekConfig, key, sharding=None) -> dict:
    """Seeded parameters, made on the device leaf by leaf as the other served
    models' are: matrices ``N(0, 1/fan_in)``, those that write into the
    residual stream scaled by ``1/sqrt(2 L)`` besides, norms at one, the
    balancing bias at zero."""
    return init_tree(param_spec(cfg), jnp.dtype(cfg.dtype), key, sharding)


# ---------------------------------------------------------------------- layers

def _rms_norm(x, w, eps):
    x = x.astype(_F32)
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w.astype(_F32)


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
    and the index heads' weights."""
    heads, nope, rank = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    lead, dtype = u.shape[:-1], jnp.dtype(cfg.dtype)
    c_q = _rms_norm(_dot(u, p["w_dq"]), p["q_norm"], cfg.rms_norm_eps)
    q = _dot(c_q, p["w_uq"]).reshape(lead + (heads, cfg.qk_head_dim))
    q_pe = _rope_interleaved(q[..., nope:], *_turns(cfg, positions, True))
    kv = _dot(u, p["w_dkv"])
    c_kv = _rms_norm(kv[..., :rank], p["kv_norm"], cfg.rms_norm_eps)
    k_pe = _rope_interleaved(kv[..., rank:], *_turns(cfg, positions, False))
    c_kv, k_pe = c_kv.astype(dtype), k_pe.astype(dtype)
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


def _attention_chunk(cfg, p, x, first, pos0, caches):
    """A chunk of positions ``pos0 ..`` of the sessions ``first ..``.  ``x``:
    ``(sessions, chunk, d)``."""
    sessions, chunk = x.shape[:2]
    latent, rope, index = caches
    with jax.named_scope("ht.lm.latent_attn"):
        u = _rms_norm(x, p["norm1"]["w"], cfg.rms_norm_eps)
        positions = pos0 + jnp.arange(chunk, dtype=jnp.int32)
        q_nope, q_pe, c_kv, k_pe, q_idx, k_idx, w_idx = _projections(cfg, p["attn"], u, positions)
        latent = jax.lax.dynamic_update_slice(latent, c_kv, (first, pos0, _ZERO))
        index = jax.lax.dynamic_update_slice(index, k_idx, (first, pos0, _ZERO))
        # a chunk may begin or end inside a row of the rope cache: its keys are scattered
        lanes = ((positions % ROPE_PACK) * cfg.qk_rope_head_dim)[:, None] + jnp.arange(
            cfg.qk_rope_head_dim, dtype=jnp.int32)
        rope = rope.at[(first + jnp.arange(sessions, dtype=jnp.int32))[:, None, None],
                       (positions // ROPE_PACK)[None, :, None], lanes[None]].set(k_pe)

        def one_session(xs):
            q_nope, q_pe, q_idx, w_idx, b = xs
            keys = jax.lax.dynamic_index_in_dim(index, first + b, keepdims=False)
            rows = jax.lax.dynamic_index_in_dim(latent, first + b, keepdims=False)
            turned = jax.lax.dynamic_index_in_dim(rope, first + b, keepdims=False)
            with jax.named_scope("ht.lm.sparse_select"):
                scores = index_scores_chunk(q_idx, w_idx, keys, pos0)
                cut = kth_largest(scores, cfg.index_topk)
            with jax.named_scope("ht.lm.latent_read"):
                return latent_prefill_attention(q_nope, q_pe, rows, turned, scores, cut, pos0,
                                                p["attn"]["w_uk"], p["attn"]["w_uv"],
                                                cfg.softmax_scale)

        o = jax.lax.map(one_session, (q_nope, q_pe, q_idx, w_idx,
                                      jnp.arange(sessions, dtype=jnp.int32)))
        return _dot(o.reshape(sessions, chunk, -1), p["attn"]["w_o"]), (latent, rope, index)


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


def _by_layer(shared) -> list:
    """A session's caches as one ``(latent, rope, index)`` a layer."""
    return [tuple(kind) for kind in zip(*(shared[name] for name in _KINDS))]


def _by_kind(caches) -> dict:
    return {name: tuple(layer[i] for layer in caches) for i, name in enumerate(_KINDS)}


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
    return _by_kind(caches), x[:, -1]


@functools.partial(jax.jit, static_argnames=("cfg",))
@telemetry.module_name("ht_lm_sparse_prefill_finish")
def _prefill_finish(cfg, params, x):
    return _head(cfg, params, x)


@functools.partial(jax.jit, static_argnames=("cfg", "steps"), donate_argnums=(2, 3))
@telemetry.module_name("ht_lm_sparse_decode")
def _decode(cfg, params, shared, token, pos, *, steps):
    """``steps`` greedy steps: the token fed at position ``pos + j`` gives the
    logits of that position and, by their argmax, the next token.  Also
    returns the slots the last step's attention read, a layer, and the
    routing's counts summed over steps and expert layers."""
    batch = token.shape[0]
    picked = min(cfg.index_topk, shared["index"][0].shape[1])

    def step(carry, _):
        shared, token, pos, _, counted = carry
        caches = _by_layer(shared)
        x, selected = _embed(params, token), []
        for i, p in enumerate(params["layers"]):
            out, caches[i], chosen = _attention_step(cfg, p, x, pos, caches[i])
            selected.append(chosen)
            x, counts = _mlp(cfg, p, x + out)
            if counts is not None:
                counted = (counted[0] + counts["pairs"], counted[1] + counts["hit"])
        chosen, logits = _head(cfg, params, x)
        return (_by_kind(caches), chosen, pos + 1, jnp.stack(selected), counted), (chosen, logits)

    start = (shared, token, pos, jnp.full((cfg.num_hidden_layers, batch, picked), -1, jnp.int32),
             (jnp.int32(0), jnp.int32(0)))
    (shared, token, _, selected, (pairs, hit)), (chosen, logits) = jax.lax.scan(
        step, start, None, length=steps)
    return shared, token, chosen.T, jnp.moveaxis(logits, 0, 1), selected, pairs, hit


# ----------------------------------------------------------------------- model

class DeepSeek:
    """The model: a configuration and its parameters.

    ``DeepSeek(cfg)`` draws seeded parameters (:func:`init_params`);
    ``DeepSeek(cfg, params)`` takes a tree of the same layout.  Serving goes
    through :meth:`session`.  ``last_selection`` is what the newest decode
    call's last step read: int32 ``(layers, batch, index_topk)``, the cache
    slots each session's attention was given, ``-1`` where fewer were
    visible (the array the attention itself gathered by, on the device)."""

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

    def session(self, batch: int, max_context: int) -> DecodeSession:
        return DecodeSession(self, batch, max_context)

    prefill_chunk = property(lambda self: PREFILL_ROWS)

    def serve_cache(self, batch: int, max_context: int):
        """All of the cache grows with the context: a latent row, a rotary key
        (two positions' in a row) and an index key a position, layer and
        session, in whole blocks of :data:`KEY_BLOCK` positions.  There is no
        constant-size state: a snapshot copies nothing but the pending
        token."""
        cfg = self.cfg
        if max_context > cfg.max_position_embeddings:
            raise ValueError(f"{max_context} positions pass the model's "
                             f"{cfg.max_position_embeddings}")
        capacity = -(-max_context // KEY_BLOCK) * KEY_BLOCK
        dtype = jnp.dtype(cfg.dtype)

        def zeros(rows, width):
            return tuple(jnp.zeros((batch, rows, width), dtype, device=self._placement)
                         for _ in range(cfg.num_hidden_layers))

        shared = {"latent": zeros(capacity, cfg.kv_lora_rank),
                  "rope": zeros(capacity // ROPE_PACK, ROPE_PACK * cfg.qk_rope_head_dim),
                  "index": zeros(capacity, cfg.index_head_dim)}
        return capacity, shared, ()

    def serve_bytes(self, shared, state) -> dict:
        return {"shared": tree_bytes(shared)}

    def serve_prefill(self, shared, state, ids, position: int):
        """The prompt in programs of :data:`PREFILL_ROWS` token rows.  Also
        returns the ``lm.prefill`` span's ``attend`` (the form of the chunks'
        attention: ``flash_kernel``, ``blockwise_jnp``, or ``mixed`` where a
        short last chunk differs) and ``prefill_blocks_by_kernel``, the layers x
        key blocks the kernel folded for the sessions."""
        cfg, params = self.cfg, self.params
        batch, n = int(ids.shape[0]), int(ids.shape[1])
        capacity = int(shared["latent"][0].shape[1])
        chunk = min(n, PREFILL_ROWS)
        group = max(1, min(batch, PREFILL_ROWS // chunk))
        widths = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank)
        forms, folded = set(), 0
        for start in range(0, n, chunk):
            rows = min(chunk, n - start)
            form = prefill_form(rows, capacity, cfg.num_attention_heads, widths,
                                jnp.dtype(cfg.dtype).itemsize)
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

    def serve_notes(self, session: DecodeSession, steps: int):
        cfg = self.cfg
        itemsize = jnp.dtype(cfg.dtype).itemsize
        seen = [session.position + j + 1 for j in range(steps)]
        each = session.batch * cfg.num_hidden_layers
        select = selection_form(session.batch, session.capacity, cfg.index_topk)
        notes = dict(batch=session.batch, context=session.position, steps=steps, select=select,
                     layers=cfg.num_hidden_layers, moe_layers=cfg.moe_layers,
                     selected=cfg.index_topk, latent_bytes=cfg.latent_width * itemsize,
                     index_bytes=cfg.index_head_dim * itemsize, experts_held=cfg.experts_held[1],
                     expert_bytes=3 * cfg.hidden_size * cfg.moe_intermediate_size * itemsize)
        return notes, {"index_keys_scanned": each * sum(seen),
                       "latent_rows_read": each * sum(min(cfg.index_topk, s) for s in seen),
                       "selections_by_cut": (steps * cfg.num_hidden_layers
                                             if select.startswith("cut") else 0)}

    def serve_decode(self, shared, state, token, position: int, steps: int):
        shared, token, chosen, logits, self.last_selection, pairs, hit = _decode(
            self.cfg, self.params, shared, token, np.int32(position), steps=steps)
        return shared, state, token, chosen, logits, {"expert_pairs": pairs, "experts_hit": hit}
