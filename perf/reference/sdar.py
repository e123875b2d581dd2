"""Plain reference for SDAR-30B-A3B-Chat (``config.json`` of
JetLM/SDAR-30B-A3B-Chat, ``model_type`` ``sdar_moe``): the Qwen3-MoE layer under
SDAR's block-causal mask, float32 at ``highest``, over whole sequences: no
cache code, no kernel, no folded product (heads a key/value group at a time
and query rows in blocks, so that 32,768 positions fit beside 7.5 GB of
weights).

Imports nothing of ``heat_tpu``; the parameter tree is the one
``perf/generators/sdar_weights.py`` makes (bfloat16 values, upcast here as
they are used).

Every layer, ``x`` the float32 stream, pre-norm RMSNorm (eps
``rms_norm_eps``, ``x / sqrt(mean(x^2) + eps) * w``) and residual:

- **Attention.**  ``u = RMSNorm(x)``; ``q = u W_q`` (``heads x head_dim``),
  ``k = u W_k``, ``v = u W_v`` (``kv_heads x head_dim``); ``q <-
  RoPE(RMSNorm_q(q))``, ``k <- RoPE(RMSNorm_k(k))`` a head; RoPE over the
  whole head, lane ``i`` paired with ``i + head_dim / 2``, ``theta^(-2i /
  head_dim)``; query head ``h`` reads key/value head ``h // (heads /
  kv_heads)``; logit ``q . k / sqrt(head_dim)``; softmax over the positions
  ``j`` with ``j // B <= i // B`` (``B`` the block length: **block-causal**);
  ``x += [o^1 .. o^H] W_o``.
- **Experts.**  ``h = RMSNorm(x)``; ``p = softmax(h W_r)`` over all experts;
  the ``top_k`` largest chosen, weights ``p_e`` divided by their sum
  (``norm_topk_prob``); ``x += sum_{e chosen} g_e W_down^e(silu(W_gate^e h) *
  W_up^e h)``, an expert at a time over every row.
- ``h = RMSNorm(x)`` (the final norm), logits ``h W_head^T``; the logits at a
  position are that position's (no shift).

The mask makes a block's rows depend on earlier blocks and on itself alone,
so the check computes a sequence's prefix once (:func:`prefix`: the keys and
values of every layer) and then a block at a time over it (:func:`block`).
Known departures from the released code: none in the mathematics; the weights
are the generator's bfloat16 values.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference.deepseek import _in_row_blocks_of
from perf.reference.sambay import in_row_blocks

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
NEG = -jnp.inf

# what the reference reads of a configuration
SIZES = ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
         "num_experts", "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps", "rope_theta",
         "block_length")


def _mm(x, w):
    return jnp.dot(x, w.astype(F32), precision=HI)


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def rope(cfg, x, positions):
    """``x``: ``(seq, heads, head_dim)``, lane ``i`` paired with ``i + head_dim / 2``."""
    dim = x.shape[-1]
    freq = (float(cfg["rope_theta"]) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
            ).astype(np.float32)
    angle = positions.astype(F32)[:, None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    lo, hi = x[..., :dim // 2], x[..., dim // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)


def projections(cfg, p, x, positions):
    """Queries ``(seq, heads, hd)``, keys and values ``(seq, kv_heads, hd)`` of
    the stream ``x`` (normed here)."""
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    u = rms_norm(x, p["norm1"]["w"], eps)
    a = p["attn"]
    q = _mm(u, a["w_q"]).reshape(x.shape[0], -1, hd)
    k = _mm(u, a["w_k"]).reshape(x.shape[0], -1, hd)
    v = _mm(u, a["w_v"]).reshape(x.shape[0], -1, hd)
    return (rope(cfg, rms_norm(q, a["q_norm"], eps), positions),
            rope(cfg, rms_norm(k, a["k_norm"], eps), positions), v)


def attend(cfg, q, q_pos, k, v, k_pos, rows: int):
    """Every query row against the keys its block sees, a key/value group at
    a time and ``rows`` query rows at a time.  Returns ``(seq, heads x hd)``."""
    kv_heads, hd, width = cfg["num_key_value_heads"], cfg["head_dim"], cfg["block_length"]
    group = cfg["num_attention_heads"] // kv_heads
    scale = hd ** -0.5
    out = []
    for g in range(kv_heads):
        qg = q[:, g * group:(g + 1) * group]

        def some_rows(r, g=g):
            qr, at = r
            logit = jnp.einsum("thd,sd->hts", qr, k[:, g], precision=HI) * scale
            seen = (k_pos[None, :] // width) <= (at[:, None] // width)
            prob = jax.nn.softmax(jnp.where(seen[None], logit, NEG), axis=-1)
            return jnp.einsum("hts,sd->thd", prob, v[:, g], precision=HI)

        out.append(_in_row_blocks_of(some_rows, (qg, q_pos), rows))
    return jnp.concatenate(out, axis=1).reshape(q.shape[0], -1)


def route(cfg, p, h):
    """The routing weights as ``(tokens, experts)``, zero where an expert is
    not chosen."""
    experts = cfg["num_experts"]
    prob = jax.nn.softmax(_mm(h, p["router"]), axis=-1)
    chosen = jax.lax.top_k(prob, cfg["num_experts_per_tok"])[1]
    picked = jnp.any(chosen[:, :, None] == jnp.arange(experts)[None, None, :], axis=1)
    weights = jnp.where(picked, prob, 0.0)
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return weights


def experts(cfg, p, x, block):
    """``x`` plus the routed experts' sum, an expert at a time."""
    h = rms_norm(x, p["norm2"]["w"], cfg["rms_norm_eps"])
    weights = route(cfg, p["moe"], h)
    e = p["moe"]["experts"]

    def one_expert(total, xs):
        w_gate, w_up, w_down, g = xs
        fn = lambda r: _mm(jax.nn.silu(_mm(r, w_gate)) * _mm(r, w_up), w_down)  # noqa: E731
        return total + in_row_blocks(fn, h, block) * g[:, None], None

    total, _ = jax.lax.scan(one_expert, x, (e["w_gate"], e["w_up"], e["w_down"], weights.T))
    return total


def _static(cfg: dict) -> tuple:
    return tuple((name, cfg[name]) for name in SIZES)


@functools.partial(jax.jit, static_argnames=("cfg_t", "block"), donate_argnums=(3,))
def _prefix_layer(cfg_t, block, p, x):
    """One layer over a whole prefix from position 0: its stream, keys and values."""
    cfg = dict(cfg_t)
    seq = x.shape[0]
    positions = jnp.arange(seq)
    q, k, v = projections(cfg, p, x, positions)
    rows = max(1, min(seq, (1 << 21) // max(seq, 1)))       # (heads of a group, rows, seq) at once
    x = x + in_row_blocks(lambda r: _mm(r, p["attn"]["w_o"]),
                          attend(cfg, q, positions, k, v, positions, rows), block)
    return experts(cfg, p, x, block), (k, v)


@functools.partial(jax.jit, static_argnames=("cfg_t", "block"))
def _block_layer(cfg_t, block, p, x, keys, values, pos):
    """One layer over a block at ``pos ..``: its keys and values laid over the
    rows ``pos ..`` of ``keys`` and ``values`` (the rows before them every
    earlier position's; the rows after them are past the block, which the mask
    hides), then the block-causal attention over all of them."""
    cfg = dict(cfg_t)
    width = x.shape[0]
    positions = pos + jnp.arange(width)
    q, k, v = projections(cfg, p, x, positions)
    keys = jax.lax.dynamic_update_slice_in_dim(keys, k, pos, axis=0)
    values = jax.lax.dynamic_update_slice_in_dim(values, v, pos, axis=0)
    att = attend(cfg, q, positions, keys, values, jnp.arange(keys.shape[0]), width)
    return experts(cfg, p, x + _mm(att, p["attn"]["w_o"]), block), (k, v)


@functools.partial(jax.jit, static_argnames=("cfg_t",))
def _logits(cfg_t, norm, head, x):
    h = rms_norm(x, norm["w"], dict(cfg_t)["rms_norm_eps"])
    return jnp.einsum("td,vd->tv", h, head.astype(F32), precision=HI)


@functools.partial(jax.jit, static_argnames=("capacity",))
def _rows(made, capacity):
    return jnp.pad(made, ((0, capacity - made.shape[0]), (0, 0), (0, 0)))


@jax.jit
def _laid(rows, made, pos):
    return jax.lax.dynamic_update_slice_in_dim(rows, made, pos, axis=0)


def prefix(cfg: dict, params: dict, tokens, capacity: int, block: int = 2048) -> list:
    """Token ids ``(seq,)`` (whole blocks) from position 0 through every
    layer.  Returns each layer's ``(keys, values)``, float32 ``(capacity,
    kv_heads, head_dim)``, the first ``seq`` rows those of the positions (keys
    after their norm and RoPE), zero past them."""
    cfg_t = _static(cfg)
    if tokens.shape[0] == 0:
        empty = jnp.zeros((capacity, cfg["num_key_value_heads"], cfg["head_dim"]), F32)
        return [(empty, empty) for _ in params["layers"]]
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    made = []
    for p in params["layers"]:
        x, (k, v) = _prefix_layer(cfg_t, block, p, x)
        made.append((_rows(k, capacity), _rows(v, capacity)))
    return made


def block(cfg: dict, params: dict, before: list, tokens, pos: int, block: int = 2048):
    """A block of token ids ``(B,)`` at positions ``pos ..`` over ``before``
    (a layer's ``(keys, values)`` whose rows ``0 .. pos - 1`` are the earlier
    positions'; :func:`prefix`, :func:`committed`).  Returns its logits ``(B,
    vocab)`` and each layer's ``(keys, values)`` of the block."""
    cfg_t = _static(cfg)
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    made = []
    for p, (k, v) in zip(params["layers"], before):
        x, kv = _block_layer(cfg_t, block, p, x, k, v, jnp.int32(pos))
        made.append(kv)
    return _logits(cfg_t, params["final_norm"], params["head"], x), made


def committed(before: list, made: list, pos: int) -> list:
    """``before`` with a finished block's keys and values (``made`` of
    :func:`block`) laid over its rows ``pos ..``."""
    at = jnp.int32(pos)
    return [(_laid(k, mk, at), _laid(v, mv, at)) for (k, v), (mk, mv) in zip(before, made)]
