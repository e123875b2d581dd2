"""Plain reference for DeepSeek-V3.2 (``config.json`` of deepseek-ai/DeepSeek-V3.2:
multi-head latent attention read through DeepSeek sparse attention, sigmoid
routed experts with a shared expert): the full forward pass over one whole
sequence, float32 at ``highest``, in the **published per-head form** with the
selection as a mask: no cache, no folded products, no gather, no kernel (in
blocks of rows and sixteen heads at a time, so that 32,780 positions fit
beside 9 GB of weights).
Imports nothing of ``heat_tpu``; the parameter tree is the one the
configuration's generator makes (bfloat16 values, upcast here as they are
used).  It is given the same share of the deployment as the program: the
experts held (``experts_first``, ``n_routed_experts`` of ``router_experts``)
and the slice of the vocabulary (the rows of ``embed`` and ``head``).

Every layer, ``x`` the float32 stream, pre-norm RMSNorm and residual:

- **MLA.**  ``u = RMSNorm(x)``; ``c_q = RMSNorm(u W_dq)``; ``[q_nope | q_pe] =
  c_q W_uq`` a head; ``[c_kv | k_pe] = u W_dkv``, ``c_kv <- RMSNorm(c_kv)``,
  ``k_pe <- RoPE(k_pe)`` (one key for all heads), ``q_pe <- RoPE(q_pe)``;
  ``k_nope^i = W_uk^i c_kv``, ``v^i = c_kv W_uv^i``; logit ``(q_nope . k_nope +
  q_pe . k_pe) * scale`` with ``scale = (nope + rope)^-1/2 * m^2``, ``m = 0.1 *
  mscale_all_dim * ln(factor) + 1``; softmax over the selection ``S_t``;
  ``x += [o^1 .. o^H] W_o``.  RoPE with YaRN frequencies, pairs interleaved
  ``(2i, 2i + 1)``.
- **The selection.**  ``q^I = c_q W_q^I`` (``index_n_heads x index_head_dim``),
  ``k^I = LayerNorm(u W_k^I)``, RoPE on the first ``rope`` lanes of both (same
  frequencies, lane ``i`` paired with ``i + rope / 2``), ``w = u W_w^I *
  heads^-1/2 * index_head_dim^-1/2``; ``I_ts = sum_j w_tj relu(q^I_tj . k^I_s)``
  for ``s <= t``; ``S_t`` the ``min(index_topk, t + 1)`` positions of largest
  ``I_ts``.
- Dense layers: ``x += W_down(silu(W_gate h) * W_up h)``, ``h = RMSNorm(x)``.
- Expert layers: ``s = sigmoid(h W_r)``; the choice by ``s + b``: groups of
  consecutive experts scored by the sum of their two largest, the ``topk_group``
  best kept, among them the ``num_experts_per_tok`` largest; weights ``scale *
  s_e / sum_chosen s``; ``x += Shared(h) + sum_{e chosen and held} g_e
  Expert_e(h)``.

Departures from the released code that the builder knows of: the release
stores weights and the index keys in FP8 with block scales, this reference
reads the bfloat16 values the generator made; the released indexer turns
``q^I`` and ``k^I`` by a Hadamard matrix before its FP8 cast, an orthogonal
turn that leaves every ``q^I . k^I`` as it is and that is left out here with
the cast; the released attention at decode is the folded form, which this
reference must not be.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference.sambay import in_row_blocks

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
NEG = -jnp.inf

# what the reference reads of a configuration (``rope_*`` are the keys of the
# published ``rope_scaling``)
SIZES = ("hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "index_n_heads",
         "index_head_dim", "index_topk", "n_group", "topk_group", "num_experts_per_tok",
         "routed_scaling_factor", "rms_norm_eps", "layer_norm_eps", "rope_theta",
         "rope_factor", "rope_original", "rope_beta_fast", "rope_beta_slow", "rope_mscale",
         "rope_mscale_all_dim", "router_experts", "experts_first", "n_routed_experts")


def _mm(x, w):
    return jnp.dot(x, w.astype(F32), precision=HI)


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def layer_norm(x, w, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w.astype(F32) + b.astype(F32)


# ------------------------------------------------------------------------ RoPE

def yarn_frequencies(cfg: dict) -> np.ndarray:
    """The ``rope / 2`` angular frequencies: ``theta^(-2i / rope)``, divided by
    ``factor`` below the slow correction dimension, kept above the fast one and
    blended by a linear ramp between them."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    freq = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    factor, original = float(cfg["rope_factor"]), float(cfg["rope_original"])
    if factor == 1.0:
        return freq.astype(np.float32)

    def correction_dim(rotations):
        return dim * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(correction_dim(cfg["rope_beta_fast"])), 0)
    high = min(math.ceil(correction_dim(cfg["rope_beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / ((high if high != low else high + 0.001) - low), 0.0, 1.0)
    keep = 1.0 - ramp
    return (freq / factor * (1.0 - keep) + freq * keep).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    m = 1.0
    if float(cfg["rope_factor"]) != 1.0:
        m = 0.1 * cfg["rope_mscale_all_dim"] * math.log(cfg["rope_factor"]) + 1.0
    return (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5 * m * m


def _angles(cfg, positions):
    angle = positions.astype(F32)[:, None] * yarn_frequencies(cfg)
    return jnp.cos(angle), jnp.sin(angle)          # the multiplier mscale / mscale_all_dim is 1


def rope_interleaved(cfg, x, positions):
    """``x``: ``(seq, ..., rope)``, lanes ``(2i, 2i + 1)`` a pair."""
    cos, sin = _angles(cfg, positions)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[-1],)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(x.shape)


def rope_half(cfg, x, positions):
    """``x``: ``(seq, ..., rope)``, lane ``i`` paired with ``i + rope / 2``."""
    cos, sin = _angles(cfg, positions)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[-1],)
    cos, sin = cos.reshape(shape), sin.reshape(shape)
    half = x.shape[-1] // 2
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)


# ------------------------------------------------------------------- attention

def _in_row_blocks_of(fn, arrays, rows):
    """``fn`` over blocks of ``rows`` rows of several arrays cut alike (rows
    are independent; the last block is padded with copies of the last row)."""
    n = arrays[0].shape[0]
    pad = (-n) % rows
    cut = lambda a: jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1), mode="edge").reshape(
        (-1, rows) + a.shape[1:])
    out = jax.lax.map(fn, tuple(cut(a) for a in arrays))
    return out.reshape((-1,) + out.shape[2:])[:n]


def index_keys(cfg, p, u, positions):
    """``k^I`` of every position: ``(seq, index_head_dim)``."""
    rope = cfg["qk_rope_head_dim"]
    k = layer_norm(_mm(u, p["w_ik"]), p["ik_norm_w"], p["ik_norm_b"], cfg["layer_norm_eps"])
    return jnp.concatenate([rope_half(cfg, k[:, :rope], positions), k[:, rope:]], axis=-1)


def index_scores(cfg, p, u, c_q, k_idx, positions):
    """``I_ts`` of the query rows ``(u, c_q, positions)`` against every key;
    ``-inf`` where ``s > t``."""
    heads, width, rope = cfg["index_n_heads"], cfg["index_head_dim"], cfg["qk_rope_head_dim"]
    q = _mm(c_q, p["w_iq"]).reshape(-1, heads, width)
    q = jnp.concatenate([rope_half(cfg, q[..., :rope], positions), q[..., rope:]], axis=-1)
    w = _mm(u, p["w_iw"]) * heads ** -0.5 * width ** -0.5
    hit = jax.nn.relu(jnp.einsum("thd,sd->ths", q, k_idx, precision=HI))
    score = jnp.einsum("ths,th->ts", hit, w, precision=HI)
    seen = jnp.arange(k_idx.shape[0])[None, :] <= positions[:, None]
    return jnp.where(seen, score, NEG)


def selection_mask(cfg, scores):
    """The keys a row reads: the ``index_topk`` largest scores of the visible
    ones (all of them where fewer are visible)."""
    k = min(cfg["index_topk"], scores.shape[-1])
    cut = jax.lax.top_k(scores, k)[0][:, -1:]
    return (scores >= cut) & (scores > NEG)


def unpacked(mask, seq: int):
    """A packed selection as booleans: ``(rows, ceil(seq / 8)) -> (rows, seq)``."""
    return jnp.unpackbits(mask, axis=-1, count=seq).astype(bool)


def attention(cfg, p, x, block, start=None):
    """One attention block over every position of the normed stream ``x``.
    Returns ``start`` plus its output (``start`` the residual stream, zero if
    none), the latent cache rows ``(c_kv, k_pe)``, the index keys and the
    selection: the keys each position read, ``(seq, seq)`` booleans packed
    eight to a byte along the keys (:func:`unpacked`; whole it would be a
    gigabyte at 32,768 positions).  Heads are computed sixteen at a time and
    their outputs summed as they come, so that no array of all heads' keys,
    values or outputs is alive."""
    seq = x.shape[0]
    heads, nope, rope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, vd, eps = cfg["kv_lora_rank"], cfg["v_head_dim"], cfg["rms_norm_eps"]
    positions = jnp.arange(seq)
    c_q = rms_norm(in_row_blocks(lambda r: _mm(r, p["w_dq"]), x, block), p["q_norm"], eps)
    kv = _mm(x, p["w_dkv"])
    c_kv = rms_norm(kv[:, :rank], p["kv_norm"], eps)
    k_pe = rope_interleaved(cfg, kv[:, rank:], positions)
    k_idx = index_keys(cfg, p, x, positions)

    rows = max(1, min(seq, (1 << 21) // max(seq, 1)))      # (rows, index heads, seq) floats at once
    mask = _in_row_blocks_of(
        lambda r: jnp.packbits(
            selection_mask(cfg, index_scores(cfg, p, r[0], r[1], k_idx, r[2])), axis=-1),
        (x, c_q, positions), rows)

    group = math.gcd(heads, 16)                            # heads computed at once
    q_rows = max(1, min(seq, (1 << 21) // max(seq, 1)))
    scale = softmax_scale(cfg)
    w_uq = p["w_uq"].reshape(p["w_uq"].shape[0], heads // group, group, nope + rope)
    w_o = p["w_o"].reshape(heads // group, group * vd, -1)

    def one_group(total, xs):
        uq, uk, uv, wo = xs                                # this group's maps
        q = _mm(c_q, uq.reshape(uq.shape[0], -1)).reshape(seq, group, nope + rope)
        q_pe = rope_interleaved(cfg, q[..., nope:], positions)
        k_nope = jnp.einsum("sc,hnc->shn", c_kv, uk.astype(F32), precision=HI)
        v = jnp.einsum("sc,hcv->shv", c_kv, uv.astype(F32), precision=HI)

        def some_rows(r):
            qn, qp, m = r
            logit = (jnp.einsum("thn,shn->hts", qn, k_nope, precision=HI)
                     + jnp.einsum("thr,sr->hts", qp, k_pe, precision=HI)) * scale
            prob = jax.nn.softmax(jnp.where(unpacked(m, seq)[None], logit, NEG), axis=-1)
            return jnp.einsum("hts,shv->thv", prob, v, precision=HI)

        o = _in_row_blocks_of(some_rows, (q[..., :nope], q_pe, mask), q_rows)
        o = o.reshape(-1, group * vd)[:seq]
        return total + in_row_blocks(lambda r: _mm(r, wo), o, block), None

    total, _ = jax.lax.scan(
        one_group, jnp.zeros((seq, w_o.shape[-1]), F32) if start is None else start,
        (jnp.moveaxis(w_uq, 1, 0),
         p["w_uk"].reshape((heads // group, group) + p["w_uk"].shape[1:]),
         p["w_uv"].reshape((heads // group, group) + p["w_uv"].shape[1:]), w_o))
    return total, (c_kv, k_pe), k_idx, mask


# --------------------------------------------------------------------- experts

def gated_mlp(p, h):
    return _mm(jax.nn.silu(_mm(h, p["w_gate"])) * _mm(h, p["w_up"]), p["w_down"])


def route(cfg, p, h):
    """The routing weights as ``(tokens, router_experts)``, zero where an
    expert is not chosen."""
    experts, groups = cfg["router_experts"], cfg["n_group"]
    s = jax.nn.sigmoid(_mm(h, p["router"]))
    choice = s + p["bias"].astype(F32)
    by_group = choice.reshape(-1, groups, experts // groups)
    group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    kept = jax.lax.top_k(group_score, cfg["topk_group"])[1]
    in_kept = jnp.any(kept[:, :, None] == jnp.arange(groups)[None, None, :], axis=1)
    open_ = jnp.repeat(in_kept, experts // groups, axis=1)
    chosen = jax.lax.top_k(jnp.where(open_, choice, NEG), cfg["num_experts_per_tok"])[1]
    picked = jnp.any(chosen[:, :, None] == jnp.arange(experts)[None, None, :], axis=1)
    weights = jnp.where(picked, s, 0.0)
    return weights / jnp.sum(weights, axis=-1, keepdims=True) * cfg["routed_scaling_factor"]


def experts(cfg, p, h, block, start=None):
    """``start`` (the residual stream; zero if none) plus the shared expert
    and the held experts' part of the routed sum, an expert at a time."""
    first, count = cfg["experts_first"], cfg["n_routed_experts"]
    mine = route(cfg, p, h)[:, first:first + count]

    def one_expert(total, xs):
        w_gate, w_up, w_down, g = xs
        fn = lambda r: gated_mlp({"w_gate": w_gate, "w_up": w_up, "w_down": w_down}, r)
        return total + in_row_blocks(fn, h, block) * g[:, None], None

    total = in_row_blocks(lambda r: gated_mlp(p["shared"], r), h, block)
    if start is not None:
        total = total + start
    total, _ = jax.lax.scan(one_expert, total, (p["experts"]["w_gate"], p["experts"]["w_up"],
                                                p["experts"]["w_down"], mine.T))
    return total, mine


# ----------------------------------------------------------------------- model

def _static(cfg: dict) -> tuple:
    return tuple((name, cfg[name]) for name in SIZES)


@functools.partial(jax.jit, static_argnames=("cfg_t", "block"), donate_argnums=(3,))
def _attention_layer(cfg_t, block, p, x):
    cfg = dict(cfg_t)
    x, latent, k_idx, mask = attention(
        cfg, p["attn"], rms_norm(x, p["norm1"]["w"], cfg["rms_norm_eps"]), block, start=x)
    return x, latent, k_idx, unpacked(mask[-1:], x.shape[0])[0]


@functools.partial(jax.jit, static_argnames=("cfg_t", "block"), donate_argnums=(3,))
def _mlp_layer(cfg_t, block, p, x):
    cfg = dict(cfg_t)
    h = rms_norm(x, p["norm2"]["w"], cfg["rms_norm_eps"])
    if "moe" in p:
        return experts(cfg, p["moe"], h, block, start=x)
    return x + in_row_blocks(lambda r: gated_mlp(p["mlp"], r), h, block), None


@functools.partial(jax.jit, static_argnames=("cfg_t",))
def _head(cfg_t, norm, head, x):
    h = rms_norm(x, norm["w"], dict(cfg_t)["rms_norm_eps"])
    return jnp.einsum("td,vd->tv", h, head.astype(F32), precision=HI)


def layers_of(cfg: dict, params: dict, x, block: int = 2048):
    """The stream through every layer; yields what each layer made."""
    cfg_t = _static(cfg)
    for p in params["layers"]:
        x, latent, k_idx, last_mask = _attention_layer(cfg_t, block, p, x)
        x, weights = _mlp_layer(cfg_t, block, p, x)
        yield x, {"latent": latent, "index": k_idx, "selected": last_mask, "weights": weights}


def forward(cfg: dict, params: dict, tokens, n_last: int, block: int = 2048) -> dict:
    """One sequence of token ids ``(seq,)`` through the model.  Returns

    - ``logits``: float32 ``(n_last, vocab held)``, the last ``n_last`` positions;
    - ``latent``: for every layer ``(c_kv, k_pe)`` of every position, and
      ``index``: for every layer ``k^I`` of every position;
    - ``selected``: for every layer the mask ``(seq,)`` of the keys that the
      last position read (``S_t``)."""
    seq = int(tokens.shape[0])
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    out = {"latent": [], "index": [], "selected": []}
    for x, made in layers_of(cfg, params, x, block):
        for name in out:
            out[name].append(made[name])
    out["logits"] = _head(_static(cfg), params["final_norm"], params["head"], x[seq - n_last:])
    return out
