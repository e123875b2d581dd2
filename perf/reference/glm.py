"""Plain reference for GLM-4.7-Flash (``config.json`` of zai-org/GLM-4.7-Flash,
``model_type`` ``glm4_moe_lite``): DeepSeek-V3's layer, multi-head latent
attention read in full and sigmoid-routed experts beside a shared expert, and
its multi-token-prediction (MTP) module, over one whole sequence, float32 at
``highest``, in the **published per-head form**: no cache, no folded
products, no kernel (heads four at a time and query rows in blocks, so that
32,800 positions fit beside 7.8 GB of weights).

Imports nothing of ``heat_tpu``.  It shares the DeepSeek-V3.2 reference's
pieces (``perf/reference/deepseek.py``: the norms, the rotary embedding, the
router, the experts, the dense MLP) with the selection switched off: a
position reads every position up to its own.  The parameter tree is the one
``perf/generators/glm_weights.py`` makes (bfloat16 values, upcast here as
they are used).

Main layers, ``x`` the float32 stream, pre-norm RMSNorm and residual:

- **MLA.**  ``u = RMSNorm(x)``; ``c_q = RMSNorm(u W_dq)``; ``[q_nope | q_pe] =
  c_q W_uq`` a head; ``[c_kv | k_pe] = u W_dkv``, ``c_kv <- RMSNorm(c_kv)``,
  ``k_pe <- RoPE(k_pe)`` (one key for all heads), ``q_pe <- RoPE(q_pe)``;
  ``k_nope^i = W_uk^i c_kv``, ``v^i = c_kv W_uv^i``; logit ``(q_nope . k_nope +
  q_pe . k_pe) (nope + rope)^-1/2``; softmax over the positions ``s <= t``;
  ``x += [o^1 .. o^H] W_o``.  Plain RoPE (``rope_scaling`` null), pairs
  interleaved ``(2i, 2i + 1)``.
- Dense layer ``x += W_down(silu(W_gate h) * W_up h)``, ``h = RMSNorm(x)``;
  expert layers ``x += Shared(h) + sum_{e chosen} g_e Expert_e(h)``, the choice
  the ``top_k`` largest ``sigmoid(h W_r) + b`` (one group), weights ``scale *
  s_e / sum_chosen s``.
- ``h = RMSNorm(x)`` (the final norm), logits ``h W_head^T``.

**The MTP module** (DeepSeek-V3 report section 2.2, depth 1): for ``i < seq -
1``, ``h'_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]``, ``h_i`` the
main model's stream after its final norm; one decoder layer (MLA and experts,
as above) over the rows ``h'``; then its own RMSNorm and the head it shares
with the main model: row ``i``'s logits are the draft of token ``i + 2``.

Known departures from the released code: none in the
mathematics; the release stores bfloat16 weights as the generator makes them,
and the released attention at decode is the folded form, which this reference
must not be.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from perf.reference import deepseek as ds
from perf.reference.sambay import in_row_blocks

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
NEG = -jnp.inf

# what the reference reads of a configuration
SIZES = ("hidden_size", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "n_group", "topk_group",
         "num_experts_per_tok", "routed_scaling_factor", "rms_norm_eps", "rope_theta",
         "router_experts", "experts_first", "n_routed_experts")
# the rotary embedding is the plain one: the DeepSeek reference's YaRN keys at factor 1
PLAIN_ROPE = {"rope_factor": 1.0, "rope_original": 1, "rope_beta_fast": 32.0,
              "rope_beta_slow": 1.0, "rope_mscale": 1.0, "rope_mscale_all_dim": 1.0}


def _mm(x, w):
    return jnp.dot(x, w.astype(F32), precision=HI)


def attention(cfg, p, x, block, start=None):
    """One attention block over every position of the normed stream ``x``,
    every position reading those up to its own.  Returns ``start`` plus its
    output (``start`` the residual stream, zero if none) and the latent cache
    rows ``(c_kv, k_pe)``."""
    seq = x.shape[0]
    heads, nope, rope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, vd, eps = cfg["kv_lora_rank"], cfg["v_head_dim"], cfg["rms_norm_eps"]
    positions = jnp.arange(seq)
    c_q = ds.rms_norm(in_row_blocks(lambda r: _mm(r, p["w_dq"]), x, block), p["q_norm"], eps)
    kv = _mm(x, p["w_dkv"])
    c_kv = ds.rms_norm(kv[:, :rank], p["kv_norm"], eps)
    k_pe = ds.rope_interleaved(cfg, kv[:, rank:], positions)

    group = math.gcd(heads, 16)                            # heads computed at once
    q_rows = max(1, min(seq, (1 << 21) // max(seq, 1)))
    scale = (nope + rope) ** -0.5
    w_uq = p["w_uq"].reshape(p["w_uq"].shape[0], heads // group, group, nope + rope)
    w_o = p["w_o"].reshape(heads // group, group * vd, -1)

    def one_group(total, xs):
        uq, uk, uv, wo = xs                                # this group's maps
        q = _mm(c_q, uq.reshape(uq.shape[0], -1)).reshape(seq, group, nope + rope)
        q_pe = ds.rope_interleaved(cfg, q[..., nope:], positions)
        k_nope = jnp.einsum("sc,hnc->shn", c_kv, uk.astype(F32), precision=HI)
        v = jnp.einsum("sc,hcv->shv", c_kv, uv.astype(F32), precision=HI)

        def some_rows(r):
            qn, qp, at = r
            logit = (jnp.einsum("thn,shn->hts", qn, k_nope, precision=HI)
                     + jnp.einsum("thr,sr->hts", qp, k_pe, precision=HI)) * scale
            seen = positions[None, :] <= at[:, None]
            prob = jax.nn.softmax(jnp.where(seen[None], logit, NEG), axis=-1)
            return jnp.einsum("hts,shv->thv", prob, v, precision=HI)

        o = ds._in_row_blocks_of(some_rows, (q[..., :nope], q_pe, positions), q_rows)
        o = o.reshape(-1, group * vd)[:seq]
        return total + in_row_blocks(lambda r: _mm(r, wo), o, block), None

    total, _ = jax.lax.scan(
        one_group, jnp.zeros((seq, w_o.shape[-1]), F32) if start is None else start,
        (jnp.moveaxis(w_uq, 1, 0),
         p["w_uk"].reshape((heads // group, group) + p["w_uk"].shape[1:]),
         p["w_uv"].reshape((heads // group, group) + p["w_uv"].shape[1:]), w_o))
    return total, (c_kv, k_pe)


def _static(cfg: dict) -> tuple:
    return tuple((name, cfg[name]) for name in SIZES) + tuple(PLAIN_ROPE.items())


@functools.partial(jax.jit, static_argnames=("cfg_t", "block"), donate_argnums=(3,))
def _attention_layer(cfg_t, block, p, x):
    cfg = dict(cfg_t)
    return attention(cfg, p["attn"], ds.rms_norm(x, p["norm1"]["w"], cfg["rms_norm_eps"]), block,
                     start=x)


@functools.partial(jax.jit, static_argnames=("cfg_t",))
def _normed(cfg_t, w, x):
    return ds.rms_norm(x, w, dict(cfg_t)["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("cfg_t", "block"))
def _mtp_rows(cfg_t, block, mtp, embed, tokens, h):
    """``W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]`` for ``i < seq - 1``."""
    eps = dict(cfg_t)["rms_norm_eps"]
    e = ds.rms_norm(jnp.take(embed, tokens[1:], axis=0).astype(F32), mtp["enorm"]["w"], eps)
    both = jnp.concatenate([e, ds.rms_norm(h[:-1], mtp["hnorm"]["w"], eps)], axis=-1)
    return in_row_blocks(lambda r: _mm(r, mtp["eh_proj"]), both, block)


@jax.jit
def _logits(head, h):
    return jnp.einsum("td,vd->tv", h, head.astype(F32), precision=HI)


def layer(cfg_t, block, p, x):
    """One decoder layer: its stream and latent rows."""
    x, latent = _attention_layer(cfg_t, block, p, x)
    x, _ = ds._mlp_layer(cfg_t, block, p, x)
    return x, latent


def forward(cfg: dict, params: dict, tokens, main_rows, mtp_rows, block: int = 2048) -> dict:
    """One sequence of token ids ``(seq,)`` through the model and its MTP
    module.  Returns

    - ``logits``: float32 ``(len(main_rows), vocab)``, the main model's at
      those positions;
    - ``draft_logits``: float32 ``(len(mtp_rows), vocab)``, the MTP module's
      rows ``i`` (``i < seq - 1``), the drafts of the tokens at ``i + 2``;
    - ``latent``: for every main layer ``(c_kv, k_pe)`` of every position;
      ``mtp_latent``: the MTP layer's of the positions ``0 .. seq - 2``."""
    cfg_t = _static(cfg)
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    out = {"latent": []}
    for p in params["layers"]:
        x, latent = layer(cfg_t, block, p, x)
        out["latent"].append(latent)
    h = _normed(cfg_t, params["final_norm"]["w"], x)
    del x
    out["logits"] = _logits(params["head"], h[jnp.asarray(main_rows)])
    mtp = params["mtp"]
    x = _mtp_rows(cfg_t, block, mtp, params["embed"], tokens, h)
    del h
    x, out["mtp_latent"] = layer(cfg_t, block, mtp["layer"], x)
    out["draft_logits"] = _logits(
        params["head"], _normed(cfg_t, mtp["head_norm"]["w"], x[jnp.asarray(mtp_rows)]))
    return out
