"""Plain reference for the SambaY language model (Phi-4-mini-flash-reasoning,
arXiv:2507.06607): the full forward pass over a whole sequence, float32 at
``highest``, no cache, no kernels, a sequential scan.  Imports nothing of
``heat_tpu``; the parameter tree is the one the configuration's generator
makes (bfloat16 values, upcast here a layer at a time: the whole model in
float32 would not fit beside them).

Every layer ``i``: ``x += Mixer_i(LN(x))``, ``x += W_down(silu(W_gate h) *
W_up h)`` with ``h = LN(x)``; final LayerNorm; logits by the tied embedding;
no positional encoding.  Mixers, by the layer's kind:

- ``mamba``: ``[u, z] = W_in h``; ``u' = silu(conv1d_causal(u) + b)``;
  ``[d, B, C] = W_x u'``; ``D = softplus(W_dt d + b_dt)``; ``A = -exp(A_log)``;
  ``S_t = exp(D_t A) * S_{t-1} + (D_t u'_t) B_t^T``; ``y_t = S_t C_t + D_skip *
  u'_t``; output ``W_out(y * silu(z))``.  ``y`` is also the memory of the
  gated memory units that follow.
- ``gmu``: ``W_out(silu(W_in h) * m)`` with ``m`` the newest Mamba layer's
  ``y`` at the same position.
- ``window`` / ``full`` / ``cross``: differential attention.  Query heads
  ``2p, 2p+1`` are pair ``p``; key/value heads ``2g, 2g+1`` are pair ``g``,
  their values read as one head of twice the width, serving query pairs ``g *
  P .. g * P + P - 1``.  ``o = (softmax(q1 k1^T / sqrt(hd) + mask) - lambda
  softmax(q2 k2^T / sqrt(hd) + mask)) V``, ``lambda = exp(lq1 . lk1) - exp(lq2
  . lk2) + lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 i)``; then
  ``RMSNorm(o) * g * (1 - lambda_init)`` into ``W_o``.  The mask is causal,
  under ``window`` also ``k > q - window``.  A ``cross`` layer has ``W_q`` and
  ``W_o`` only and reads the keys and values of the ``full`` layer.

A property of the architecture is used, and only one: nothing at a later
position reads what the layers after the ``full`` layer compute, nor the
``full`` layer's own output.  So those are evaluated at the judged positions
(the last ``n_last``) alone; the layers before run over every position, and
the ``full`` layer's keys and values are made for every position.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def layer_types(cfg: dict) -> tuple:
    if cfg.get("layer_types"):
        return tuple(cfg["layer_types"])
    n, every = cfg["num_hidden_layers"], cfg.get("mb_per_layer", 2)
    kinds = []
    for i in range(n):
        if i < n // 2:
            kinds.append("mamba" if i % every == 0 else "window")
        elif i <= n // 2 + 1:
            kinds.append("mamba" if i == n // 2 else "full")
        else:
            kinds.append("gmu" if i % 2 == 0 else "cross")
    return tuple(kinds)


def rounded(cfg: dict, x):
    """``x`` as the configuration's ``operands`` would hold it (``cfg["operands"]``:
    None, the float32 reference; "bfloat16", the witness that rounds where the
    configuration states that values are stored or enter a product)."""
    kind = cfg.get("operands")
    if kind is None:
        return x
    # reduce_precision, not a cast there and back: XLA may drop such a pair
    info = jnp.finfo(jnp.dtype(kind))
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)


def _mm(cfg, x, w):
    return jnp.dot(rounded(cfg, x), w.astype(F32), precision=HI)


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["w"].astype(F32) + p["b"].astype(F32)


def in_row_blocks(fn, x, block):
    """``fn`` over blocks of ``block`` rows of ``x`` (rows are independent)."""
    rows = x.shape[0]
    block = min(block, rows)
    pad = (-rows) % block
    xs = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    out = jax.lax.map(fn, xs.reshape((-1, block) + x.shape[1:]))
    return out.reshape((-1,) + out.shape[2:])[:rows]


def mlp(cfg, p, x):
    h = layer_norm(x, p["norm2"], cfg["layer_norm_eps"])
    inner = jax.nn.silu(_mm(cfg, h, p["mlp"]["w_gate"])) * _mm(cfg, h, p["mlp"]["w_up"])
    return x + _mm(cfg, inner, p["mlp"]["w_down"])


def mamba_mixer(cfg: dict, p: dict, h):
    """``h``: ``(seq, d)`` from position 0.  Returns ``(output, y)``."""
    di, ds, rank, taps = cfg["d_inner"], cfg["d_state"], cfg["dt_rank"], cfg["d_conv"]
    seq = h.shape[0]
    uz = _mm(cfg, h, p["w_in"])
    u, z = uz[:, :di], uz[:, di:]
    padded = jnp.pad(u, ((taps - 1, 0), (0, 0)))
    conv = p["conv_b"].astype(F32) + sum(
        padded[j:j + seq] * p["conv_w"][j].astype(F32) for j in range(taps))
    u = jax.nn.silu(conv)
    dbc = _mm(cfg, u, p["w_x"])
    delta = jax.nn.softplus(_mm(cfg, dbc[:, :rank], p["w_dt"]) + p["b_dt"].astype(F32))
    b, c = dbc[:, rank:rank + ds], dbc[:, rank + ds:]
    a = -jnp.exp(p["a_log"].astype(F32)).T                       # (d_inner, d_state)
    skip = p["d_skip"].astype(F32)

    def step(state, xs):
        u_t, d_t, b_t, c_t = xs
        state = jnp.exp(d_t[:, None] * a) * state + (d_t * u_t)[:, None] * b_t[None, :]
        return state, jnp.sum(state * c_t[None, :], axis=1) + skip * u_t

    _, y = jax.lax.scan(step, jnp.zeros((di, ds), F32), (u, delta, b, c))
    return _mm(cfg, y * jax.nn.silu(z), p["w_out"]), y


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def diff_attention(cfg: dict, p: dict, lam_init, q, k, v, q_pos, k_pos, window=None):
    """``q``: ``(nq, heads * hd)``, ``k``, ``v``: ``(nk, kv_heads * hd)``, with
    their positions; ``lam_init``: the layer's ``lambda_init``.  Returns the
    ``(nq, heads * hd)`` rows that enter ``W_o``."""
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    groups = cfg["num_key_value_heads"] // 2
    nq, nk = q.shape[0], k.shape[0]
    q, k, v = rounded(cfg, q), rounded(cfg, k), rounded(cfg, v)
    q = q.reshape(nq, groups, -1, 2, hd)          # (q, group, pair in group, branch, hd)
    k = k.reshape(nk, groups, 2, hd)              # (k, group, branch, hd)
    v = v.reshape(nk, groups, 2 * hd)             # (k, group, doubled head)
    s = jnp.einsum("qgpjd,kgjd->gpjqk", q, k, precision=HI) / math.sqrt(hd)
    seen = (k_pos[None, :] <= q_pos[:, None]) & (k_pos[None, :] >= 0)
    if window is not None:
        seen &= k_pos[None, :] > q_pos[:, None] - window
    s = jnp.where(seen, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    w = jnp.where(seen, w, 0.0)
    a = jnp.einsum("gpjqk,kge->qgpje", rounded(cfg, w), v, precision=HI)
    lam = (jnp.exp(jnp.sum(p["lam_q1"].astype(F32) * p["lam_k1"].astype(F32)))
           - jnp.exp(jnp.sum(p["lam_q2"].astype(F32) * p["lam_k2"].astype(F32))) + lam_init)
    o = a[..., 0, :] - lam * a[..., 1, :]
    o = o / jnp.sqrt(jnp.mean(o ** 2, axis=-1, keepdims=True) + cfg["layer_norm_eps"])
    o = o * p["subln"].astype(F32) * (1.0 - lam_init)
    return o.reshape(nq, -1)


def _widths(cfg):
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd


def window_mixer(cfg: dict, p: dict, lam_init, h):
    """Sliding-window attention over ``h`` of ``(seq, d)`` from position 0,
    in blocks of ``window`` queries, each against its own block of keys and
    the block before."""
    window = cfg["sliding_window"]
    q_width, kv_width = _widths(cfg)
    seq = h.shape[0]
    qkv = _mm(cfg, h, p["w_qkv"])
    pad = (-seq) % window
    qkv = jnp.pad(qkv, ((window, pad), (0, 0)))            # one empty block in front
    nblocks = (seq + pad) // window

    def one(j):
        rows = jax.lax.dynamic_slice_in_dim(qkv, j * window, 2 * window)
        k_pos = (j - 1) * window + jnp.arange(2 * window)
        return diff_attention(
            cfg, p, lam_init, rows[window:, :q_width], rows[:, q_width:q_width + kv_width],
            rows[:, q_width + kv_width:], k_pos[window:], k_pos, window)

    out = jax.lax.map(one, jnp.arange(nblocks)).reshape(nblocks * window, -1)[:seq]
    return _mm(cfg, out, p["w_o"])


# one jitted program per layer kind: the configuration's sizes are static, a
# layer's lambda_init is an operand
SIZES = ("hidden_size", "num_attention_heads", "num_key_value_heads", "sliding_window",
         "layer_norm_eps", "d_inner", "d_state", "d_conv", "dt_rank")


def _static(cfg: dict) -> tuple:
    return tuple((k, cfg[k]) for k in SIZES) + (("operands", cfg.get("operands")),)


@functools.partial(jax.jit, static_argnames=("cfg_t", "kind", "block"))
def _self_layer(cfg_t, kind, lam_init, block, p, x):
    """A ``mamba`` or ``window`` layer over every position.  Returns ``(x, y)``."""
    cfg = dict(cfg_t)
    eps = cfg["layer_norm_eps"]
    h = layer_norm(x, p["norm1"], eps)
    y = None
    if kind == "mamba":
        out, y = mamba_mixer(cfg, p["mixer"], h)
    else:
        out = window_mixer(cfg, p["mixer"], lam_init, h)
    return in_row_blocks(functools.partial(mlp, cfg, p), x + out, block), y


@functools.partial(jax.jit, static_argnames=("cfg_t", "n_last", "block"))
def _full_layer(cfg_t, lam_init, n_last, block, p, x):
    """The ``full`` layer: keys and values of every position, the output at
    the last ``n_last``.  Returns ``(x at those, k, v)``."""
    cfg = dict(cfg_t)
    eps = cfg["layer_norm_eps"]
    q_width, kv_width = _widths(cfg)
    seq = x.shape[0]
    w = p["mixer"]["w_qkv"]
    kv = in_row_blocks(lambda r: _mm(cfg, layer_norm(r, p["norm1"], eps), w[:, q_width:]), x, block)
    k, v = kv[:, :kv_width], kv[:, kv_width:]
    last = x[seq - n_last:]
    q = _mm(cfg, layer_norm(last, p["norm1"], eps), w[:, :q_width])
    pos = jnp.arange(seq)
    out = diff_attention(cfg, p["mixer"], lam_init, q, k, v, pos[seq - n_last:], pos)
    return mlp(cfg, p, last + _mm(cfg, out, p["mixer"]["w_o"])), k, v


@functools.partial(jax.jit, static_argnames=("cfg_t", "kind"))
def _cross_layer(cfg_t, kind, lam_init, p, x, memory, k, v):
    """A ``gmu`` or ``cross`` layer at the last positions."""
    cfg = dict(cfg_t)
    eps = cfg["layer_norm_eps"]
    h = layer_norm(x, p["norm1"], eps)
    if kind == "gmu":
        out = _mm(cfg, jax.nn.silu(_mm(cfg, h, p["mixer"]["w_in"])) * memory, p["mixer"]["w_out"])
    else:
        pos = jnp.arange(k.shape[0])
        out = _mm(cfg, diff_attention(cfg, p["mixer"], lam_init, _mm(cfg, h, p["mixer"]["w_q"]), k,
                                      v, pos[k.shape[0] - x.shape[0]:], pos), p["mixer"]["w_o"])
    return mlp(cfg, p, x + out)


@functools.partial(jax.jit, static_argnames=("cfg_t", "blocks"))
def _head(cfg_t, blocks, norm, embed, x):
    cfg = dict(cfg_t)
    h = layer_norm(x, norm, cfg["layer_norm_eps"])
    parts = jax.lax.map(lambda e: _mm(cfg, h, e.T), embed.reshape((blocks, -1) + embed.shape[1:]))
    return jnp.moveaxis(parts, 0, 1).reshape(x.shape[0], -1)


def logits_at_end(cfg: dict, params: dict, tokens, n_last: int, block: int = 4096,
                  with_cache: bool = False):
    """Float32 logits of the last ``n_last`` positions of one sequence of token
    ids ``(seq,)``: ``(n_last, vocab)``.  ``with_cache``: also the ``full``
    layer's keys and values of every position, ``(seq, kv_heads * hd)`` each:
    what a shared cache has to hold."""
    cfg_t = _static(cfg)
    kinds = layer_types(cfg)
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    memory = k = v = None
    for layer, (kind, p) in enumerate(zip(kinds, params["layers"])):
        if kind in ("mamba", "window"):
            x, y = _self_layer(cfg_t, kind, lambda_init(layer), block, p, x)
            memory = y if y is not None else memory
        elif kind == "full":
            x, k, v = _full_layer(cfg_t, lambda_init(layer), n_last, block, p, x)
            memory = None if memory is None else memory[memory.shape[0] - n_last:]
        else:
            x = _cross_layer(cfg_t, kind, lambda_init(layer), p, x, memory, k, v)
    vocab = params["embed"].shape[0]
    blocks = max(b for b in (1, 2, 4, 8, 16, 32) if vocab % b == 0)
    logits = _head(cfg_t, blocks, params["final_norm"], params["embed"], x)
    return (logits, k, v) if with_cache else logits
