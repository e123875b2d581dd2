"""Plain reference for Brumby-14B-Base (Manifest AI; power retention,
arXiv:2507.04239; the shapes of its ``config.json`` are Qwen3-14B's): the full
forward pass over a whole sequence, float32 at ``highest``, **attention form
only**: no state, no chunks, no kernel, every position against every earlier
one.  Imports nothing of ``heat_tpu``; the parameter tree is the one the
configuration's generator makes (bfloat16 values, upcast here a layer at a
time).

Every layer, ``x`` the stream, ``d`` the head width, ``R`` query heads to a
key/value head:

- ``u = RMSNorm(x)``; ``q = RoPE(RMSNorm_head(W_q u))``, ``k = RoPE(RMSNorm_head
  (W_k u))``, ``v = W_v u``; one gate a key/value head, ``log g = log
  sigmoid(W_g u + b_g)``.  RoPE over the whole head with ``theta``, lane ``i``
  paired with lane ``i + d / 2``; the head norms have one gain of ``d``.
- ``a_tj = exp(sum_{s=j+1..t} log g_s) * (q_t . k_j / sqrt(d))^2`` for ``j <=
  t``; ``y_t = sum_j a_tj v_j / (sum_j a_tj + eps)``; the mixer's output is
  ``W_o`` of the heads' ``y`` side by side.
- ``x += y W_o``; ``x += W_down(silu(W_gate h) * W_up h)``, ``h = RMSNorm(x)``.

After the last layer a final RMSNorm and the untied head.

The state a served session must hold after position ``t`` is, by the recurrent
form, ``S = sum_{j<=t} exp(sum_{s=j+1..t} log g_s) phi(k_j) v_j^T`` and ``z``
the same sum of ``phi(k_j)``, with ``phi(x)`` the symmetric square of ``x /
d^(1/4)``: the ``d`` squares ``x_a^2 / sqrt(d)``, then ``sqrt(2) x_a x_b /
sqrt(d)`` for the pairs ``a < b`` in row-major order, ``d (d + 1) / 2``
features.  :func:`logits_at_end` computes that closed sum from its own keys,
values and gates, once for the end of the prompt (what a session saves) and
once for the end of the sequence (what the decode steps leave); it never
steps a state.

Departures from arXiv:2507.04239 and the released code that the builder knows
of: the paper's kernels expand the symmetric power in padded tiles (more rows
than ``d (d + 1) / 2``) and keep parts of it in bfloat16, this reference is the
exact feature count in float32; the paper's chunked form and its fused
normalisation are left out on purpose (the program has them, the reference
must not); the gate's parametrisation (one number a key/value head from the
layer's input, with an offset), the place of ``eps`` and the scale ``1 /
sqrt(d)`` inside the square are the configuration's ``assumed``, since the
released modelling code could not be read on this machine.
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

# what the reference reads of a configuration
SIZES = ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
         "rms_norm_eps", "rope_theta", "retention_eps")


def _mm(x, w):
    return jnp.dot(x, w.astype(F32), precision=HI)


def rms_norm(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _blocks(arrays, size):
    """Each array's leading axis padded with zeros and cut into blocks of
    ``size``: ``(n, ...) -> (blocks, size, ...)``."""
    pad = (-arrays[0].shape[0]) % size
    padded = [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1)) for a in arrays]
    return tuple(a.reshape((-1, size) + a.shape[1:]) for a in padded)


def rope(cfg, x, positions):
    """``x``: ``(seq, heads, d)``."""
    d = cfg["head_dim"]
    freq = (float(cfg["rope_theta"]) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
            ).astype(np.float32)
    angle = positions.astype(F32)[:, None] * freq
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    lo, hi = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)


def sym_square(x, d):
    """``phi(x)`` over the last axis: the squares, then the pairs ``a < b``."""
    a, b = np.triu_indices(d, 1)
    return jnp.concatenate(
        [x * x, math.sqrt(2.0) * x[..., a] * x[..., b]], axis=-1) / math.sqrt(d)


def retention(cfg, q, k, v, log_g, block):
    """The attention form.  ``q``: ``(seq, heads, d)``; ``k``, ``v``: ``(seq,
    kv_heads, d)``; ``log_g``: ``(seq, kv_heads)``.  Returns ``(seq, heads *
    d)``, computed for ``block`` query positions and one key/value head at a
    time."""
    seq, heads, d = q.shape
    kv_heads = k.shape[1]
    run = jnp.cumsum(log_g, axis=0)                            # (seq, kv_heads)
    position = jnp.arange(seq)

    def one_head(xs):
        qh, kh, vh, rh = xs                                    # (seq, R, d), (seq, d), (seq, d), (seq,)

        def rows(xs):
            qb, rb, pb = xs                                    # (block, R, d), (block,), (block,)
            score = jnp.einsum("trd,jd->trj", qb, kh, precision=HI) / math.sqrt(d)
            seen = position[None, :] <= pb[:, None]
            decay = jnp.exp(jnp.where(seen, rb[:, None] - rh[None, :], -jnp.inf))
            a = score * score * decay[:, None, :]
            return jnp.einsum("trj,jd->trd", a, vh, precision=HI) / (
                jnp.sum(a, axis=-1, keepdims=True) + cfg["retention_eps"])

        out = jax.lax.map(rows, _blocks((qh, rh, position), min(block, seq)))
        return out.reshape((-1,) + out.shape[2:])[:seq]

    qg = jnp.moveaxis(q.reshape(seq, kv_heads, heads // kv_heads, d), 1, 0)
    y = jax.lax.map(one_head, (qg, jnp.moveaxis(k, 1, 0), jnp.moveaxis(v, 1, 0), run.T))
    return jnp.moveaxis(y, 0, 1).reshape(seq, heads * d)       # (seq, kv_heads, R, d) flattened


def state_at(cfg, k, v, log_g, end, block):
    """``(S, z)`` after position ``end - 1`` by the closed sum: ``(kv_heads,
    features, d)`` and ``(kv_heads, features)``."""
    d = cfg["head_dim"]
    run = jnp.cumsum(log_g, axis=0)
    weight = jnp.exp(run[end - 1][None, :] - run[:end])        # (end, kv_heads)

    def one_head(xs):
        kh, vh, wh = xs

        def part(xs):
            kb, vb, wb = xs
            phi = sym_square(kb, d) * wb[:, None]
            return jnp.einsum("jf,jd->fd", phi, vb, precision=HI), jnp.sum(phi, axis=0)

        S, z = jax.lax.map(part, _blocks((kh, vh, wh), min(block, end)))
        return jnp.sum(S, axis=0), jnp.sum(z, axis=0)

    return jax.lax.map(one_head, (jnp.moveaxis(k[:end], 1, 0), jnp.moveaxis(v[:end], 1, 0),
                                  weight.T))


def _static(cfg: dict) -> tuple:
    return tuple((name, cfg[name]) for name in SIZES)


@functools.partial(jax.jit, static_argnames=("cfg_t", "block", "state_ends"))
def _layer(cfg_t, block, state_ends, p, x):
    """One layer over every position.  Returns the stream and the states
    after each of ``state_ends`` positions."""
    cfg = dict(cfg_t)
    eps, d = cfg["rms_norm_eps"], cfg["head_dim"]
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    seq = x.shape[0]
    mixer = p["mixer"]
    u = rms_norm(x, p["norm1"]["w"], eps)
    qkv = in_row_blocks(lambda r: _mm(r, mixer["w_qkv"]), u, block)
    q = qkv[:, :heads * d].reshape(seq, heads, d)
    k = qkv[:, heads * d:(heads + kv_heads) * d].reshape(seq, kv_heads, d)
    v = qkv[:, (heads + kv_heads) * d:].reshape(seq, kv_heads, d)
    positions = jnp.arange(seq)
    q = rope(cfg, rms_norm(q, mixer["q_norm"], eps), positions)
    k = rope(cfg, rms_norm(k, mixer["k_norm"], eps), positions)
    log_g = jax.nn.log_sigmoid(_mm(u, mixer["w_g"]) + mixer["b_g"].astype(F32))
    y = retention(cfg, q, k, v, log_g, min(block, 1024))
    x = x + in_row_blocks(lambda r: _mm(r, mixer["w_o"]), y, block)

    def mlp(r):
        h = rms_norm(r, p["norm2"]["w"], eps)
        return r + _mm(jax.nn.silu(_mm(h, p["mlp"]["w_gate"])) * _mm(h, p["mlp"]["w_up"]),
                       p["mlp"]["w_down"])

    x = in_row_blocks(mlp, x, block)
    return x, tuple(state_at(cfg, k, v, log_g, end, min(block, 1024)) for end in state_ends)


@functools.partial(jax.jit, static_argnames=("cfg_t", "blocks"))
def _head(cfg_t, blocks, norm, head, x):
    cfg = dict(cfg_t)
    h = rms_norm(x, norm["w"], cfg["rms_norm_eps"])
    parts = jax.lax.map(lambda e: _mm(h, e.T), head.reshape((blocks, -1) + head.shape[1:]))
    return jnp.moveaxis(parts, 0, 1).reshape(x.shape[0], -1)


def logits_at_end(cfg: dict, params: dict, tokens, n_last: int, block: int = 4096,
                  with_state: bool = False):
    """Float32 logits of the last ``n_last`` positions of one sequence of token
    ids ``(seq,)``: ``(n_last, vocab)``.  ``with_state``: also, for every
    layer, a pair of states ``(S, z)``: after the first ``seq - n_last``
    positions (what a session saved at the end of that prompt has to hold)
    and after all ``seq`` (what it holds once the last token was fed)."""
    cfg_t = _static(cfg)
    seq = int(tokens.shape[0])
    x = jnp.take(params["embed"], tokens, axis=0).astype(F32)
    states = []
    for p in params["layers"]:
        x, pair = _layer(cfg_t, block, (seq - n_last, seq) if with_state else (), p, x)
        states.append(pair)
    vocab = params["head"].shape[0]
    blocks = max(b for b in (1, 2, 4, 8, 16, 32) if vocab % b == 0)
    logits = _head(cfg_t, blocks, params["final_norm"], params["head"], x[seq - n_last:])
    return (logits, states) if with_state else logits
