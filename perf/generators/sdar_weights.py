"""Seeded weights of SDAR-30B-A3B-Chat (the Qwen3-MoE layer: grouped-query
attention with per-head q/k norms, 128 softmax-routed experts, an untied
head) and the sessions' prompts, of lengths of their own, drawn here from the
configuration's sizes and its ``assumed.init`` alone: nothing of the program
is imported, so what the cell computes, and what its limits mean, is fixed by
files under ``perf/``.

The tree is the plain one the program and the reference read: ``embed``,
``layers[i]`` with ``norm1``, ``attn`` (``w_q``, ``w_k``, ``w_v``, ``q_norm``,
``k_norm``, ``w_o``), ``norm2`` and ``moe`` (``router``, ``experts`` with
``w_gate``, ``w_up`` of ``(experts, d, f)`` and ``w_down`` of ``(experts, f,
d)``); ``final_norm``; ``head``.  Matrices are stored ``(fan_in, fan_out)``,
the embedding and the head ``(vocabulary, d)``, in the configuration's type;
vectors in float32.

The draw (``assumed.init`` gives the numbers and their reasons):

- every matrix ``N(0, 1/fan_in)``; ``w_down`` times ``residual_scale``,
  ``w_o`` times ``attention_out_scale``; the head ``N(0, 1/d)``; the layer
  norms at gain 1;
- **the attention reads the neighbours**: every embedding row shares one
  direction ``m`` (``e_v = embed_shared m + (1 - embed_shared^2)^1/2 r_v``),
  and in every layer ``W_q`` and ``W_k`` map ``m`` onto one vector ``c_g`` a
  key/value group (``position_tie`` of the product, the rest fresh normals),
  whose energy lies in the ``position_pairs`` fastest rotary pairs; the q/k
  norms' gain is ``qk_norm_gain``.  A query and a key then score mostly by
  ``c_g . R(j - i) c_g``, which is largest at ``j = i`` and falls within a few
  positions: a row weighs its block's positions and its own most, so that a
  block's rows read each other's tokens (random weights would spread every
  row evenly over 32,000 keys, and no mask inside a block would show).

Leaves are made on the device one by one, a large one in blocks of its first
axis.  The prompts are token ids uniform over the vocabulary but the mask id,
``serve.sessions`` rows of ``serve.context``; session ``b``'s prompt is its
first ``lengths[b]`` ids, ``lengths`` the ``serve.sessions`` evenly spaced
numbers from ``serve.context_min`` to ``serve.context - serve.decode_blocks x
block_length`` (a call's blocks end at ``serve.context`` at most) in an order
drawn from the seed: every session starts at a position of its own, with a
prompt tail of 0 to 3 tokens, and every seed streams the same key/value rows,
so that the device work of a call does not follow the seed.  The model is not
sharded: of ``sharding`` only the mesh is used."""

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import key_from_seed
from .sambay_weights import BLOCK_ELEMENTS, F32, _normal


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "blocks"))
def _shared_rows(key, m, share, shape, dtype, blocks):
    """``share m + (1 - share^2)^1/2 r_v`` a row, ``r_v`` fresh normals."""
    def rows(k, n):
        fresh = jax.random.normal(k, (n, shape[1]), F32)
        return (share * m[None, :] + jnp.sqrt(1.0 - share * share) * fresh).astype(dtype)

    if blocks == 1:
        return rows(key, shape[0])
    made = jax.lax.map(lambda k: rows(k, shape[0] // blocks), jax.random.split(key, blocks))
    return made.reshape(shape)


@functools.partial(jax.jit, static_argnames=("heads", "dtype"))
def _positional(key, m, cs, tie, heads, dtype):
    """``(d, heads x hd)``: head ``h`` maps ``m`` onto ``c`` of its group
    (``tie`` of the product, scaled as a fresh column block is), plus fresh
    ``N(0, (1 - tie^2) / d)``."""
    d = m.shape[0]
    kv, hd = cs.shape
    c = jnp.repeat(cs, heads // kv, axis=0).reshape(-1)
    m_hat = m / jnp.linalg.norm(m)
    fresh = jax.random.normal(key, (d, heads * hd), F32) * d ** -0.5
    tied = tie * math.sqrt(hd / d) * m_hat[:, None] * c[None, :]
    return (tied + jnp.sqrt(1.0 - tie * tie) * fresh).astype(dtype)


@functools.partial(jax.jit, static_argnames=("kv", "hd", "pairs"))
def _turned_vectors(key, kv, hd, pairs):
    """One unit vector a key/value group, its energy in the first ``pairs``
    rotary pairs (lanes ``i`` and ``i + hd / 2``), phases drawn."""
    phase = jax.random.uniform(key, (kv, pairs), F32, 0.0, 2.0 * math.pi)
    c = jnp.zeros((kv, hd), F32)
    c = c.at[:, :pairs].set(jnp.cos(phase)).at[:, hd // 2:hd // 2 + pairs].set(jnp.sin(phase))
    return c / math.sqrt(pairs)


def weights(config: dict, key, place) -> dict:
    """The parameter tree; ``place(array)`` puts a leaf where it belongs."""
    init = config["assumed"]["init"]
    d, f, hd = config["hidden_size"], config["moe_intermediate_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    experts, vocab = config["num_experts"], config["vocab_size"]
    dtype = jnp.dtype(config["dtype"])
    out, tie = float(init["residual_scale"]), float(init["position_tie"])
    gain = float(init["qk_norm_gain"])
    count = iter(range(1 << 30))

    def next_key():
        return jax.random.fold_in(key, next(count))

    def blocks_of(shape):
        blocks = 1
        while math.prod(shape) // blocks > BLOCK_ELEMENTS and shape[0] % (2 * blocks) == 0:
            blocks *= 2
        return blocks

    def matrix(shape, std):
        return place(_normal(next_key(), std, tuple(shape), dtype, blocks_of(shape)))

    def fill(n, value=1.0):
        return place(jnp.full((n,), value, F32))

    m = jax.random.normal(next_key(), (d,), F32)

    def layer():
        cs = _turned_vectors(next_key(), kv, hd, int(init["position_pairs"]))
        return {
            "norm1": {"w": fill(d)},
            "attn": {
                "w_q": place(_positional(next_key(), m, cs, tie, heads, dtype)),
                "w_k": place(_positional(next_key(), m, cs, tie, kv, dtype)),
                "w_v": matrix((d, kv * hd), d ** -0.5),
                "q_norm": fill(hd, gain), "k_norm": fill(hd, gain),
                "w_o": matrix((heads * hd, d),
                              float(init["attention_out_scale"]) * (heads * hd) ** -0.5),
            },
            "norm2": {"w": fill(d)},
            "moe": {"router": matrix((d, experts), d ** -0.5),
                    "experts": {"w_gate": matrix((experts, d, f), d ** -0.5),
                                "w_up": matrix((experts, d, f), d ** -0.5),
                                "w_down": matrix((experts, f, d), out * f ** -0.5)}},
        }

    layers = [layer() for _ in range(config["num_hidden_layers"])]
    embed = place(_shared_rows(next_key(), m, float(init["embed_shared"]), (vocab, d), dtype,
                               blocks_of((vocab, d))))
    return {"embed": embed, "layers": layers, "final_norm": {"w": fill(d)},
            "head": matrix((vocab, d), d ** -0.5)}


def make(config: dict, seed: int, sharding) -> dict:
    everywhere = NamedSharding(sharding.mesh, P())
    key = key_from_seed(seed)
    params = weights(config, jax.random.fold_in(key, 1),
                     lambda leaf: jax.device_put(leaf, everywhere))
    serve, mask = config["serve"], config["assumed"]["mask_token_id"]
    longest = serve["context"] - serve["decode_blocks"] * config["assumed"]["block_length"]

    def ids(k):
        drawn = jax.random.randint(k, (serve["sessions"], serve["context"]), 0,
                                   config["vocab_size"] - 1, jnp.int32)
        return drawn + (drawn >= mask).astype(jnp.int32)     # every id but the mask's

    tokens = jax.jit(ids, out_shardings=everywhere)(jax.random.fold_in(key, 2))
    sessions, shortest = serve["sessions"], serve["context_min"]
    ladder = shortest + jnp.arange(sessions, dtype=jnp.int32) * (longest - shortest) // max(
        1, sessions - 1)
    lengths = jax.jit(lambda k: jax.random.permutation(k, ladder),
                      out_shardings=everywhere)(jax.random.fold_in(key, 3))
    return {"params": params, "tokens": tokens, "lengths": lengths}
