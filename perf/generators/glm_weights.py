"""Seeded weights of GLM-4.7-Flash (latent attention read in full, sigmoid-
routed experts beside a shared expert, and its multi-token-prediction module)
and the sessions' prompts, of lengths of their own, drawn here from the
configuration's sizes and its ``assumed.init`` alone: nothing of the program
is imported, so what the cell computes, and what its limits mean, is fixed by
files under ``perf/``.

The tree is the plain one the program and the reference read: ``embed``,
``layers[i]`` with ``norm1``, ``attn`` (``w_dq``, ``q_norm``, ``w_uq``,
``w_dkv``, ``kv_norm``, ``w_uk`` ``(heads, nope, rank)``, ``w_uv`` ``(heads,
rank, v)``, ``w_o``), ``norm2`` and either ``mlp`` or ``moe`` (``router``,
``bias``, ``shared``, ``experts``); ``final_norm``; ``head``; ``mtp``
(``enorm``, ``hnorm``, ``eh_proj`` ``(2d, d)``, ``layer`` as the others,
``head_norm``).  Matrices are stored ``(fan_in, fan_out)``, the embedding and
the head ``(vocabulary, d)``, in the configuration's type; vectors in
float32.

The draw (``assumed.init`` gives the numbers and their reasons):

- every matrix ``N(0, 1/fan_in)``; ``w_down`` times ``residual_scale``,
  ``w_o`` times ``attention_out_scale``; the embedding's rows ``N(0,
  embed_std^2)``; norms at gain 1; the balancing bias ``N(0, bias_std^2)``;
- **the attention matters**: a query is tied to its own position's key, as in
  ``perf/generators/deepseek_weights.py`` (the first ``rank`` columns of
  ``w_dq`` are those of ``w_dkv``, and on those rows the no-position part of
  ``w_uq`` is ``query_key_tie`` times the head's own ``w_uk``), so that a head
  puts much of its weight on the newest row and a read that misses it moves
  the logits;
- **the draft agrees with the model at a rate set by the weights**: the head's
  row ``v`` is ``(head_tie e_sigma(v) / embed_std + (1 - head_tie^2)^1/2
  r_v) / d^1/2`` (``sigma`` a permutation drawn from the seed, ``r_v`` fresh
  normals), so that greedy decoding leans on the current token: the token
  after ``t`` is ``sigma^-1(t)`` unless what the blocks wrote into the stream
  outweighs the tie; ``eh_proj``'s embedding half is ``eh_tie`` times the
  identity plus what is left of a unit variance, its other half ``N(0,
  1/d)``, so that the MTP module predicts the same function one position on
  from the next token, and the two disagree where the blocks' writes (the
  main model's at the later position, the MTP layer's at its own) tip one
  choice and not the other.  How often is computed, not set: the acceptance
  rate is what the cell measures.

Leaves are made on the device one by one, a large one in blocks of its first
axis.  The prompts are token ids uniform over the vocabulary, ``serve.sessions``
rows of ``serve.context``; session ``b``'s prompt is its first ``lengths[b]``
ids, ``lengths`` uniform over ``serve.context_min .. serve.context``.  The
model is not sharded: of ``sharding`` only the mesh is used."""

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import key_from_seed
from .deepseek_weights import _tied_uq
from .sambay_weights import BLOCK_ELEMENTS, F32, _normal


@functools.partial(jax.jit, static_argnames=("dtype",))
def _tied_head(key, embed, tie, std, dtype):
    """``(tie e_sigma(v) / std + (1 - tie^2)^1/2 r_v) / d^1/2`` a row ``v``."""
    vocab, d = embed.shape
    sigma = jax.random.permutation(jax.random.fold_in(key, 0), vocab)
    fresh = jax.random.normal(jax.random.fold_in(key, 1), (vocab, d), F32)
    rows = tie * jnp.take(embed, sigma, axis=0).astype(F32) / std + jnp.sqrt(1.0 - tie * tie) * fresh
    return (rows * d ** -0.5).astype(dtype)


@functools.partial(jax.jit, static_argnames=("d", "dtype"))
def _tied_eh(key, tie, d, dtype):
    """``eh_proj`` ``(2d, d)``: on the embedding's half ``tie`` times the
    identity plus fresh ``N(0, (1 - tie^2) / d)``, on the stream's half ``N(0,
    1/d)``."""
    fresh = jax.random.normal(key, (2 * d, d), F32) * d ** -0.5
    tied = tie * jnp.eye(d, dtype=F32) + jnp.sqrt(1.0 - tie * tie) * fresh[:d]
    return jnp.concatenate([tied, fresh[d:]], axis=0).astype(dtype)


def weights(config: dict, key, place) -> dict:
    """The parameter tree; ``place(array)`` puts a leaf where it belongs."""
    init = config["assumed"]["init"]
    d, f, fd = config["hidden_size"], config["moe_intermediate_size"], config["intermediate_size"]
    heads, rank, q_rank = (config["num_attention_heads"], config["kv_lora_rank"],
                           config["q_lora_rank"])
    nope, rope, vd = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    experts, vocab = config["n_routed_experts"], config["vocab_size"]
    dtype = jnp.dtype(config["dtype"])
    out = float(init["residual_scale"])
    count = iter(range(1 << 30))

    def next_key():
        return jax.random.fold_in(key, next(count))

    def matrix(shape, std):
        blocks = 1
        while math.prod(shape) // blocks > BLOCK_ELEMENTS and shape[0] % (2 * blocks) == 0:
            blocks *= 2
        return place(_normal(next_key(), std, tuple(shape), dtype, blocks))

    def ones(n):
        return place(jnp.ones((n,), F32))

    def mlp(width, lead=()):
        return {"w_gate": matrix(lead + (d, width), d ** -0.5),
                "w_up": matrix(lead + (d, width), d ** -0.5),
                "w_down": matrix(lead + (width, d), out * width ** -0.5)}

    def layer(i):
        w_dkv = matrix((d, rank + rope), d ** -0.5)
        w_dq = place(jnp.concatenate([w_dkv[:, :rank], matrix((d, q_rank - rank), d ** -0.5)],
                                     axis=1))
        w_uk = matrix((heads, nope, rank), rank ** -0.5)
        made = {
            "norm1": {"w": ones(d)},
            "attn": {
                "w_dq": w_dq, "q_norm": ones(q_rank),
                "w_uq": place(_tied_uq(next_key(), w_uk, float(init["query_key_tie"]), q_rank,
                                       rope, dtype)),
                "w_dkv": w_dkv, "kv_norm": ones(rank),
                "w_uk": w_uk, "w_uv": matrix((heads, rank, vd), rank ** -0.5),
                "w_o": matrix((heads * vd, d),
                              float(init["attention_out_scale"]) * (heads * vd) ** -0.5),
            },
            "norm2": {"w": ones(d)},
        }
        if i < config["first_k_dense_replace"]:
            made["mlp"] = mlp(fd)
        else:
            made["moe"] = {
                "router": matrix((d, experts), d ** -0.5),
                "bias": place(jax.random.normal(next_key(), (experts,), F32)
                              * float(init["bias_std"])),
                "shared": mlp(f), "experts": mlp(f, (experts,)),
            }
        return made

    layers = [layer(i) for i in range(config["num_hidden_layers"])]
    embed = matrix((vocab, d), float(init["embed_std"]))
    head = place(_tied_head(next_key(), embed, float(init["head_tie"]), float(init["embed_std"]),
                            dtype))
    mtp = {"enorm": {"w": ones(d)}, "hnorm": {"w": ones(d)},
           "eh_proj": place(_tied_eh(next_key(), float(init["eh_tie"]), d, dtype)),
           "layer": layer(config["num_hidden_layers"]), "head_norm": {"w": ones(d)}}
    return {"embed": embed, "layers": layers, "final_norm": {"w": ones(d)}, "head": head,
            "mtp": mtp}


def make(config: dict, seed: int, sharding) -> dict:
    everywhere = NamedSharding(sharding.mesh, P())
    key = key_from_seed(seed)
    params = weights(config, jax.random.fold_in(key, 1),
                     lambda leaf: jax.device_put(leaf, everywhere))
    serve = config["serve"]
    tokens = jax.jit(
        lambda k: jax.random.randint(k, (serve["sessions"], serve["context"]), 0,
                                     config["vocab_size"], jnp.int32),
        out_shardings=everywhere)(jax.random.fold_in(key, 2))
    lengths = jax.jit(
        lambda k: jax.random.randint(k, (serve["sessions"],), serve["context_min"],
                                     serve["context"] + 1, jnp.int32),
        out_shardings=everywhere)(jax.random.fold_in(key, 3))
    return {"params": params, "tokens": tokens, "lengths": lengths}
