"""Seeded weights of a DeepSeek-V3.2 language model (latent attention read
through a learned sparse selection, routed experts of which some are held)
and the sessions' prompts, drawn here from the configuration's sizes and its
``assumed.init`` alone: nothing of the program is imported, so what the cell
computes, and what its limits mean, is fixed by files under ``perf/``.

The tree is the plain one the program and the reference read: ``embed``,
``layers[i]`` with ``norm1``, ``attn`` (``w_dq``, ``q_norm``, ``w_uq``,
``w_dkv``, ``kv_norm``; ``W_ukv`` a head as ``w_uk`` ``(heads, nope, rank)``
and ``w_uv`` ``(heads, rank, v)``; ``w_o``; the indexer's ``w_iq``, ``w_ik``,
``ik_norm_w``, ``ik_norm_b``, ``w_iw``), ``norm2`` and either ``mlp`` or
``moe`` (``router`` over all the router's experts, its balancing ``bias``,
``shared``, and ``experts``: the held experts' ``w_gate``, ``w_up``,
``w_down`` stacked); ``final_norm``; ``head``.  Matrices are stored ``(fan_in,
fan_out)``, the embedding and the head ``(vocabulary held, d)``, in the
configuration's type; vectors in float32.

The draw (``assumed.init`` gives the numbers and their reasons):

- every matrix ``N(0, 1/fan_in)``; ``w_down`` times ``residual_scale``
  besides, ``w_o`` times ``attention_out_scale``; the embedding's rows are the
  stream itself, ``N(0, embed_std^2)``; the head ``N(0, 1/d)``; norms at gain
  1; the balancing bias ``N(0,
  bias_std^2)``, so that the choice and the weights differ;
- **the choice of keys matters**: a query is tied to its own position's key.
  The first ``rank`` columns of ``w_dq`` are those of ``w_dkv`` (so the first
  ``rank`` numbers of ``c_q`` are ``c_kv`` up to the norms), and on those rows
  the no-position part of ``w_uq`` is ``query_key_tie`` times a head's own
  ``w_uk``; the other rows carry what is left of a unit variance.  A position
  then scores ``query_key_tie * |k_nope|^2`` against itself, well above every
  other key, as trained heads put much of their weight on the newest tokens:
  a third of a head's weight lies on that one row, and a selection that misses
  it, or reads other rows, or all of them, moves the logits;
- **and the selection always holds the position itself**, far above its cut,
  so that no rounding decides whether that heavy row is read.  An index score
  is ``sum_h w_h relu(q_h . k)``, and the heads' weights ``w = u W_w`` take
  either sign, so tying ``q_h`` to the position's own key alone would make the
  position score itself highest or lowest by the toss of ``sum_h w_h``.  Three
  ties, all inside the indexer: the next ``index_head_dim`` columns of ``w_dq``
  are ``w_ik`` (so those numbers of ``c_q`` are the position's key before its
  LayerNorm) and on those rows every head of ``w_iq`` is ``index_key_tie``
  times the identity: ``q_h . k_t`` holds ``A = index_key_tie x 128`` for every
  head but in the last lane; the key LayerNorm's offset is ``index_bias`` in
  that last lane (a constant key part ``b``, ``|b| = A``, in one lane without
  rotary embedding, so that bfloat16 keeps its full resolution for the other
  127); and ``w_iw = w_dq (w_iq' b) / |b|`` (``w_iq'`` the rows of ``w_iq``
  that are not tied), so that ``w_h = q_h . b / |b|``.  Inside the ``relu``
  then stands ``|b| w_h + A [s = t] + noise``: a head of negative weight is
  shut for nearly every key, and the position's own key gains ``A`` in every
  head of positive weight, 0.34 A a head in the mean.  At the published widths
  that is eight spreads of the scores above their mean and no less than four
  (the heads' weights sum to more or less from one position to the next),
  where the cut of 2,048 in 32,768 lies 1.5 above it (1,024 positions of one
  draw: the position itself was the highest score in every one).

Leaves are made on the device one by one, a large one in blocks of its first
axis.  The prompts are token ids uniform over the vocabulary held,
``serve.sessions`` rows of ``serve.context``.  The model is not sharded: of
``sharding`` only the mesh is used."""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import key_from_seed
from .sambay_weights import BLOCK_ELEMENTS, F32, _normal


@functools.partial(jax.jit, static_argnames=("q_rank", "heads", "width", "first", "dtype"))
def _tied_iq(key, tie, q_rank, heads, width, first, dtype):
    """``w_iq`` ``(q_rank, heads * width)``: on the rows ``first .. first +
    width`` every head is ``tie`` times the identity, the other rows carry
    what is left of a unit variance."""
    fresh = jax.random.normal(key, (q_rank, heads, width), F32) * jnp.sqrt(
        (1.0 - tie * tie) / (q_rank - width))
    # the last lane holds the keys' constant part: the tie leaves it out, so that a head's
    # product with that part is its weight and nothing the heads share
    eye = jnp.eye(width, dtype=F32).at[-1, -1].set(0.0)
    own = jnp.broadcast_to(tie * eye[:, None, :], (width, heads, width))
    out = jax.lax.dynamic_update_slice(fresh, own, (first, 0, 0))
    return out.reshape(q_rank, heads * width).astype(dtype)


@functools.partial(jax.jit, static_argnames=("heads", "first", "dtype"))
def _tied_iw(w_dq, w_iq, offset, heads, first, dtype):
    """``w_iw = w_dq (w_iq' b) / |b|``: a head's weight is the product of its
    query's fresh part with the keys' constant part (``w_iq'`` is ``w_iq``
    without the rows tied to the key, which every head shares: with them the
    heads' weights would rise and fall together)."""
    width = offset.shape[0]
    fresh = w_iq.astype(F32).reshape(w_iq.shape[0], heads, width)
    fresh = jax.lax.dynamic_update_slice(fresh, jnp.zeros((width, heads, width), F32),
                                         (first, 0, 0))
    through = jnp.einsum("rhj,j->rh", fresh, offset)
    return (jnp.dot(w_dq.astype(F32), through) / jnp.linalg.norm(offset)).astype(dtype)


@functools.partial(jax.jit, static_argnames=("q_rank", "rope", "dtype"))
def _tied_uq(key, w_uk, tie, q_rank, rope, dtype):
    """``w_uq`` ``(q_rank, heads * (nope + rope))`` whose no-position columns
    read, on the first ``rank`` rows, ``tie`` times the head's own ``w_uk``."""
    heads, nope, rank = w_uk.shape
    fresh = jax.random.normal(key, (q_rank, heads, nope + rope), F32)
    own = tie * jnp.transpose(w_uk.astype(F32), (2, 0, 1))                  # (rank, heads, nope)
    rest = fresh[rank:, :, :nope] * jnp.sqrt((1.0 - tie * tie) / (q_rank - rank))
    pe = fresh[:, :, nope:] * q_rank ** -0.5
    out = jnp.concatenate([jnp.concatenate([own, rest], axis=0), pe], axis=-1)
    return out.reshape(q_rank, heads * (nope + rope)).astype(dtype)


def weights(config: dict, key, place) -> dict:
    """The parameter tree; ``place(array)`` puts a leaf where it belongs."""
    init, size = config["assumed"]["init"], config["assumed"]["sizes"]
    d, f, fd = config["hidden_size"], config["moe_intermediate_size"], config["intermediate_size"]
    heads, rank, q_rank = (config["num_attention_heads"], config["kv_lora_rank"],
                           config["q_lora_rank"])
    nope, rope, vd = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    i_heads, i_width = config["index_n_heads"], config["index_head_dim"]
    held, routed, vocab = config["n_routed_experts"], size["router_experts"], config["vocab_size"]
    dtype = jnp.dtype(config["dtype"])
    out = float(init["residual_scale"])
    count = iter(range(1 << 30))

    def next_key():
        return jax.random.fold_in(key, next(count))

    def matrix(shape, std):
        blocks, total = 1, 1
        for n in shape:
            total *= n
        while total // blocks > BLOCK_ELEMENTS and shape[0] % (2 * blocks) == 0:
            blocks *= 2
        return place(_normal(next_key(), std, tuple(shape), dtype, blocks))

    def ones(n):
        return place(jnp.ones((n,), F32))

    def mlp(width, lead=()):
        return {"w_gate": matrix(lead + (d, width), d ** -0.5),
                "w_up": matrix(lead + (d, width), d ** -0.5),
                "w_down": matrix(lead + (width, d), out * width ** -0.5)}

    layers = []
    for i in range(config["num_hidden_layers"]):
        w_dkv = matrix((d, rank + rope), d ** -0.5)
        w_ik = matrix((d, i_width), d ** -0.5)
        w_dq = place(jnp.concatenate(
            [w_dkv[:, :rank], w_ik] + ([matrix((d, q_rank - rank - i_width), d ** -0.5)]
                                       if q_rank > rank + i_width else []), axis=1))
        w_uk = matrix((heads, nope, rank), rank ** -0.5)
        w_iq = place(_tied_iq(next_key(), float(init["index_key_tie"]), q_rank, i_heads, i_width,
                              rank, dtype))
        offset = jnp.zeros((i_width,), F32).at[-1].set(float(init["index_bias"]))
        layer = {
            "norm1": {"w": ones(d)},
            "attn": {
                "w_dq": w_dq, "q_norm": ones(q_rank),
                "w_uq": place(_tied_uq(next_key(), w_uk, float(init["query_key_tie"]), q_rank,
                                       rope, dtype)),
                "w_dkv": w_dkv, "kv_norm": ones(rank),
                "w_uk": w_uk, "w_uv": matrix((heads, rank, vd), rank ** -0.5),
                "w_o": matrix((heads * vd, d),
                              float(init["attention_out_scale"]) * (heads * vd) ** -0.5),
                "w_iq": w_iq, "w_ik": w_ik,
                "ik_norm_w": ones(i_width), "ik_norm_b": place(offset),
                "w_iw": place(_tied_iw(w_dq, w_iq, offset, i_heads, rank, dtype)),
            },
            "norm2": {"w": ones(d)},
        }
        if i < config["first_k_dense_replace"]:
            layer["mlp"] = mlp(fd)
        else:
            layer["moe"] = {
                "router": matrix((d, routed), d ** -0.5),
                "bias": place(jax.random.normal(next_key(), (routed,), F32)
                              * float(init["bias_std"])),
                "shared": mlp(f), "experts": mlp(f, (held,)),
            }
        layers.append(layer)
    return {"embed": matrix((vocab, d), float(init["embed_std"])), "layers": layers,
            "final_norm": {"w": ones(d)}, "head": matrix((vocab, d), d ** -0.5)}


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
    return {"params": params, "tokens": tokens}
