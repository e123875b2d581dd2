"""Seeded weights of a SambaY language model and the sessions' prompts, drawn
here from the configuration's sizes and its ``assumed.init`` alone: nothing of
the program is imported, so what the cell computes, and what its limits mean,
is fixed by files under ``perf/``.

The tree is the plain one the program and the reference read: ``embed``,
``layers[i]`` with ``norm1``, ``mixer``, ``norm2``, ``mlp``, and
``final_norm``.  Matrices are stored ``(fan_in, fan_out)`` in the
configuration's type; vectors, ``a_log`` and ``b_dt`` in float32.

The draw (``assumed.init`` gives the numbers and their reasons):

- every matrix ``N(0, 1/fan_in)``; one that writes into the residual stream
  (``w_o``, ``w_down``, the ``w_out`` of Mamba and of the memory units) times
  ``residual_scale`` besides;
- the layers that read the shared cache (the ``full`` layer and every
  ``cross`` layer): ``w_o`` times ``shared_reader_gain`` more, and queries
  tied to the keys they will meet: the columns of a query head are
  ``query_temperature * (tie * K + sqrt(1 - tie^2) * N)``, ``K`` the ``full``
  layer's key columns of the key head that the query head reads, ``N`` fresh;
- norms at gain 1 and bias 0, ``subln`` and ``d_skip`` ones, ``conv_b`` zeros;
  ``A = -(1 .. d_state)`` for every channel; ``b_dt`` the inverse softplus of
  steps log-uniform in ``dt_range``; lambda vectors ``N(0, lambda_std^2)``.

Leaves are made on the device one by one, a large one in row blocks, so that
making the weights never takes more than the weights and a block.  The prompts
are token ids uniform over the vocabulary, ``serve.sessions`` rows of
``serve.context``.  The model is not sharded: of ``sharding`` only the mesh is
used."""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from perf.reference.sambay import layer_types

from . import key_from_seed

F32 = jnp.float32
BLOCK_ELEMENTS = 1 << 26


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "blocks"))
def _normal(key, std, shape, dtype, blocks):
    if blocks == 1:
        return (jax.random.normal(key, shape, F32) * std).astype(dtype)
    part = (shape[0] // blocks,) + shape[1:]
    made = jax.lax.map(lambda k: (jax.random.normal(k, part, F32) * std).astype(dtype),
                       jax.random.split(key, blocks))
    return made.reshape(shape)


@functools.partial(jax.jit, static_argnames=("groups", "pairs", "hd", "dtype"))
def _tied_queries(key, keys_of_full, temperature, tie, groups, pairs, hd, dtype):
    """Query columns ``(d, groups * pairs * 2 * hd)`` from the full layer's key
    columns ``(d, groups * 2 * hd)``: query head ``(g, p, branch)`` reads key
    head ``(g, branch)``."""
    d = keys_of_full.shape[0]
    k = keys_of_full.astype(F32).reshape(d, groups, 1, 2, hd)
    k = jnp.broadcast_to(k, (d, groups, pairs, 2, hd)).reshape(d, -1)
    fresh = jax.random.normal(key, k.shape, F32) * d ** -0.5
    return (temperature * (tie * k + jnp.sqrt(1.0 - tie * tie) * fresh)).astype(dtype)


@functools.partial(jax.jit, static_argnames=("shape",))
def _dt_bias(key, lo, hi, shape):
    dt = jnp.exp(jax.random.uniform(key, shape, F32, jnp.log(lo), jnp.log(hi)))
    return dt + jnp.log(-jnp.expm1(-dt))


def weights(config: dict, key, place) -> dict:
    """The parameter tree; ``place(array)`` puts a leaf where it belongs."""
    init, size = config["assumed"]["init"], config["assumed"]["sizes"]
    d, f = config["hidden_size"], config["intermediate_size"]
    di, ds, taps, rank = size["d_inner"], size["d_state"], size["d_conv"], size["dt_rank"]
    heads, kv_heads = config["num_attention_heads"], config["num_key_value_heads"]
    hd = d // heads
    q_width, kv_width = heads * hd, kv_heads * hd
    groups, pairs = kv_heads // 2, heads // kv_heads
    dtype = jnp.dtype(config["dtype"])
    out = float(init["residual_scale"])
    count = iter(range(1 << 30))

    def next_key():
        return jax.random.fold_in(key, next(count))

    def matrix(rows, cols, scale=1.0, fan_in=None):
        blocks = 1
        while rows * cols // blocks > BLOCK_ELEMENTS and rows % (2 * blocks) == 0:
            blocks *= 2
        std = scale * (fan_in or rows) ** -0.5
        return place(_normal(next_key(), std, (rows, cols), dtype, blocks))

    def ones(n):
        return place(jnp.ones((n,), F32))

    def zeros(n):
        return place(jnp.zeros((n,), F32))

    def norm():
        return {"w": ones(d), "b": zeros(d)}

    def attention(kind, keys_of_full):
        shared = kind in ("full", "cross")
        mixer = {"w_o": matrix(q_width, d, out * (init["shared_reader_gain"] if shared else 1.0)),
                 "subln": ones(2 * hd)}
        for name in ("lam_q1", "lam_k1", "lam_q2", "lam_k2"):
            mixer[name] = place(_normal(next_key(), init["lambda_std"], (hd,), F32, 1))
        if kind == "window":
            mixer["w_qkv"] = matrix(d, q_width + 2 * kv_width)
            return mixer, keys_of_full
        if kind == "full":
            keys_of_full = matrix(d, kv_width)
        queries = place(_tied_queries(next_key(), keys_of_full, init["query_temperature"],
                                      init["query_key_tie"], groups, pairs, hd, dtype))
        if kind == "cross":
            mixer["w_q"] = queries
        else:
            mixer["w_qkv"] = place(jnp.concatenate(
                [queries, keys_of_full, matrix(d, kv_width)], axis=1))
        return mixer, keys_of_full

    def mamba():
        lo, hi = init["dt_range"]
        return {
            "w_in": matrix(d, 2 * di),
            "conv_w": place(_normal(next_key(), taps ** -0.5, (taps, di), dtype, 1)),
            "conv_b": zeros(di),
            "w_x": matrix(di, rank + 2 * ds),
            "w_dt": matrix(rank, di),
            "b_dt": place(_dt_bias(next_key(), lo, hi, (di,))),
            "a_log": place(jnp.broadcast_to(
                jnp.log(jnp.arange(1, ds + 1, dtype=F32))[:, None], (ds, di))),
            "d_skip": ones(di),
            "w_out": matrix(di, d, out),
        }

    layers, keys_of_full = [], None
    for kind in layer_types(config):
        if kind == "mamba":
            mixer = mamba()
        elif kind == "gmu":
            mixer = {"w_in": matrix(d, di), "w_out": matrix(di, d, out)}
        else:
            mixer, keys_of_full = attention(kind, keys_of_full)
        layers.append({
            "norm1": norm(), "mixer": mixer, "norm2": norm(),
            "mlp": {"w_gate": matrix(d, f), "w_up": matrix(d, f), "w_down": matrix(f, d, out)},
        })
    # N(0, 1/d) too, so that the logits have unit scale
    return {"embed": matrix(config["vocab_size"], d, fan_in=d),
            "layers": layers, "final_norm": norm()}


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
