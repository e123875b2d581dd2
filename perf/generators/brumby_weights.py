"""Seeded weights of a Brumby language model (power-retention layers) and the
sessions' prompts, drawn here from the configuration's sizes and its
``assumed.init`` alone: nothing of the program is imported, so what the cell
computes, and what its limits mean, is fixed by files under ``perf/``.

The tree is the plain one the program and the reference read: ``embed``,
``layers[i]`` with ``norm1``, ``mixer`` (``w_qkv``: queries, keys, values side
by side; ``w_g``, ``b_g``: the gate; ``q_norm``, ``k_norm``; ``w_o``),
``norm2``, ``mlp``; ``final_norm``; ``head``.  Matrices are stored ``(fan_in,
fan_out)``, the embedding and the head ``(vocab, d)``, in the configuration's
type; vectors in float32.

The draw (``assumed.init`` gives the numbers and their reasons), as
``sambay_weights.py`` draws SambaY's:

- every matrix ``N(0, 1/fan_in)``; one that writes into the residual stream
  (``w_o``, ``w_down``) times ``residual_scale`` besides; the embedding's rows
  are the stream itself, ``N(0, embed_std^2)``; the head ``N(0, 1/d)``, so that
  the logits have unit scale; norms at gain 1;
- **the gate remembers**: ``w_g`` is ``N(0, gate_std^2 / d)`` and ``b_g`` is
  ``gate_bias`` for every head, so that a gate's logit is ``N(gate_bias,
  gate_std^2)`` and the median half-life of a state is ``ln 2 / -log
  sigmoid(gate_bias)`` positions (2,067 at 8).  With ``N(0, 1)`` logits a
  state would forget within two positions and neither a stale state nor a
  lost chunk of the prefill would show in any logit.

Leaves are made on the device one by one, a large one in row blocks.  The
prompts are token ids uniform over the vocabulary, ``serve.sessions`` rows of
``serve.context``.  The model is not sharded: of ``sharding`` only the mesh is
used."""

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from . import key_from_seed
from .sambay_weights import BLOCK_ELEMENTS, F32, _normal


def weights(config: dict, key, place) -> dict:
    """The parameter tree; ``place(array)`` puts a leaf where it belongs."""
    init = config["assumed"]["init"]
    d, f, hd = config["hidden_size"], config["intermediate_size"], config["head_dim"]
    heads, kv_heads = config["num_attention_heads"], config["num_key_value_heads"]
    q_width, kv_width = heads * hd, kv_heads * hd
    dtype = jnp.dtype(config["dtype"])
    out = float(init["residual_scale"])
    count = iter(range(1 << 30))

    def matrix(rows, cols, std):
        blocks = 1
        while rows * cols // blocks > BLOCK_ELEMENTS and rows % (2 * blocks) == 0:
            blocks *= 2
        return place(_normal(jax.random.fold_in(key, next(count)), std, (rows, cols), dtype,
                             blocks))

    def ones(n):
        return place(jnp.ones((n,), F32))

    layers = []
    for _ in range(config["num_hidden_layers"]):
        layers.append({
            "norm1": {"w": ones(d)},
            "mixer": {
                "w_qkv": matrix(d, q_width + 2 * kv_width, d ** -0.5),
                "w_g": matrix(d, kv_heads, init["gate_std"] * d ** -0.5),
                "b_g": place(jnp.full((kv_heads,), init["gate_bias"], F32)),
                "q_norm": ones(hd), "k_norm": ones(hd),
                "w_o": matrix(q_width, d, out * q_width ** -0.5),
            },
            "norm2": {"w": ones(d)},
            "mlp": {"w_gate": matrix(d, f, d ** -0.5), "w_up": matrix(d, f, d ** -0.5),
                    "w_down": matrix(f, d, out * f ** -0.5)},
        })
    return {"embed": matrix(config["vocab_size"], d, init["embed_std"]), "layers": layers,
            "final_norm": {"w": ones(d)}, "head": matrix(config["vocab_size"], d, d ** -0.5)}


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
