"""What the served language models share below the session: the seeded
parameter trees (a spec of ``(shape, init)`` leaves, made leaf by leaf on the
device), the product in the weights' type with float32 accumulation, the
gated-SiLU MLP, the embedding lookup, the greedy head and the rotary
frequencies under YaRN.  A model (:mod:`~heat_tpu.models.sambay`,
:mod:`~heat_tpu.models.brumby`, :mod:`~heat_tpu.models.deepseek`) keeps its
own norms, mixers and programs.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_F32 = jnp.float32


# ------------------------------------------------------------------ parameters

def is_leaf(node) -> bool:
    return isinstance(node, tuple) and len(node) == 2 and isinstance(node[0], tuple)


def spec_size(tree) -> int:
    """Parameters in a tree of ``(shape, init)`` leaves."""
    return sum(math.prod(leaf[0]) for leaf in jax.tree.leaves(tree, is_leaf=is_leaf))


@functools.partial(jax.jit, static_argnames=("shape", "init", "dtype", "blocks"))
def make_leaf(key, shape, init, dtype, blocks):
    if init == "ones":
        return jnp.ones(shape, dtype)
    if init == "zeros":
        return jnp.zeros(shape, dtype)
    if isinstance(init, tuple):  # ("fill", value)
        return jnp.full(shape, init[1], dtype)
    if init == "a_log":  # A = -(1 .. d_state) for every channel
        return jnp.broadcast_to(
            jnp.log(jnp.arange(1, shape[0] + 1, dtype=_F32))[:, None], shape).astype(dtype)
    if init == "dt_bias":  # softplus^-1 of step sizes log-uniform in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, _F32, math.log(1e-3), math.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if blocks == 1:
        return (jax.random.normal(key, shape, _F32) * init).astype(dtype)
    part = (shape[0] // blocks,) + shape[1:]
    made = jax.lax.map(
        lambda k: (jax.random.normal(k, part, _F32) * init).astype(dtype),
        jax.random.split(key, blocks))
    return made.reshape(shape)


def init_tree(spec, dtype, key, sharding=None) -> dict:
    """The leaves of ``spec`` made one by one, each from a key of its own."""
    leaves, tree = jax.tree.flatten(spec, is_leaf=is_leaf)
    made = []
    for number, (shape, init) in enumerate(leaves):
        blocks = 1
        while math.prod(shape) // blocks > (1 << 26) and shape[0] % (2 * blocks) == 0:
            blocks *= 2
        # what the scan and the lambdas read in float32 is stored in float32
        leaf_dtype = _F32 if init in ("a_log", "dt_bias") or len(shape) == 1 else dtype
        leaf = make_leaf(jax.random.fold_in(key, number), shape, init, leaf_dtype, blocks)
        made.append(leaf if sharding is None else jax.device_put(leaf, sharding))
    return jax.tree.unflatten(tree, made)


# ---------------------------------------------------------------------- layers

def dot(x, w):
    """``x @ w`` in the weights' type with float32 accumulation."""
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=_F32)


def gated_mlp(p, h):
    """``W_down(silu(W_gate h) * W_up h)``."""
    gate, up = dot(h, p["w_gate"]), dot(h, p["w_up"])
    return dot(jax.nn.silu(gate) * up, p["w_down"])


def embed(params, tokens):
    with jax.named_scope("ht.lm.embed"):
        return jnp.take(params["embed"], tokens, axis=0).astype(_F32)


def greedy(h, table):
    """Greedy token and float32 logits of the normed ``h`` of ``(batch, d)``
    against the ``(vocab, d)`` output table."""
    logits = jax.lax.dot_general(h.astype(table.dtype), table, (((1,), (1,)), ((), ())),
                                 preferred_element_type=_F32)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32), logits


# ---------------------------------------------------------------------- rotary

def yarn_frequencies(dim: int, theta: float, factor: float = 1.0, original: int = 4096,
                     beta_fast: float = 32.0, beta_slow: float = 1.0) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies ``theta^(-2i / dim)`` stretched by
    YaRN: a pair that turns more than ``beta_fast`` times over the
    ``original`` context keeps its frequency, one that turns less than
    ``beta_slow`` times has it divided by ``factor``, and between the two
    correction dimensions the two are blended by a linear ramp.  float32;
    ``factor`` 1 is the plain embedding."""
    freq = float(theta) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if float(factor) == 1.0:
        return freq.astype(np.float32)

    def correction_dim(turns):
        return dim * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    span = (high - low) or 0.001
    stretched = np.clip((np.arange(dim // 2, dtype=np.float64) - low) / span, 0.0, 1.0)
    return (freq / factor * stretched + freq * (1.0 - stretched)).astype(np.float32)
