"""Brumby: a dense decoder whose every layer mixes positions by power
retention, and what a session needs to serve it.

The architecture of Brumby-14B-Base (Manifest AI; power retention,
arXiv:2507.04239; the shapes are Qwen3-14B's): pre-norm residual layers
``x += Retention(RMSNorm(x)) W_o``, ``x += W_down(silu(W_gate h) * W_up h)``
with ``h = RMSNorm(x)``, a final RMSNorm and an untied output head.  The mixer
is linear attention of degree 2: ``q = RoPE(RMSNorm_head(W_q u))``, ``k``
likewise, ``v = W_v u``, one gate a key/value head ``log g = log sigmoid(W_g u
+ b_g)``, weights ``(q . k / sqrt(d))^2`` under the running product of the
gates, normalised by their sum (:mod:`heat_tpu.ops.power_retention`).  It has
an exact recurrent form, so a layer keeps no keys or values: its cache is a
state of ``d (d + 1) / 2`` features by ``d + 1`` numbers a key/value head,
the same size at every position (34 MB a layer and sequence at the published
widths), read and written whole by every decode step.

A :class:`~heat_tpu.models.session.DecodeSession` serves it, the class that
serves every model here: ``Brumby(cfg).session(batch, max_context)``, then
``prefill`` (chunks of ``PREFILL_CHUNK`` positions: inside a chunk the
attention form, across chunks the state), ``decode`` (greedy steps as one
``lax.scan``, the state updated in place by one pass of the kernel
``ht_power_retention_step``, one readback) and ``save`` / ``rewind``, which
copy the whole state: there is nothing else to return to.  How far a session
can go is bounded by ``max_position_embeddings``, not by a cache.  Weights and
activations entering a matrix product are ``cfg.dtype`` (bfloat16); the
stream, norms, gates, the rotary embedding, the state and every product with
it are float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import telemetry
from ..ops.power_retention import (feature_blocks, retention_chunked, retention_step,
                                   state_rows)
from ._lm import (dot as _dot, embed as _embed, gated_mlp as _gated_mlp, greedy as _greedy,
                  init_tree, rms_norm as _rms_norm, rope_half, spec_size)
from .session import DecodeSession, tree_bytes

__all__ = ["Brumby", "BrumbyConfig"]

_F32 = jnp.float32

# positions a prefill program walks at once, all of them one attention-form
# chunk of the retention inside it: at 256 the feature expansion of one
# key/value head's queries is 0.68 GB for 16 sequences
PREFILL_CHUNK = 256

# the seeded gate remembers: logits N(GATE_BIAS, GATE_STD^2) give a median
# half-life of some 2,000 positions (N(0, 1) would forget in two)
GATE_BIAS = 8.0
GATE_STD = 1.0


@dataclasses.dataclass(frozen=True)
class BrumbyConfig:
    """Sizes of one model.  The defaults are Brumby-14B-Base's ``config.json``;
    what that file does not say of the retention layer (degree, gate, epsilon)
    is this repository's reading of arXiv:2507.04239."""

    vocab_size: int = 151936
    hidden_size: int = 5120
    intermediate_size: int = 17408
    num_attention_heads: int = 40
    num_key_value_heads: int = 8
    head_dim: int = 128
    num_hidden_layers: int = 40
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1e6
    max_position_embeddings: int = 32768
    tie_word_embeddings: bool = False
    attention_bias: bool = False
    retention_degree: int = 2
    retention_eps: float = 1e-6
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.retention_degree != 2:
            raise ValueError("the retention kernel and its state are those of degree 2")
        if self.tie_word_embeddings or self.attention_bias:
            raise ValueError("Brumby has an untied head and no bias on q, k, v, o")
        if self.num_attention_heads % self.num_key_value_heads or self.head_dim % 2:
            raise ValueError("a key/value head serves a whole number of query heads, "
                             "and the rotary embedding pairs the halves of a head")

    @classmethod
    def from_dict(cls, published: dict, **assumed) -> "BrumbyConfig":
        """From a published ``config.json`` (keys it does not know are
        ignored) and what the publication leaves to convention."""
        names = {f.name for f in dataclasses.fields(cls)}
        known = {k: v for k, v in published.items() if k in names}
        known.update(assumed)
        return cls(**known)

    q_width = property(lambda self: self.num_attention_heads * self.head_dim)
    kv_width = property(lambda self: self.num_key_value_heads * self.head_dim)
    group = property(lambda self: self.num_attention_heads // self.num_key_value_heads)


# ------------------------------------------------------------------ parameters

def param_spec(cfg: BrumbyConfig) -> dict:
    """The parameter tree as ``(shape, init)`` leaves, from shapes alone."""
    d, f, hd = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    heads = cfg.num_key_value_heads
    out = 1.0 / math.sqrt(2.0 * cfg.num_hidden_layers)

    def norm(width=d):
        return {"w": ((width,), "ones")}

    layer = {
        "norm1": norm(),
        "mixer": {
            "w_qkv": ((d, cfg.q_width + 2 * cfg.kv_width), d ** -0.5),
            "w_g": ((d, heads), GATE_STD * d ** -0.5),
            "b_g": ((heads,), ("fill", GATE_BIAS)),
            "q_norm": norm(hd)["w"], "k_norm": norm(hd)["w"],
            "w_o": ((cfg.q_width, d), out * cfg.q_width ** -0.5),
        },
        "norm2": norm(),
        "mlp": {"w_gate": ((d, f), d ** -0.5), "w_up": ((d, f), d ** -0.5),
                "w_down": ((f, d), out * f ** -0.5)},
    }
    # a row of the embedding is the stream itself (unit scale); the head is
    # N(0, 1/d), so that the logits have unit scale
    return {"embed": ((cfg.vocab_size, d), 1.0), "layers": [layer] * cfg.num_hidden_layers,
            "final_norm": norm(), "head": ((cfg.vocab_size, d), d ** -0.5)}


def param_count(cfg: BrumbyConfig) -> dict:
    """Parameters of one layer, of the embedding, of the head and of the
    whole model; shapes only."""
    spec = param_spec(cfg)
    return {"layer": spec_size(spec["layers"][0]), "embed": spec_size(spec["embed"]),
            "head": spec_size(spec["head"]), "total": spec_size(spec)}


def init_params(cfg: BrumbyConfig, key, sharding=None) -> dict:
    """Seeded parameters, made on the device leaf by leaf as SambaY's are:
    matrices ``N(0, 1/fan_in)``, those that write into the residual stream
    scaled by ``1/sqrt(2 L)`` besides, norms at one, the gate's offset at
    ``GATE_BIAS``."""
    return init_tree(param_spec(cfg), jnp.dtype(cfg.dtype), key, sharding)


# ---------------------------------------------------------------------- layers

def _retention(cfg, p, x, pos0, S, z):
    """The mixer on the stream ``x`` of ``(batch, seq, d)`` whose first
    position is ``pos0``.  Returns ``(output, S, z)``."""
    batch, seq = x.shape[:2]
    heads, kv_heads, hd = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    with jax.named_scope("ht.lm.retention"):
        u = _rms_norm(x, p["norm1"]["w"], cfg.rms_norm_eps)
        mixer = p["mixer"]
        qkv = _dot(u, mixer["w_qkv"])
        positions = pos0 + jnp.arange(seq, dtype=jnp.int32)
        q = qkv[..., :cfg.q_width].reshape(batch, seq, heads, hd)
        k = qkv[..., cfg.q_width:cfg.q_width + cfg.kv_width].reshape(batch, seq, kv_heads, hd)
        v = qkv[..., cfg.q_width + cfg.kv_width:].reshape(batch, seq, kv_heads, hd)
        q = rope_half(_rms_norm(q, mixer["q_norm"], cfg.rms_norm_eps), positions, cfg.rope_theta)
        k = rope_half(_rms_norm(k, mixer["k_norm"], cfg.rms_norm_eps), positions, cfg.rope_theta)
        q = q.reshape(batch, seq, kv_heads, cfg.group, hd)
        log_g = -jax.nn.softplus(-(_dot(u, mixer["w_g"]) + mixer["b_g"]))
        with jax.named_scope("ht.lm.retention_state"):
            if seq == 1:
                y, S, z = retention_step(S, z, q[:, 0], k[:, 0], v[:, 0], log_g[:, 0],
                                         eps=cfg.retention_eps)
                y = y[:, None]
            else:
                y, S, z = retention_chunked(q, k, v, log_g, S, z, seq, eps=cfg.retention_eps)
        return _dot(y.reshape(batch, seq, cfg.q_width), mixer["w_o"]), S, z


def _mlp(cfg, p, x):
    with jax.named_scope("ht.lm.mlp"):
        return x + _gated_mlp(p["mlp"], _rms_norm(x, p["norm2"]["w"], cfg.rms_norm_eps))


def _run_layers(cfg, params, x, pos0, state):
    S, z = list(state["S"]), list(state["z"])
    for i, p in enumerate(params["layers"]):
        out, S[i], z[i] = _retention(cfg, p, x, pos0, S[i], z[i])
        x = _mlp(cfg, p, x + out)
    return x, {"S": tuple(S), "z": tuple(z)}


def _head(cfg, params, x):
    """Greedy token and float32 logits of ``x`` of ``(batch, d)``."""
    with jax.named_scope("ht.lm.head"):
        return _greedy(_rms_norm(x, params["final_norm"]["w"], cfg.rms_norm_eps), params["head"])


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnums=(2,))
@telemetry.module_name("ht_lm_retention_prefill_chunk")
def _prefill_chunk(cfg, params, state, tokens, pos0):
    """One chunk of a prompt through every layer.  Returns the state and the
    stream at the chunk's last position."""
    x, state = _run_layers(cfg, params, _embed(params, tokens), pos0, state)
    return state, x[:, -1]


@functools.partial(jax.jit, static_argnames=("cfg",))
@telemetry.module_name("ht_lm_retention_prefill_finish")
def _prefill_finish(cfg, params, x):
    return _head(cfg, params, x)


@functools.partial(jax.jit, static_argnames=("cfg", "steps"), donate_argnums=(2, 3))
@telemetry.module_name("ht_lm_retention_decode")
def _decode(cfg, params, state, token, pos, *, steps):
    """``steps`` greedy steps: the token fed at position ``pos + j`` gives the
    logits of that position and, by their argmax, the next token."""

    def step(carry, _):
        state, token, pos = carry
        x, state = _run_layers(cfg, params, _embed(params, token)[:, None], pos, state)
        chosen, logits = _head(cfg, params, x[:, 0])
        return (state, chosen, pos + 1), (chosen, logits)

    (state, token, _), (chosen, logits) = jax.lax.scan(
        step, (state, token, pos), None, length=steps)
    return state, token, chosen.T, jnp.moveaxis(logits, 0, 1)


# ----------------------------------------------------------------------- model

class Brumby:
    """The model: a configuration and its parameters.

    ``Brumby(cfg)`` draws seeded parameters (:func:`init_params`);
    ``Brumby(cfg, params)`` takes a tree of the same layout.  Serving goes
    through :meth:`session`."""

    def __init__(self, cfg: BrumbyConfig, params: Optional[dict] = None, *, seed: int = 0,
                 comm=None):
        from ..parallel.mesh import get_comm

        self.cfg = cfg
        self.comm = comm or get_comm()
        self._placement = self.comm.replicated(0)
        if params is None:
            params = init_params(cfg, jax.random.key(seed), self._placement)
        self.params = params

    def session(self, batch: int, max_context: int) -> DecodeSession:
        return DecodeSession(self, batch, max_context)

    prefill_chunk = property(lambda self: PREFILL_CHUNK)

    def serve_cache(self, batch: int, max_context: int):
        """No part of the cache grows with the context; the state a snapshot
        copies is all of it: ``S`` and ``z`` of every layer."""
        cfg = self.cfg
        if max_context > cfg.max_position_embeddings:
            raise ValueError(f"{max_context} positions pass the model's "
                             f"{cfg.max_position_embeddings}")
        hd, heads = cfg.head_dim, cfg.num_key_value_heads

        def zeros(*shape):
            return jnp.zeros(shape, _F32, device=self._placement)

        layers = range(cfg.num_hidden_layers)
        state = {"S": tuple(zeros(batch, heads, hd, state_rows(hd)) for _ in layers),
                 "z": tuple(zeros(batch, heads, feature_blocks(hd), hd) for _ in layers)}
        return cfg.max_position_embeddings, (), state

    def serve_bytes(self, shared, state) -> dict:
        return {"state": tree_bytes(state)}

    def serve_prefill(self, shared, state, ids, position: int):
        cfg, params = self.cfg, self.params
        for start in range(0, int(ids.shape[1]), PREFILL_CHUNK):
            state, x = _prefill_chunk(cfg, params, state, ids[:, start:start + PREFILL_CHUNK],
                                      np.int32(position + start))
        token, logits = _prefill_finish(cfg, params, x)
        return shared, state, token, logits

    def serve_notes(self, session: DecodeSession, steps: int):
        cfg = self.cfg
        held = tree_bytes(session._state)
        notes = dict(batch=session.batch, context=session.position, steps=steps,
                     layers=cfg.num_hidden_layers, state_bytes=held,
                     kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim)
        return notes, {"state_bytes_stepped": 2 * steps * held}

    def serve_decode(self, shared, state, token, position: int, steps: int):
        state, token, chosen, logits = _decode(self.cfg, self.params, state, token,
                                               np.int32(position), steps=steps)
        return shared, state, token, chosen, logits
