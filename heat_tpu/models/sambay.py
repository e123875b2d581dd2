"""SambaY: a decoder-hybrid-decoder language model, and a session that serves it.

The architecture of Phi-4-mini-flash-reasoning (arXiv:2507.06607): a
**self-decoder** of Mamba layers alternating with sliding-window attention,
closed by one full-attention layer, and a **cross-decoder** in which gated
memory units (reading the last Mamba layer's scan output of the same
position) alternate with cross-attention layers that own no keys or values:
they read the one key/value cache that the full-attention layer wrote.  So a
long generation keeps **one** cache that grows with the context, and every
other layer keeps state of constant size.

One :class:`SambaYConfig` describes a model, its ``layer_types`` naming the
kind of every layer (``mamba`` | ``window`` | ``full`` | ``gmu`` | ``cross``).
Every layer is ``x += Mixer(LN(x)); x += W_down(silu(W_gate h) * W_up h)``
with ``h = LN(x)``; there is no positional encoding; the logits come from the
tied embedding.  Attention is differential: the query heads form pairs, the
key/value heads form pairs whose two value heads are read as one of twice the
width, and a pair's output is ``softmax(q1 k1) V - lambda softmax(q2 k2) V``,
RMS-normalised and scaled by ``1 - lambda_init``.

:class:`~heat_tpu.models.session.DecodeSession` (one class for every served
model; this module supplies the cache and the programs) holds the hybrid cache
of a batch of sequences that advance in lockstep: ``prefill(tokens)`` walks a
prompt through the
self-decoder in chunks (the cross-decoder runs for the last position only: no
later position reads it), ``decode(steps)`` generates greedily on the device
and reads the chosen tokens back once, and ``save()`` / ``rewind(snapshot)``
return to an earlier position by copying the constant-size states alone, never
the shared cache.  Weights, activations and caches are ``cfg.dtype``
(bfloat16); the residual stream, softmax, norms, the scan and its state are
float32.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import telemetry
from ..ops._pallas_common import mode as pallas_mode
from ..ops.decode_attention import decode_attention, keys_fetched, masked_attention
from ..ops.selective_scan import selective_scan, selective_step
from ._lm import (dot as _dot, embed as _embed, gated_mlp as _gated_mlp, greedy as _greedy,
                  init_tree as _init_tree, spec_size as _spec_size)
from .session import DecodeSession, Snapshot, tree_bytes

__all__ = ["SambaY", "SambaYConfig", "DecodeSession", "Snapshot", "sambay_layer_types"]

KINDS = ("mamba", "window", "full", "gmu", "cross")
_F32 = jnp.float32

# tiles, found on the v5e (PERF.md section 6, PR 27): positions a prefill
# program walks at once, keys the decode kernel streams a grid step (the
# shared cache holds whole blocks of them), scan steps unrolled a chunk
PREFILL_CHUNK = 512
ATTN_BLOCK = 2048
SCAN_CHUNK = 16


def sambay_layer_types(num_layers: int, mb_per_layer: int = 2) -> Tuple[str, ...]:
    """The published placement: in the first half every ``mb_per_layer``-th
    layer is Mamba and the others window attention; then one more Mamba layer
    (whose scan output the memory units read) and the full-attention layer;
    then memory units and cross-attention in turn."""
    half = num_layers // 2
    kinds = []
    for i in range(num_layers):
        if i < half:
            kinds.append("mamba" if i % mb_per_layer == 0 else "window")
        elif i == half:
            kinds.append("mamba")
        elif i == half + 1:
            kinds.append("full")
        else:
            kinds.append("gmu" if i % 2 == 0 else "cross")
    return tuple(kinds)


@dataclasses.dataclass(frozen=True)
class SambaYConfig:
    """Sizes of one model.  The defaults are Phi-4-mini-flash-reasoning's."""

    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    num_hidden_layers: int = 32
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    mb_per_layer: int = 2
    d_inner: int = 5120
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 160
    layer_types: Tuple[str, ...] = ()
    dtype: str = "bfloat16"

    def __post_init__(self):
        kinds = tuple(self.layer_types) or sambay_layer_types(
            self.num_hidden_layers, self.mb_per_layer)
        object.__setattr__(self, "layer_types", kinds)
        if len(kinds) != self.num_hidden_layers or set(kinds) - set(KINDS):
            raise ValueError(f"layer_types must name {self.num_hidden_layers} layers of {KINDS}")
        if kinds.count("full") != 1:
            raise ValueError("exactly one full-attention layer writes the shared cache")
        last_self = kinds.index("full")
        if set(kinds[:last_self]) - {"mamba", "window"} or set(kinds[last_self + 1:]) - {"gmu", "cross"}:
            raise ValueError("mamba and window layers come before the full layer, "
                             "gmu and cross layers after it")
        if "gmu" in kinds and "mamba" not in kinds:
            raise ValueError("a gated memory unit needs a Mamba layer before it")
        if self.num_key_value_heads % 2 or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("heads must form differential pairs, a key/value pair "
                             "serving a whole number of query pairs")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size must divide by num_attention_heads")

    @classmethod
    def from_dict(cls, published: dict, **assumed) -> "SambaYConfig":
        """From a published ``config.json`` (keys it does not know are
        ignored) and the sizes the publication leaves to convention."""
        names = {f.name for f in dataclasses.fields(cls)}
        known = {k: v for k, v in published.items() if k in names}
        known.update(assumed)
        if "layer_types" in known:
            known["layer_types"] = tuple(known["layer_types"])
        return cls(**known)

    head_dim = property(lambda self: self.hidden_size // self.num_attention_heads)
    kv_groups = property(lambda self: self.num_key_value_heads // 2)      # pairs of k/v heads
    n_self = property(lambda self: self.layer_types.index("full") + 1)
    # layers that attend over the shared cache, and what a position takes in it
    n_shared_readers = property(lambda self: 1 + self.layer_types.count("cross"))
    cache_token_bytes = property(lambda self: 2 * self.num_key_value_heads * self.head_dim
                                 * jnp.dtype(self.dtype).itemsize)

    def lambda_init(self, layer: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * layer)


# ------------------------------------------------------------------ parameters

def param_spec(cfg: SambaYConfig) -> dict:
    """The parameter tree as ``(shape, init)`` leaves, from shapes alone.
    ``init`` is a standard deviation, or a name for the few that are not
    drawn from a normal."""
    d, f, di, ds = cfg.hidden_size, cfg.intermediate_size, cfg.d_inner, cfg.d_state
    hd = cfg.head_dim
    q_width = cfg.num_attention_heads * hd
    kv_width = cfg.num_key_value_heads * hd
    # a projection that writes into the residual stream is scaled down by
    # sqrt(2 L), so that the stream's variance stays of order 1 at any depth
    out = 1.0 / math.sqrt(2.0 * cfg.num_hidden_layers)

    def norm():
        return {"w": ((d,), "ones"), "b": ((d,), "zeros")}

    def lambdas():
        return {name: ((hd,), 0.1) for name in ("lam_q1", "lam_k1", "lam_q2", "lam_k2")}

    def mixer(kind):
        if kind == "mamba":
            return {
                "w_in": ((d, 2 * di), d ** -0.5),
                "conv_w": ((cfg.d_conv, di), cfg.d_conv ** -0.5),
                "conv_b": ((di,), "zeros"),
                "w_x": ((di, cfg.dt_rank + 2 * ds), di ** -0.5),
                "w_dt": ((cfg.dt_rank, di), cfg.dt_rank ** -0.5),
                "b_dt": ((di,), "dt_bias"),
                "a_log": ((ds, di), "a_log"),
                "d_skip": ((di,), "ones"),
                "w_out": ((di, d), out * di ** -0.5),
            }
        if kind == "gmu":
            return {"w_in": ((d, di), d ** -0.5), "w_out": ((di, d), out * di ** -0.5)}
        proj = {"w_o": ((q_width, d), out * q_width ** -0.5), "subln": ((2 * hd,), "ones")}
        proj.update(lambdas())
        if kind == "cross":
            proj["w_q"] = ((d, q_width), d ** -0.5)
        else:
            proj["w_qkv"] = ((d, q_width + 2 * kv_width), d ** -0.5)
        return proj

    layers = []
    for kind in cfg.layer_types:
        layers.append({
            "norm1": norm(), "mixer": mixer(kind), "norm2": norm(),
            "mlp": {"w_gate": ((d, f), d ** -0.5), "w_up": ((d, f), d ** -0.5),
                    "w_down": ((f, d), out * f ** -0.5)},
        })
    return {"embed": ((cfg.vocab_size, d), d ** -0.5), "layers": layers, "final_norm": norm()}


def param_count(cfg: SambaYConfig) -> dict:
    """Parameters per layer kind (mixer, norms and MLP of one layer), of the
    embedding (tied: counted once) and of the whole model; shapes only."""
    spec = param_spec(cfg)
    count = _spec_size
    out = {"embed": count(spec["embed"]), "final_norm": count(spec["final_norm"])}
    for kind, layer in zip(cfg.layer_types, spec["layers"]):
        out[kind] = count(layer)
        out.setdefault("mlp", count(layer["mlp"]))
    out["total"] = count(spec)
    return out


def init_params(cfg: SambaYConfig, key, sharding=None) -> dict:
    """Seeded parameters, made on the device leaf by leaf (a large leaf in row
    blocks, so that making the weights never needs more than the weights and
    a block).  Matrices are ``N(0, 1/fan_in)``, those that write into the
    residual stream scaled by ``1/sqrt(2 L)`` besides; norms start at one and
    zero; ``A = -(1..d_state)``; the step-size bias gives steps in
    ``[1e-3, 1e-1]``; the lambda vectors are ``N(0, 0.01)``."""
    return _init_tree(param_spec(cfg), jnp.dtype(cfg.dtype), key, sharding)


# ---------------------------------------------------------------------- layers

def _layer_norm(x, p, eps):
    x = x.astype(_F32)
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["w"].astype(_F32) + p["b"].astype(_F32)


def _mlp(cfg, p, x):
    with jax.named_scope("ht.lm.mlp"):
        return x + _gated_mlp(p["mlp"], _layer_norm(x, p["norm2"], cfg.layer_norm_eps))


def _mamba(cfg, p, h, conv_tail, ssm, scan_chunk):
    """The Mamba mixer on ``h`` of ``(batch, seq, d)``.  Returns the mixer's
    output, the scan output ``y`` (what a memory unit reads), and the new
    convolution tail and state."""
    di, ds, taps = cfg.d_inner, cfg.d_state, cfg.d_conv
    uz = _dot(h, p["w_in"])
    u, z = uz[..., :di], uz[..., di:]
    seq = jnp.concatenate([conv_tail.astype(_F32), u], axis=1)
    steps = u.shape[1]
    conv = p["conv_b"].astype(_F32) + sum(
        seq[:, j:j + steps] * p["conv_w"][j].astype(_F32) for j in range(taps))
    new_tail = seq[:, steps:].astype(conv_tail.dtype)
    u = jax.nn.silu(conv)
    dbc = _dot(u, p["w_x"])
    delta = jax.nn.softplus(_dot(dbc[..., :cfg.dt_rank], p["w_dt"]) + p["b_dt"])
    b, c = dbc[..., cfg.dt_rank:cfg.dt_rank + ds], dbc[..., cfg.dt_rank + ds:]
    a = -jnp.exp(p["a_log"].astype(_F32))
    if steps == 1:
        y, ssm = selective_step(u[:, 0], delta[:, 0], a, b[:, 0], c[:, 0], p["d_skip"], ssm)
        y = y[:, None]
    else:
        y, ssm = selective_scan(u, delta, a, b, c, p["d_skip"], ssm, chunk=scan_chunk)
    return _dot(y * jax.nn.silu(z), p["w_out"]), y, new_tail, ssm


def _lambda(cfg, p, layer):
    one = jnp.exp(jnp.sum(p["lam_q1"].astype(_F32) * p["lam_k1"].astype(_F32)))
    two = jnp.exp(jnp.sum(p["lam_q2"].astype(_F32) * p["lam_k2"].astype(_F32)))
    return one - two + cfg.lambda_init(layer)


def _query_rows(cfg, q):
    """``(batch, seq, heads * head_dim)`` to ``(batch, groups, seq * rows, 2 *
    head_dim)``: a key/value pair's block of query rows, the first head of a
    query pair in the lanes of the pair's first key head and the second head
    in the lanes of its second, zero elsewhere, so that one product against
    the concatenated key pair gives both score maps."""
    batch, seq = q.shape[:2]
    hd, groups = cfg.head_dim, cfg.kv_groups
    q = q.reshape(batch, seq, groups, -1, 2, hd)               # (.., pair in group, branch, hd)
    zero = jnp.zeros_like(q[..., 0, :])
    rows = jnp.stack([jnp.concatenate([q[..., 0, :], zero], -1),
                      jnp.concatenate([zero, q[..., 1, :]], -1)], axis=-2)
    rows = rows.reshape(batch, seq, groups, -1, 2 * hd)        # (.., rows, 2 hd)
    return jnp.moveaxis(rows, 1, 2).reshape(batch, groups, -1, 2 * hd)


def _combine(cfg, p, layer, att, batch, seq):
    """From the two attention maps' outputs to the mixer's output rows:
    ``(a1 - lambda a2)``, RMS-normalised over the doubled head, scaled."""
    hd, groups = cfg.head_dim, cfg.kv_groups
    att = att.reshape(batch, groups, seq, -1, 2, 2 * hd)       # (.., pair, branch, 2 hd)
    o = att[..., 0, :] - _lambda(cfg, p, layer) * att[..., 1, :]
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.layer_norm_eps)
    o = o * p["subln"].astype(_F32) * (1.0 - cfg.lambda_init(layer))
    return jnp.moveaxis(o, 2, 1).reshape(batch, seq, -1)


def _kv_rows(cfg, kv):
    """``(batch, seq, kv_heads * head_dim)`` to ``(batch, groups, seq, 2 *
    head_dim)``: the two heads of a pair side by side, as the caches hold them."""
    batch, seq = kv.shape[:2]
    return jnp.moveaxis(kv.reshape(batch, seq, cfg.kv_groups, 2 * cfg.head_dim), 1, 2)


def ring_positions(last, window):
    """The position each slot of a ring of ``window`` slots holds once
    position ``last`` has been written (slot = position mod window);
    negative where the slot is still empty."""
    slots = jnp.arange(window, dtype=jnp.int32)
    return last - jnp.mod(last - slots, window)


def _attention(cfg, kind, layer, p, h, pos0, shared, ring, block):
    """An attention mixer on ``h`` of ``(batch, seq, d)`` whose first position
    is ``pos0``.  ``window`` layers read and update ``ring``; the ``full``
    layer writes ``shared`` and reads it; ``cross`` layers (one position
    only) read it.  Returns ``(output, shared, ring)``."""
    batch, seq = h.shape[:2]
    dtype, hd = jnp.dtype(cfg.dtype), cfg.head_dim
    scale = hd ** -0.5
    q_width = cfg.num_attention_heads * hd
    kv_width = cfg.num_key_value_heads * hd
    with jax.named_scope("ht.lm.attn_proj"):
        if kind == "cross":
            q = _dot(h, p["w_q"])
        else:
            qkv = _dot(h, p["w_qkv"])
            q = qkv[..., :q_width]
            k = _kv_rows(cfg, qkv[..., q_width:q_width + kv_width]).astype(dtype)
            v = _kv_rows(cfg, qkv[..., q_width + kv_width:]).astype(dtype)
        q = _query_rows(cfg, q).astype(dtype)
    rows = q.shape[2] // seq
    q_pos = jnp.repeat(pos0 + jnp.arange(seq, dtype=jnp.int32), rows)
    if kind == "window":
        with jax.named_scope("ht.lm.window_attn"):
            window = cfg.sliding_window
            ring_k, ring_v = ring
            if seq == 1:
                slot = jnp.mod(pos0, window)
                ring_k = jax.lax.dynamic_update_slice_in_dim(ring_k, k, slot, axis=2)
                ring_v = jax.lax.dynamic_update_slice_in_dim(ring_v, v, slot, axis=2)
                att = masked_attention(q, ring_k, ring_v, q_pos, ring_positions(pos0, window),
                                       scale=scale, window=window, block=window)
            else:
                k_pos = jnp.concatenate([ring_positions(pos0 - 1, window),
                                         pos0 + jnp.arange(seq, dtype=jnp.int32)])
                att = masked_attention(
                    q, jnp.concatenate([ring_k, k], axis=2), jnp.concatenate([ring_v, v], axis=2),
                    q_pos, k_pos, scale=scale, window=window, block=window + seq)
                keep = min(seq, window)
                slots = jnp.mod(pos0 + seq - keep + jnp.arange(keep, dtype=jnp.int32), window)
                ring_k = ring_k.at[:, :, slots].set(k[:, :, seq - keep:])
                ring_v = ring_v.at[:, :, slots].set(v[:, :, seq - keep:])
            ring = (ring_k, ring_v)
            out = _combine(cfg, p, layer, att, batch, seq)
    else:
        with jax.named_scope("ht.lm.shared_kv_attn"):
            cache_k, cache_v = shared
            if kind == "full":
                cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, k, pos0, axis=2)
                cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, v, pos0, axis=2)
                shared = (cache_k, cache_v)
            if seq == 1:
                att = decode_attention(q, cache_k, cache_v, pos0 + 1, scale=scale, block=block)
            elif kind == "full":
                att = masked_attention(
                    q, cache_k, cache_v, q_pos, jnp.arange(cache_k.shape[2], dtype=jnp.int32),
                    scale=scale, kv_len=pos0 + seq, block=min(block, 1024))
            else:
                raise ValueError("a cross-attention layer runs one position at a time")
            out = _combine(cfg, p, layer, att, batch, seq)
    with jax.named_scope("ht.lm.attn_proj"):
        return _dot(out, p["w_o"]), shared, ring


def _run_layers(cfg, params, first, last, x, pos0, shared, state, memory, *, block, scan_chunk):
    """Layers ``first .. last - 1`` on ``x`` of ``(batch, seq, d)`` (float32)
    whose first position is ``pos0``.  ``state`` holds the window rings and the
    Mamba states as tuples in layer order; ``memory`` is the newest Mamba
    layer's scan output.  Returns ``(x, shared, state, memory)``."""
    rings, convs, ssms = list(state["ring"]), list(state["conv"]), list(state["ssm"])
    kinds = cfg.layer_types
    for layer in range(first, last):
        kind, p = kinds[layer], params["layers"][layer]
        if kind == "mamba":
            with jax.named_scope("ht.lm.ssm"):
                n = kinds[:layer].count("mamba")
                h = _layer_norm(x, p["norm1"], cfg.layer_norm_eps)
                out, memory, convs[n], ssms[n] = _mamba(
                    cfg, p["mixer"], h, convs[n], ssms[n], scan_chunk)
        elif kind == "gmu":
            with jax.named_scope("ht.lm.ssm"):
                h = _layer_norm(x, p["norm1"], cfg.layer_norm_eps)
                gate = jax.nn.silu(_dot(h, p["mixer"]["w_in"]))
                out = _dot(gate * memory, p["mixer"]["w_out"])
        else:
            n = kinds[:layer].count("window")
            ring = rings[n] if kind == "window" else None
            with jax.named_scope("ht.lm.attn_proj"):
                h = _layer_norm(x, p["norm1"], cfg.layer_norm_eps)
            out, shared, ring = _attention(cfg, kind, layer, p["mixer"], h, pos0, shared, ring, block)
            if kind == "window":
                rings[n] = ring
        x = _mlp(cfg, p, x + out)
    state = {"ring": tuple(rings), "conv": tuple(convs), "ssm": tuple(ssms)}
    return x, shared, state, memory


def _head(cfg, params, x):
    """Greedy token and float32 logits of ``x`` of ``(batch, d)``."""
    with jax.named_scope("ht.lm.head"):
        return _greedy(_layer_norm(x, params["final_norm"], cfg.layer_norm_eps), params["embed"])


@functools.partial(jax.jit, static_argnames=("cfg", "block", "scan_chunk"), donate_argnums=(2, 3))
@telemetry.module_name("ht_lm_prefill_chunk")
def _prefill_chunk(cfg, params, shared, state, tokens, pos0, *, block, scan_chunk):
    """One chunk of a prompt through the self-decoder.  Returns the caches and,
    of the chunk's last position, the hidden state and the scan output."""
    x, shared, state, memory = _run_layers(
        cfg, params, 0, cfg.n_self, _embed(params, tokens), pos0, shared, state, None,
        block=block, scan_chunk=scan_chunk)
    return shared, state, x[:, -1:], None if memory is None else memory[:, -1:]


@functools.partial(jax.jit, static_argnames=("cfg", "block"))
@telemetry.module_name("ht_lm_prefill_finish")
def _prefill_finish(cfg, params, shared, x, memory, pos, *, block):
    """The cross-decoder and the head for a prompt's last position."""
    empty = {"ring": (), "conv": (), "ssm": ()}
    x, _, _, _ = _run_layers(cfg, params, cfg.n_self, cfg.num_hidden_layers, x, pos, shared,
                             empty, memory, block=block, scan_chunk=1)
    return _head(cfg, params, x[:, 0])


@functools.partial(jax.jit, static_argnames=("cfg", "steps", "block"), donate_argnums=(2, 3))
@telemetry.module_name("ht_lm_decode")
def _decode(cfg, params, shared, state, token, pos, *, steps, block):
    """``steps`` greedy steps: the token fed at position ``pos + j`` gives the
    logits of that position and, by their argmax, the next token."""

    def step(carry, _):
        shared, state, token, pos = carry
        x = _embed(params, token)[:, None]
        x, shared, state, _ = _run_layers(
            cfg, params, 0, cfg.num_hidden_layers, x, pos, shared, state, None,
            block=block, scan_chunk=1)
        chosen, logits = _head(cfg, params, x[:, 0])
        return (shared, state, chosen, pos + 1), (chosen, logits)

    (shared, state, token, _), (chosen, logits) = jax.lax.scan(
        step, (shared, state, token, pos), None, length=steps)
    return shared, state, token, chosen.T, jnp.moveaxis(logits, 0, 1)


# ----------------------------------------------------------------------- model

class SambaY:
    """The model: a configuration and its parameters.

    ``SambaY(cfg)`` draws seeded parameters (:func:`init_params`);
    ``SambaY(cfg, params)`` takes a tree of the same layout.  Serving goes
    through :meth:`session`, a :class:`~heat_tpu.models.session.DecodeSession`
    over what the ``serve_*`` methods below supply: the hybrid cache, the
    jitted programs, the decode span's notes."""

    def __init__(self, cfg: SambaYConfig, params: Optional[dict] = None, *, seed: int = 0,
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
        """The shared cache holds ``capacity`` positions: ``max_context``
        rounded up to whole blocks of ``ATTN_BLOCK`` keys, the unit the decode
        kernel streams (a context shorter than a block: to a power of two, one
        block).  The state a snapshot copies: the window rings, the Mamba
        layers' convolution tails and scan states."""
        cfg = self.cfg
        block = min(ATTN_BLOCK, 1 << max(4, (max_context - 1).bit_length()))
        capacity = -(-max_context // block) * block
        dtype = jnp.dtype(cfg.dtype)
        groups, lanes = cfg.kv_groups, 2 * cfg.head_dim

        def zeros(shape, dt):
            return jnp.zeros(shape, dt, device=self._placement)

        shared = tuple(zeros((batch, groups, capacity, lanes), dtype) for _ in range(2))
        n_window, n_mamba = cfg.layer_types.count("window"), cfg.layer_types.count("mamba")
        ring = (batch, groups, cfg.sliding_window, lanes)
        state = {
            "ring": tuple((zeros(ring, dtype), zeros(ring, dtype)) for _ in range(n_window)),
            "conv": tuple(zeros((batch, cfg.d_conv - 1, cfg.d_inner), dtype)
                          for _ in range(n_mamba)),
            "ssm": tuple(zeros((batch, cfg.d_state, cfg.d_inner), _F32)
                         for _ in range(n_mamba)),
        }
        return capacity, shared, state

    def serve_bytes(self, shared, state) -> dict:
        return {"shared": tree_bytes(shared), "window": tree_bytes(state["ring"]),
                "state": tree_bytes(state["conv"]) + tree_bytes(state["ssm"])}

    def serve_prefill(self, shared, state, ids, position: int):
        """The prompt through the self-decoder in chunks of ``PREFILL_CHUNK``
        positions, then the cross-decoder and the head for its last one."""
        cfg, params = self.cfg, self.params
        n = int(ids.shape[1])
        for start in range(0, n, PREFILL_CHUNK):
            chunk = ids[:, start:start + PREFILL_CHUNK]
            shared, state, x, memory = _prefill_chunk(
                cfg, params, shared, state, chunk, np.int32(position + start),
                block=ATTN_BLOCK, scan_chunk=SCAN_CHUNK)
        token, logits = _prefill_finish(
            cfg, params, shared, x, memory, np.int32(position + n - 1), block=ATTN_BLOCK)
        return shared, state, token, logits

    def serve_notes(self, session: DecodeSession, steps: int):
        cfg = self.cfg
        notes = dict(batch=session.batch, context=session.position, steps=steps,
                     readers=cfg.n_shared_readers, token_bytes=cfg.cache_token_bytes)
        counts = {}
        if pallas_mode() != "off":  # the rule is the kernel's: the fallback feeds neither counter
            reads = session.batch * cfg.n_shared_readers  # of the shared cache, a step
            lengths = range(session.position + 1, session.position + steps + 1)
            counts["cache_keys_visible"] = reads * sum(lengths)
            counts["cache_keys_fetched"] = notes["fetched"] = reads * sum(
                keys_fetched(n, session.capacity, ATTN_BLOCK) for n in lengths)
        return notes, counts

    def serve_decode(self, shared, state, token, position: int, steps: int):
        return _decode(self.cfg, self.params, shared, state, token, np.int32(position),
                       steps=steps, block=ATTN_BLOCK)
