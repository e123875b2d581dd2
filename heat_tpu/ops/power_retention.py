"""Power retention of degree 2: linear attention whose weights are
``(q . k)^2`` under a data-dependent decay, and its constant-size state.

For one key/value head serving ``R`` query heads of width ``d``::

    a_tj  = exp(sum_{s=j+1..t} log g_s) * (q_t . k_j / sqrt(d))^2        j <= t
    y_t   = sum_j a_tj v_j / (sum_j a_tj + eps)

``phi(x)``, the symmetric square of ``x / d^(1/4)``, has ``d (d + 1) / 2``
features with ``phi(x) . phi(y) = (x . y)^2 / d``, so the same ``y`` comes
from a state that is updated once a position::

    S_t = g_t S_{t-1} + phi(k_t) v_t^T      z_t = g_t z_{t-1} + phi(k_t)
    y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)

**How the features lie.**  Block ``j`` of ``d // 2 + 1`` blocks holds, in
lane ``a``, the pair ``(a, (a + j) mod d)``: ``c_j x_a x_{a+j} / sqrt(d)``
with ``c_0 = 1`` (the squares) and ``c_j = sqrt(2)`` (every unordered pair
once: for ``j = d / 2`` the lanes ``a < d / 2`` hold them all and the other
half of that block stays zero).  So a block is one lane rotation and two
products of whole vectors, and the state holds ``(d // 2 + 1) * d`` feature
rows a head (8,320 at ``d = 128``: the 8,256 features and 64 that are always
zero).  The state is kept values-major, ``S`` of ``(batch, heads, d, rows)``
with the features along the lanes, and ``z`` of ``(batch, heads, d // 2 + 1,
d)``; both float32.

:func:`retention_step` is one decode step.  On a TPU it is the Pallas kernel
``ht_power_retention_step``: **one pass** over the state, which reads a tile
of ``S`` and of ``z``, decays it, adds this step's ``phi(k) v^T``, answers the
group's query heads from the updated tile and writes it back where it lay
(the state is aliased in place); all of it float32 on the vector unit.  Where
``mode()`` is ``off`` the same arithmetic runs as ``jax.numpy`` (XLA passes
over the state three times: the update reads and writes it, the query reads
it again).  The kernel replaces that lowering on every input it accepts
(widths that are whole lane tiles on a TPU, any under the interpreter), so it
is chosen by ``mode()`` alone and is no autotune arm: the classic body moves
three times the bytes at every size.

:func:`retention_chunked` walks a sequence: inside a chunk the attention form
with the cumulative gates, across chunks the state; ``jax.numpy``, one
key/value head at a time so that the feature expansion of the queries is
alive for one chunk and one head only.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_common import LANE
from ._pallas_common import mode as _mode

__all__ = ["EPS", "feature_blocks", "feature_count", "feature_table", "features",
           "retention_chunked", "retention_step", "state_rows"]

EPS = 1e-6
_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
# feature blocks a grid step of the kernel updates: a tile of d x 13 d float32
# (852 KB at d = 128), five steps a head
_STEP_BLOCKS = 13


def feature_count(d: int) -> int:
    """Features of the symmetric square of a ``d``-vector."""
    return d * (d + 1) // 2


def feature_blocks(d: int) -> int:
    """Lane blocks of ``d`` features that hold them."""
    return d // 2 + 1


def state_rows(d: int) -> int:
    """Feature rows the state holds a head: whole blocks."""
    return feature_blocks(d) * d


@functools.lru_cache(maxsize=None)
def _table(d: int) -> np.ndarray:
    if d % 2:
        raise ValueError(f"power retention takes an even head width, got {d}")
    table = np.full((feature_blocks(d), d), math.sqrt(2.0 / d), np.float32)
    table[0] = 1.0 / math.sqrt(d)
    table[d // 2, d // 2:] = 0.0
    return table


def feature_table(d: int) -> jax.Array:
    """``c_j / sqrt(d)`` for every block and lane, ``(d // 2 + 1, d)``."""
    return jnp.asarray(_table(d))


def features(x: jax.Array) -> jax.Array:
    """``phi(x)`` over the last axis: ``(..., d) -> (..., d // 2 + 1, d)``."""
    d = x.shape[-1]
    x = x.astype(_F32)
    turned = jnp.stack([jnp.roll(x, -j, axis=-1) for j in range(feature_blocks(d))], axis=-2)
    return feature_table(d) * x[..., None, :] * turned


# ------------------------------------------------------------------ one step

def _step_jnp(S, z, q, k, v, gate):
    """The step's two sums, whole arrays at a time.  Returns the numerators
    ``(batch, heads, R, d)``, the denominators ``(batch, heads, R)`` and the
    new state."""
    rows = S.shape[-1]
    phik = features(k)
    phiq = features(q).reshape(q.shape[:3] + (rows,))
    g = gate[..., None, None]
    S = g * S + v[..., :, None] * phik.reshape(k.shape[:2] + (1, rows))
    z = g * z + phik
    num = jnp.einsum("bhrf,bhvf->bhrv", phiq, S, precision=_HI)
    den = jnp.einsum("bhrf,bhf->bhr", phiq, z.reshape(z.shape[:2] + (rows,)), precision=_HI)
    return num, den, S, z


def _step_kernel(tab_ref, x_ref, s_ref, z_ref, s_out, z_out, num_out, den_out,
                 phi_ref, acc_ref, den_ref, vcol_ref, *, d, heads, blocks):
    """Grid ``(batch, key/value heads, steps)``; a step updates ``blocks``
    feature blocks of one head's state.  ``x_ref`` holds the head's vectors as
    rows: the ``heads`` queries, then the key, the value and the gate."""
    c = pl.program_id(2)
    x = x_ref[...]
    tile = (8, d)

    @pl.when(c == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        den_ref[...] = jnp.zeros_like(den_ref)
        # the value along the sublanes, the same in every lane
        vcol_ref[...] = jnp.broadcast_to(x[heads + 1:heads + 2, :], (d, d)).T

    gate = jnp.broadcast_to(x[heads + 2:heads + 3, :], tile)
    # this step's features of the queries and the key, each row over a whole
    # tile of sublanes, so that the pass below multiplies whole registers
    for jj in range(blocks):
        j = c * np.int32(blocks) + np.int32(jj)
        shift = jnp.where(j == 0, np.int32(0), np.int32(d) - j)
        phi = tab_ref[pl.ds(j, 1), :] * x * pltpu.roll(x, shift, 1)
        for i in range(heads + 1):
            phi_ref[jj, i] = jnp.broadcast_to(phi[i:i + 1, :], tile)
        zj = gate[0:1, :] * z_ref[pl.ds(j, 1), :] + phi[heads:heads + 1, :]
        z_out[pl.ds(j, 1), :] = zj
        den_ref[...] += phi * zj

    # the pass: eight value rows at a time across the step's blocks, the
    # partial sums of the query heads in registers meanwhile
    for r in range(d // 8):
        at = pl.ds(r * 8, 8)
        vcol = vcol_ref[at, :]
        parts = [jnp.zeros(tile, _F32) for _ in range(heads)]
        for jj in range(blocks):
            lanes = pl.ds(jj * d, d)
            new = gate * s_ref[at, lanes] + vcol * phi_ref[jj, heads]
            s_out[at, lanes] = new
            for i in range(heads):
                parts[i] = parts[i] + new * phi_ref[jj, i]
        for i in range(heads):
            acc_ref[i, at, :] += parts[i]

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        lane = jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
        cols = jnp.zeros((d, d), _F32)
        for i in range(heads):
            total = jnp.sum(acc_ref[i], axis=1, keepdims=True)
            cols = jnp.where(lane == np.int32(i), total, cols)
        num_out[...] = cols.T[:num_out.shape[0], :]
        den_out[...] = den_ref[...]


def _step_blocks(d: int) -> int:
    nb = feature_blocks(d)
    return max(b for b in range(1, _STEP_BLOCKS + 1) if nb % b == 0)


def _step_pallas(S, z, q, k, v, gate, *, interpret):
    batch, kv_heads, heads, d = q.shape
    nb, rows = feature_blocks(d), state_rows(d)
    blocks = _step_blocks(d)
    packed = -(-(heads + 3) // 8) * 8
    x = jnp.concatenate(
        [q, k[:, :, None], v[:, :, None], jnp.broadcast_to(gate[..., None, None],
                                                            (batch, kv_heads, 1, d)),
         jnp.zeros((batch, kv_heads, packed - heads - 3, d), _F32)], axis=2)

    def head(b, h, c):
        return b, h, 0, 0

    S, z, num, den = pl.pallas_call(
        functools.partial(_step_kernel, d=d, heads=heads, blocks=blocks),
        grid=(batch, kv_heads, nb // blocks),
        in_specs=[
            pl.BlockSpec((nb, d), lambda b, h, c: (0, 0)),
            pl.BlockSpec((None, None, packed, d), head),
            pl.BlockSpec((None, None, d, blocks * d), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((None, None, nb, d), head),
        ],
        out_specs=[
            pl.BlockSpec((None, None, d, blocks * d), lambda b, h, c: (b, h, 0, c)),
            pl.BlockSpec((None, None, nb, d), head),
            pl.BlockSpec((None, None, packed, d), head),
            pl.BlockSpec((None, None, packed, d), head),
        ],
        scratch_shapes=[
            pltpu.VMEM((blocks, heads + 1, 8, d), _F32),
            pltpu.VMEM((heads, d, d), _F32),
            pltpu.VMEM((packed, d), _F32),
            pltpu.VMEM((d, d), _F32),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(S.shape, _F32),
            jax.ShapeDtypeStruct(z.shape, _F32),
            jax.ShapeDtypeStruct((batch, kv_heads, packed, d), _F32),
            jax.ShapeDtypeStruct((batch, kv_heads, packed, d), _F32),
        ],
        input_output_aliases={2: 0, 3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=batch * kv_heads * d * rows * (3 + 2 * heads),
            bytes_accessed=2 * 4 * batch * kv_heads * (d + 1) * rows,
            transcendentals=0,
        ),
        interpret=interpret,
        name="ht_power_retention_step",
    )(feature_table(d), x, S, z)
    return num[:, :, :heads], jnp.sum(den[:, :, :heads], axis=-1), S, z


def _kernel_takes(d: int, how: str) -> bool:
    return how == "interpret" or (how == "tpu" and d % LANE == 0)


def retention_step(S, z, q, k, v, log_g, *, eps: float = EPS):
    """One position for every sequence and key/value head of a layer.

    ``S``: ``(batch, heads, d, rows)`` and ``z``: ``(batch, heads, d // 2 + 1,
    d)``, the state (float32); ``q``: ``(batch, heads, R, d)``, the query heads
    of each key/value head; ``k``, ``v``: ``(batch, heads, d)``; ``log_g``:
    ``(batch, heads)``, the logarithm of the gate.  Returns ``(y, S, z)`` with
    ``y`` of ``(batch, heads, R, d)``."""
    q, k, v = (a.astype(_F32) for a in (q, k, v))
    gate = jnp.exp(log_g.astype(_F32))
    how = _mode()
    if _kernel_takes(q.shape[-1], how):
        num, den, S, z = _step_pallas(S, z, q, k, v, gate, interpret=(how == "interpret"))
    else:
        num, den, S, z = _step_jnp(S, z, q, k, v, gate)
    return num / (den[..., None] + np.float32(eps)), S, z


# ------------------------------------------------------------ a whole sequence

def retention_chunked(q, k, v, log_g, S0, z0, chunk: int, *, eps: float = EPS):
    """A sequence of ``seq`` positions from the state ``(S0, z0)``.

    ``q``: ``(batch, seq, heads, R, d)``; ``k``, ``v``: ``(batch, seq, heads,
    d)``; ``log_g``: ``(batch, seq, heads)``.  Returns ``(y, S, z)``: ``y`` of
    ``q``'s shape and the state after the last position.  A sequence that is
    no multiple of ``chunk`` is padded with zero keys and gates of one, which
    leave the state as it is."""
    batch, seq, kv_heads, heads, d = q.shape
    nb, rows = feature_blocks(d), state_rows(d)
    chunk = max(1, min(int(chunk), seq))
    pad = (-seq) % chunk
    q, k, v, log_g = (a.astype(_F32) for a in (q, k, v, log_g))
    if pad:
        q, k, v, log_g = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                          for a in (q, k, v, log_g))
    nchunks = (seq + pad) // chunk
    earlier = jnp.tril(jnp.ones((chunk, chunk), bool))     # [t, j]: j <= t

    def chunks(a):  # (batch, seq, ...) -> (nchunks, batch, chunk, ...)
        return jnp.moveaxis(a.reshape((batch, nchunks, chunk) + a.shape[2:]), 1, 0)

    def pick(a, h, axis):
        return jax.lax.dynamic_index_in_dim(a, h, axis, keepdims=False)

    def one_chunk(state, xs):
        qc, kc, vc, gc = xs
        run = jnp.cumsum(gc, axis=1)                       # log of the decay since the chunk began

        def one_head(h, carry):
            S, z, y = carry
            qh, kh, vh, lh = pick(qc, h, 2), pick(kc, h, 2), pick(vc, h, 2), pick(run, h, 2)
            Sh, zh = pick(S, h, 1), pick(z, h, 1).reshape(batch, rows)
            # inside the chunk: the attention form
            score = jnp.einsum("btrd,bjd->btrj", qh, kh, precision=_HI)
            decay = jnp.exp(jnp.where(earlier, lh[:, :, None] - lh[:, None, :], -jnp.inf))
            a = score * score * np.float32(1.0 / d) * decay[:, :, None, :]
            num = jnp.einsum("btrj,bjd->btrd", a, vh, precision=_HI)
            den = jnp.sum(a, axis=-1)
            # before the chunk: the state
            phiq = features(qh).reshape(batch, chunk, heads, rows)
            since = jnp.exp(lh)[:, :, None]
            num = num + since[..., None] * jnp.einsum("btrf,bvf->btrv", phiq, Sh, precision=_HI)
            den = den + since * jnp.einsum("btrf,bf->btr", phiq, zh, precision=_HI)
            y = jax.lax.dynamic_update_index_in_dim(
                y, num / (den[..., None] + np.float32(eps)), h, 2)
            # after the chunk: every key decayed to the chunk's end
            left = jnp.exp(lh[:, -1:] - lh)
            phik = features(kh).reshape(batch, chunk, rows) * left[..., None]
            whole = jnp.exp(lh[:, -1])[:, None, None]
            Sh = whole * Sh + jnp.einsum("btv,btf->bvf", vh, phik, precision=_HI)
            zh = whole[..., 0] * zh + jnp.sum(phik, axis=1)
            S = jax.lax.dynamic_update_index_in_dim(S, Sh, h, 1)
            z = jax.lax.dynamic_update_index_in_dim(z, zh.reshape(batch, nb, d), h, 1)
            return S, z, y

        S, z, y = jax.lax.fori_loop(0, kv_heads, one_head, state + (jnp.zeros_like(qc),))
        return (S, z), y

    (S, z), ys = jax.lax.scan(one_chunk, (S0, z0), tuple(chunks(a) for a in (q, k, v, log_g)))
    y = jnp.moveaxis(ys, 0, 1).reshape((batch, nchunks * chunk) + q.shape[2:])
    return y[:, :seq], S, z
