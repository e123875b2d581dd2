"""Flash attention — blockwise online-softmax attention in VMEM.

No reference counterpart: Heat has no attention code at all (SURVEY.md §5,
"long-context / sequence parallelism: absent").  This kernel is the per-chip
building block of this framework's long-context story: ring attention
(heat_tpu/parallel/sequence.py) calls it per K/V block while blocks rotate
around the mesh on ICI.

Layout: ``(batch·heads, seq, head_dim)``.  Grid is (BH, Sq/bq, Sk/bk) with the
K dimension innermost; running max ``m``, normalizer ``l`` and the f32
accumulator live in VMEM scratch across K steps.  Backward is a recompute
(jnp) pass under ``jax.custom_vjp`` — XLA refuses nothing there, and the
memory win of flash attention is in the forward residuals anyway.

Dispatch: Pallas on TPU, jnp reference otherwise,
``HEAT_TPU_PALLAS=interpret`` to exercise the kernel on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._pallas_common import mode as _mode

__all__ = ["flash_attention"]

_NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale, causal, sq, sk, block_q, block_k
):
    qb = pl.program_id(1)
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # causal: a k-block strictly above the diagonal band is fully masked —
    # skip its matmuls and softmax work entirely (half the grid at long seq)
    live = kb * block_k <= qb * block_q + block_q - 1 if causal else True

    @pl.when(live)
    def _():
        q = q_ref[0].astype(jnp.float32)  # (bq, d)
        k = k_ref[0].astype(jnp.float32)  # (bk, d)
        s = (
            jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            * scale
        )  # (bq, bk)

        q_idx = qb * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_idx = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_idx < sk
        if causal:
            mask &= q_idx >= k_idx
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:]  # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (bq, bk)
        correction = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * correction + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = acc_ref[:] * correction + jnp.dot(
            p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32
        )
        m_ref[:] = m_new

    @pl.when(kb == pl.num_programs(2) - 1)
    def _():
        l = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])  # fully-masked rows → 0 output
        o_ref[0] = (acc_ref[:] / l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "scale", "block_q", "block_k", "interpret")
)
def _flash_pallas(q, k, v, causal, scale, block_q=512, block_k=2048, interpret=False):
    # block defaults from a sweep on a v5e at s=4096, d=128 (an earlier
    # runtime; not re-measured on this one): (512, 2048) beat every smaller
    # pair; the (bq, bk) score tile must be large enough to amortize the per-block
    # softmax bookkeeping on the VPU, and beats finer blocks even causal
    # where finer granularity would skip more masked work
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    bq = min(block_q, max(8, sq))
    bk = min(block_k, max(128, sk))
    pad_q = (-sq) % bq
    pad_k = (-sk) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    sqp, skp = sq + pad_q, sk + pad_k
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            scale=scale,
            causal=causal,
            sq=sq,
            sk=sk,
            block_q=bq,
            block_k=bk,
        ),
        grid=(bh, sqp // bq, skp // bk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sqp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        cost_estimate=pl.CostEstimate(
            flops=4 * bh * sqp * skp * d,
            bytes_accessed=bh * (sqp * d * 2 + skp * d * 2) * q.dtype.itemsize,
            transcendentals=bh * sqp * skp,
        ),
        interpret=interpret,
    )(q, k, v)
    return out[:, :sq, :]


def _attention_ref(q, k, v, causal, scale):
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(q.dtype), v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash(q, k, v, causal, scale):
    mode = _mode()
    if mode == "off":
        return _attention_ref(q, k, v, causal, scale)
    return _flash_pallas(q, k, v, causal, scale, interpret=(mode == "interpret"))


def _flash_fwd(q, k, v, causal, scale):
    return _flash(q, k, v, causal, scale), (q, k, v)


def _flash_bwd(causal, scale, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda q, k, v: _attention_ref(q, k, v, causal, scale), q, k, v)
    return vjp(g)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: float | None = None,
) -> jax.Array:
    """Scaled-dot-product attention, ``(..., seq, head_dim)`` layout.

    Leading dims (batch, heads) are flattened into the Pallas grid's first
    axis; forward runs blockwise in VMEM on TPU, backward recomputes.
    """
    if q.shape[:-2] != k.shape[:-2] or k.shape != v.shape:
        raise ValueError(f"incompatible attention shapes {q.shape} {k.shape} {v.shape}")
    lead = q.shape[:-2]
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / (d**0.5)
    q3 = q.reshape((-1,) + q.shape[-2:])
    k3 = k.reshape((-1,) + k.shape[-2:])
    v3 = v.reshape((-1,) + v.shape[-2:])
    out = _flash(q3, k3, v3, causal, float(scale))
    return out.reshape(lead + out.shape[-2:])
