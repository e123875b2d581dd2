"""Selective state-space scan (Mamba's recurrence), float32 throughout.

For every channel ``c`` and state index ``n``::

    S_t[n, c] = exp(delta_t[c] * A[n, c]) * S_{t-1}[n, c] + delta_t[c] * u_t[c] * B_t[n]
    y_t[c]    = sum_n S_t[n, c] * C_t[n] + D[c] * u_t[c]

The state is laid out ``(batch, d_state, d_inner)``: the channels are the
minor axis, so a state of 16 x 5120 fills whole (8, 128) tiles and the sum
over ``n`` runs over sublanes.  ``A`` is stored the same way, ``(d_state,
d_inner)``.

:func:`selective_scan` walks a sequence in chunks of ``chunk`` steps: the
outer loop is a ``lax.scan`` over chunks, the steps of one chunk are unrolled
so that XLA fuses them and the state crosses HBM once a chunk, not once a
step.  A sequence that is no multiple of the chunk is padded with
``delta = 0``, which leaves the state as it is.  :func:`selective_step` is the
same recurrence for one position, which is what a decode step runs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["selective_scan", "selective_step"]


def selective_step(u, delta, a, b, c, d, state):
    """One position.  ``u``, ``delta``: ``(batch, d_inner)``; ``a``:
    ``(d_state, d_inner)``; ``b``, ``c``: ``(batch, d_state)``; ``d``:
    ``(d_inner,)``; ``state``: ``(batch, d_state, d_inner)``.  Returns
    ``(y, new_state)``, all float32."""
    decay = jnp.exp(delta[:, None, :] * a[None])
    state = decay * state + (delta * u)[:, None, :] * b[:, :, None]
    y = jnp.sum(state * c[:, :, None], axis=1) + d * u
    return y, state


def selective_scan(u, delta, a, b, c, d, state, *, chunk: int = 16):
    """A whole sequence.  ``u``, ``delta``: ``(batch, seq, d_inner)``; ``b``,
    ``c``: ``(batch, seq, d_state)``; the rest as :func:`selective_step`.
    Returns ``(y, final_state)`` with ``y`` of ``(batch, seq, d_inner)``."""
    f32 = jnp.float32
    u, delta, b, c = (v.astype(f32) for v in (u, delta, b, c))
    a, d, state = a.astype(f32), d.astype(f32), state.astype(f32)
    seq = u.shape[1]
    chunk = max(1, min(int(chunk), seq))
    pad = (-seq) % chunk
    if pad:
        widths = ((0, 0), (0, pad), (0, 0))
        u, delta, b, c = (jnp.pad(v, widths) for v in (u, delta, b, c))
    nchunks = (seq + pad) // chunk

    def chunked(v):  # (batch, seq, w) -> (nchunks, chunk, batch, w)
        return jnp.moveaxis(v, 1, 0).reshape(nchunks, chunk, v.shape[0], v.shape[2])

    def one_chunk(state, xs):
        us, ds, bs, cs = xs
        ys = []
        for t in range(chunk):
            y, state = selective_step(us[t], ds[t], a, bs[t], cs[t], d, state)
            ys.append(y)
        return state, jnp.stack(ys)

    state, ys = jax.lax.scan(one_chunk, state, tuple(chunked(v) for v in (u, delta, b, c)))
    y = jnp.moveaxis(ys.reshape(nchunks * chunk, u.shape[0], u.shape[2]), 0, 1)
    return y[:, :seq], state
