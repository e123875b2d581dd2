"""The session that serves a language model: a batch of sequences advancing
in lockstep through a cache on the device.

One :class:`DecodeSession` serves every model of :mod:`heat_tpu.models`:
``prefill(tokens)`` walks a prompt through the model in chunks,
``decode(steps)`` generates greedily on the device and reads the chosen
tokens back once, ``save()`` / ``rewind(snapshot)`` return to an earlier
position.  What differs between models is what the model supplies, the five
``serve_*`` methods and ``prefill_chunk``, the positions one of its prefill
programs walks:

- ``serve_cache(batch, max_context) -> (capacity, shared, state)``: how many
  positions the session can hold, the part of the cache that grows with the
  context and that a snapshot never copies (later positions are simply
  overwritten), and the constant-size state that a snapshot copies;
- ``serve_prefill(shared, state, ids, position) -> (shared, state, token,
  logits)``: its jitted prefill programs over a prompt, chunk by chunk;
- ``serve_decode(shared, state, token, position, steps) -> (shared, state,
  token, chosen, logits)``: its jitted decode program; a sixth element, where
  a model returns one, is a dictionary of device numbers for the ``lm``
  counters that ride back with the tokens in the one readback;
- ``serve_notes(session, steps) -> (notes, counts)``: the attributes its
  ``lm.decode`` span carries and what the call adds to the ``lm`` counters;
- ``serve_bytes(shared, state) -> dict``: the bytes held, by kind.

A snapshot and the live state are two copies and never three: ``rewind``
gives the live state up to the program that copies the saved one, and every
large leaf of the copy is written by one DMA into the buffer of the leaf it
replaces (``ops/_pallas_common.py:device_copy``).  Left to XLA, a copy into a
donated buffer compiles for the v5e to two copies through a temporary the size
of the state (PERF.md section 6, PR 32).
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import telemetry, types
from ..core.dndarray import DNDarray
from ..ops._pallas_common import device_copy

__all__ = ["DecodeSession", "Snapshot"]

# decode_steps and prefill_tokens count what sessions did; cache_keys_visible
# and cache_keys_fetched are the key slots of the shared cache that the decode
# steps' reads could see and the slots the decode kernel's rule fetches for
# them (their ratio is the fetch share; both stand still where the kernel does
# not run and the jax.numpy fallback reads the cache); state_bytes_stepped is
# the constant-size state read plus written by decode steps whose mixer walks
# all of it every step (power retention), state_bytes_copied what save and
# rewind copied; index_keys_scanned and latent_rows_read are the index keys
# that a sparse selection scored and the latent rows its attention then read
# (from shapes), selections_by_cut the layer-steps whose selection was a cut and
# a compaction and not every visible slot, expert_pairs and experts_hit the
# token-expert pairs computed on the experts held here and the held experts
# that received a token, summed over a call's steps and layers (counted on the
# device); cache_bytes is what the newest session allocated, by kind
_LM = telemetry.register_group(
    "lm",
    {"decode_steps": 0, "prefill_tokens": 0, "cache_keys_visible": 0, "cache_keys_fetched": 0,
     "state_bytes_stepped": 0, "state_bytes_copied": 0,
     "index_keys_scanned": 0, "latent_rows_read": 0, "selections_by_cut": 0,
     "expert_pairs": 0, "experts_hit": 0,
     "cache_bytes": {"shared": 0, "window": 0, "state": 0}},
)


def tree_bytes(tree) -> int:
    return sum(int(leaf.nbytes) for leaf in jax.tree.leaves(tree))


# a leaf of this size or more is copied by one DMA (``device_copy``), which
# can write into a buffer the caller gives up; smaller ones are XLA's copies
DMA_BYTES = 1 << 26


def _copy_leaf(saved, live=None):
    if saved.size * saved.dtype.itemsize >= DMA_BYTES:
        return device_copy(saved, live)
    return jnp.copy(saved)


@jax.jit
@telemetry.module_name("ht_lm_state_copy")
def _copied(tree):
    with jax.named_scope("ht.lm.state_copy"):
        return jax.tree.map(_copy_leaf, tree)


@functools.partial(jax.jit, donate_argnums=0)
@telemetry.module_name("ht_lm_state_restore")
def _restored(live, saved):
    """A copy of ``saved``; its large leaves land in the buffers of ``live``,
    which the caller gives up."""
    with jax.named_scope("ht.lm.state_copy"):
        return jax.tree.map(_copy_leaf, saved, live)


class Snapshot(NamedTuple):
    """A saved position of a :class:`DecodeSession`: the position, the token
    waiting to be fed there, and a copy of the constant-size state."""

    position: int
    token: jax.Array
    state: Any


class DecodeSession:
    """The cache of ``batch`` sequences that advance in lockstep, and the
    calls that move it: :meth:`prefill`, :meth:`decode`, :meth:`save`,
    :meth:`rewind`.  ``capacity`` is how many positions it can hold (the
    model says: whole blocks of a key/value cache, or the positions its
    encoding reaches)."""

    def __init__(self, model, batch: int, max_context: int):
        self.model = model
        self.cfg = model.cfg
        self.batch = int(batch)
        self.capacity, self._shared, self._state = model.serve_cache(self.batch, int(max_context))
        self.position = 0
        self._token = None  # the token waiting to be fed at `position`
        self.tokens = None  # the newest decode call's tokens, on the host
        _LM["cache_bytes"].update(dict.fromkeys(_LM["cache_bytes"], 0), **self.cache_bytes())

    def cache_bytes(self) -> dict:
        """Bytes this session holds on the device, by kind of state."""
        return self.model.serve_bytes(self._shared, self._state)

    def _wrap(self, array) -> DNDarray:
        from ..core.devices import get_device

        return DNDarray(array, tuple(array.shape), types.canonical_heat_type(array.dtype),
                        None, get_device(), self.model.comm)

    def prefill(self, tokens) -> DNDarray:
        """Append a prompt of ``(batch, n)`` token ids at the current position.
        Returns the logits of its last position, ``(batch, vocab)``; their
        argmax is the token the next :meth:`decode` feeds first."""
        ids = tokens.larray if isinstance(tokens, DNDarray) else jnp.asarray(tokens)
        ids = ids.astype(jnp.int32)
        if ids.ndim != 2 or ids.shape[0] != self.batch or ids.shape[1] < 1:
            raise ValueError(f"prefill takes (batch={self.batch}, n >= 1) token ids, got {ids.shape}")
        n = int(ids.shape[1])
        if self.position + n > self.capacity:
            raise ValueError(f"{self.position} + {n} positions pass the session's {self.capacity}")
        with telemetry.span("lm.prefill", tokens=self.batch * n, chunk=self.model.prefill_chunk):
            self._shared, self._state, self._token, logits = self.model.serve_prefill(
                self._shared, self._state, ids, self.position)
            self.position += n
        _LM["prefill_tokens"] += self.batch * n
        return self._wrap(logits)

    def decode(self, steps: int):
        """``steps`` greedy tokens for every sequence.  Returns ``(tokens,
        logits)``: the tokens chosen, ``(batch, steps)`` int32, and the logits
        they were chosen from, ``(batch, steps, vocab)`` float32; ``logits[:,
        j]`` are those of position ``position + j``.  The tokens are read back
        once (``session.tokens``: what a serving loop looks at)."""
        steps = int(steps)
        if self._token is None:
            raise ValueError("decode needs a prompt: call prefill first")
        if self.position + steps > self.capacity:
            raise ValueError(f"{self.position} + {steps} positions pass the session's {self.capacity}")
        notes, counts = self.model.serve_notes(self, steps)
        with telemetry.span("lm.decode", **notes):
            self._shared, self._state, self._token, chosen, logits, *counted = (
                self.model.serve_decode(self._shared, self._state, self._token, self.position,
                                        steps))
            with telemetry.sync("lm.tokens"):
                self.tokens, counted = jax.device_get((chosen, dict(*counted)))
        self.position += steps
        _LM["decode_steps"] += steps
        for name, count in {**counts, **counted}.items():
            _LM[name] += int(count)
        return self._wrap(chosen), self._wrap(logits)

    def save(self) -> Snapshot:
        """The current position, to :meth:`rewind` to.  Copies the
        constant-size state (window rings and Mamba states; a retention
        model's whole state; nothing of a model whose whole cache grows with
        the context); a shared key/value cache is not copied, its entries
        past a saved position are simply overwritten later."""
        if self._token is None:
            raise ValueError("nothing to save before the first prefill")
        token, state = _copied((self._token, self._state))
        _LM["state_bytes_copied"] += tree_bytes(state)
        return Snapshot(self.position, token, state)

    def rewind(self, snapshot: Snapshot) -> None:
        """Back to a saved position of this session."""
        with telemetry.span("lm.rewind", position=snapshot.position):
            live, self._token, self._state = (self._token, self._state), None, None
            self._token, self._state = _restored(live, (snapshot.token, snapshot.state))
            self.position = snapshot.position
        _LM["state_bytes_copied"] += tree_bytes(self._state)
