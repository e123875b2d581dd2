"""The session that serves a language model: a batch of sequences advancing
through a cache on the device, each at its own position.

One :class:`DecodeSession` serves every model of :mod:`heat_tpu.models`:
``prefill(tokens)`` walks a prompt through the model in chunks,
``decode(steps)`` generates greedily on the device and reads the chosen
tokens back once, ``save()`` / ``rewind(snapshot)`` return to earlier
positions.  A model whose step yields one token advances every sequence in
lockstep; a model that drafts (``drafts`` 1: a decode step checks the pending
token and a draft, and yields one token or two) advances each by what it
accepted, and its prompts may differ in length; a model that denoises blocks
(``block_length`` B > 1: a decode step is a block of B positions, fixed over
several passes) prefills each prompt's whole blocks, keeps the prompt's tail
pending in the first block, and advances every sequence by whole blocks, each
from its own position.  What differs between models
is what the model supplies, the five ``serve_*`` methods and
``prefill_chunk``, the positions one of its prefill programs walks:

- ``serve_cache(batch, max_context) -> (capacity, shared, state)``: how many
  positions the session can hold, the part of the cache that grows with the
  context and that a snapshot never copies (later positions are simply
  overwritten), and the constant-size state that a snapshot copies;
- ``serve_prefill(shared, state, ids, position) -> (shared, state, token,
  logits)``: its jitted prefill programs over a prompt, chunk by chunk; a
  fifth element, where a model returns one, is ``(notes, counts)``: what the
  ``lm.prefill`` span's end event carries and what the call adds to the
  ``lm`` counters;
- ``serve_decode(shared, state, token, position, steps) -> (shared, state,
  token, chosen, logits)``: its jitted decode program; a sixth element, where
  a model returns one, is a dictionary of device numbers for the ``lm``
  counters that ride back with the tokens in the one readback (and that the
  ``lm.decode`` span's end event carries).  A model that drafts takes every
  sequence's position, ``lengths`` in ``serve_prefill``, a pending token that
  is the pair ``(token, draft)``, and returns as ``chosen`` the pair
  ``(emitted, accepted)``; a model that denoises blocks takes every sequence's
  position and ``lengths``, its pending token is the pending block ``(tokens,
  fixed)``, ``steps`` counts blocks, and it returns as ``chosen`` the pair
  ``(tokens, confidence)`` (and no logits from ``serve_prefill``);
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
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import telemetry, types
from ..core.dndarray import DNDarray
from ..ops._pallas_common import device_copy

__all__ = ["DecodeSession", "Snapshot"]

# decode_steps, prefill_tokens and prefill_s (host seconds of the prefills, to the first token
# ready) count what sessions did; cache_keys_visible and cache_keys_fetched are the key slots of
# the shared cache that the decode steps' reads could see and the slots the decode kernel's rule
# fetches for them (their ratio is the fetch share; both stand still where the kernel does not run
# and the jax.numpy fallback reads the cache); state_bytes_stepped is the constant-size state read
# plus written by decode steps whose mixer walks all of it every step (power retention),
# state_bytes_copied what save and rewind copied; index_keys_scanned and latent_rows_read are the
# index keys that a sparse selection scored and the latent rows its attention then read (from
# shapes), selections_by_cut the layer-steps whose selection was a cut and a compaction and not
# every visible slot, latent_reads_by_kernel the layer-steps whose chosen rows the read's kernel
# fetched (from shapes), expert_pairs and experts_hit the token-expert pairs computed on the experts
# held here and the held experts that received a token, summed over a call's steps and layers
# (counted on the device); prefill_blocks_by_kernel the layers x key blocks of a sparse model's
# prefills that the attention kernel folded (from shapes); drafts_made and drafts_accepted the
# drafts a drafting model's steps checked and those that stood (counted on the device), and
# latent_rows_streamed the latent rows its full reads visited, layers x sessions x visible rows
# summed over steps (counted on the device); denoise_passes and commit_passes the passes a model
# that denoises blocks ran (each once for the whole batch, as decode_steps, which counts its
# blocks), tokens_unmasked the positions its denoising passes fixed (counted on the device), and
# kv_rows_streamed the key/value rows its passes' stream read, layers x sessions x visible rows
# summed over every pass, commits included (from the positions); cache_bytes is what the newest
# session allocated, by kind
_LM = telemetry.register_group(
    "lm",
    {"decode_steps": 0, "prefill_tokens": 0, "prefill_s": 0.0,
     "cache_keys_visible": 0, "cache_keys_fetched": 0,
     "state_bytes_stepped": 0, "state_bytes_copied": 0,
     "index_keys_scanned": 0, "latent_rows_read": 0, "selections_by_cut": 0,
     "latent_reads_by_kernel": 0,
     "expert_pairs": 0, "experts_hit": 0, "prefill_blocks_by_kernel": 0,
     "drafts_made": 0, "drafts_accepted": 0, "latent_rows_streamed": 0,
     "denoise_passes": 0, "commit_passes": 0, "tokens_unmasked": 0, "kv_rows_streamed": 0,
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
    """Saved positions of a :class:`DecodeSession`: the furthest position,
    the token waiting to be fed at each sequence's (a model that drafts: the
    token and its draft; a model that denoises blocks: the pending block), a
    copy of the constant-size state, and every sequence's position."""

    position: int
    token: Any
    state: Any
    positions: Any = None


class DecodeSession:
    """The cache of ``batch`` sequences, and the calls that move it:
    :meth:`prefill`, :meth:`decode`, :meth:`save`, :meth:`rewind`.
    ``capacity`` is how many positions it can hold (the model says: whole
    blocks of a key/value cache, or the positions its encoding reaches);
    ``positions`` is each sequence's position, ``position`` the furthest
    (the one position of sequences in lockstep)."""

    def __init__(self, model, batch: int, max_context: int):
        self.model = model
        self.cfg = model.cfg
        self.batch = int(batch)
        self.drafts = int(getattr(model, "drafts", 0))
        self.block = int(getattr(model, "block_length", 1))
        self.capacity, self._shared, self._state = model.serve_cache(self.batch, int(max_context))
        self.positions = np.zeros(self.batch, np.int64)
        self._token = None  # the token waiting to be fed at each position (and its draft)
        self.tokens = None  # the newest decode call's tokens, on the host
        self.emitted = None  # a drafting model's newest call: tokens emitted a sequence, on the host
        self.accepted = None  # and whether each step's draft stood, (batch, steps)
        self.confidence = None  # a block model's newest call: each pass's confidences, on the host
        _LM["cache_bytes"].update(dict.fromkeys(_LM["cache_bytes"], 0), **self.cache_bytes())

    @property
    def position(self) -> int:
        return int(self.positions.max())

    @position.setter
    def position(self, value: int) -> None:
        self.positions = np.full(self.batch, int(value), np.int64)

    def cache_bytes(self) -> dict:
        """Bytes this session holds on the device, by kind of state."""
        return self.model.serve_bytes(self._shared, self._state)

    def _wrap(self, array) -> DNDarray:
        from ..core.devices import get_device

        return DNDarray(array, tuple(array.shape), types.canonical_heat_type(array.dtype),
                        None, get_device(), self.model.comm)

    def prefill(self, tokens, lengths=None) -> DNDarray:
        """Append a prompt of ``(batch, n)`` token ids at the current position
        (one for every sequence).  Returns the logits of its last position,
        ``(batch, vocab)``; their argmax is the token the next :meth:`decode`
        feeds first.  ``lengths`` (a model that drafts, or that denoises
        blocks): sequence ``b``'s prompt is ``tokens[b, :lengths[b]]``, and the
        logits are those of its last position.  A model that denoises blocks
        prefills each prompt's whole blocks and returns the pending first
        block, ``(batch, block_length)`` token ids: the prompt's tail, then the
        mask id."""
        ids = tokens.larray if isinstance(tokens, DNDarray) else jnp.asarray(tokens)
        ids = ids.astype(jnp.int32)
        if ids.ndim != 2 or ids.shape[0] != self.batch or ids.shape[1] < 1:
            raise ValueError(f"prefill takes (batch={self.batch}, n >= 1) token ids, got {ids.shape}")
        n = int(ids.shape[1])
        if lengths is None and self.block > 1:
            lengths = np.full(self.batch, n)
        if lengths is not None:
            if not self.drafts and self.block == 1:
                raise ValueError("the sequences of a model whose step yields one token advance in "
                                 "lockstep: prefill takes no lengths (a model that drafts, or "
                                 "that denoises blocks, does)")
            lengths = np.asarray(lengths, np.int64)
            if lengths.shape != (self.batch,) or lengths.min() < 1 or lengths.max() > n:
                raise ValueError(f"lengths are (batch={self.batch},) numbers in 1 .. {n}")
        if self.positions.min() != self.positions.max():
            raise ValueError("prefill appends at one position for every sequence")
        if self.position + n > self.capacity:
            raise ValueError(f"{self.position} + {n} positions pass the session's {self.capacity}")
        advance = np.full(self.batch, n, np.int64) if lengths is None else (
            lengths - lengths % self.block)
        started = time.perf_counter()
        with telemetry.span("lm.prefill", tokens=int(advance.sum()),
                            chunk=self.model.prefill_chunk) as span:
            extra = () if lengths is None else (lengths,)
            self._shared, self._state, self._token, logits, *told = self.model.serve_prefill(
                self._shared, self._state, ids, self.position, *extra)
            notes, counts = told[0] if told else ({}, {})
            span.note(**notes)
            # the span ends when the first token is ready, as a serving loop
            # waits for it before it streams: the session's time to first token
            # (a model that denoises blocks makes no token before its first
            # block: when the prompt's keys and values are written)
            with telemetry.sync("lm.prefill"):
                jax.block_until_ready(self._token if logits is not None
                                      else (self._token, self._shared))
            self.positions = self.positions + advance
        if telemetry.ledger_enabled():
            _LM["prefill_s"] += time.perf_counter() - started
        _LM["prefill_tokens"] += int(advance.sum())
        for name, count in counts.items():
            _LM[name] += count
        # the pending block is the session's own, which decode gives up: a copy
        return self._wrap(jnp.copy(self._token[0]) if logits is None else logits)

    def decode(self, steps: int):
        """``steps`` greedy steps for every sequence.  Returns ``(tokens,
        logits)``: the tokens chosen, ``(batch, steps)`` int32, and the logits
        they were chosen from, ``(batch, steps, vocab)`` float32; ``logits[:,
        j]`` are those of position ``position + j``.  The tokens are read back
        once (``session.tokens``: what a serving loop looks at).

        A model that drafts: a step checks two positions a sequence and emits
        one token or two.  ``tokens`` ``(batch, 2 steps)`` are each sequence's
        emitted tokens, then -1 (``session.emitted``: how many, read back with
        them, as ``session.accepted``, ``(batch, steps)``, whether each step's
        draft stood); ``logits`` ``(batch, steps, 2, vocab)`` are those of the
        positions each step checked, the pending token's and the draft's.

        A model that denoises blocks: ``steps`` blocks of B positions a
        sequence.  ``tokens`` ``(batch, steps B)`` are each block's, -1 where
        a prompt token sat in the first; ``logits`` ``(batch, steps B,
        vocab)`` those of the pass that fixed each position (zero at a
        prompt's); ``session.confidence`` ``(batch, steps, passes, B)`` each
        pass's confidence at every position, ``-inf`` where it was already
        fixed (read back with the tokens)."""
        steps = int(steps)
        if self._token is None:
            raise ValueError("decode needs a prompt: call prefill first")
        reach = (1 + self.drafts) * self.block * steps
        if self.position + reach > self.capacity:
            raise ValueError(f"{self.position} + {reach} positions pass the session's {self.capacity}")
        notes, counts = self.model.serve_notes(self, steps)
        at = self.positions if self.drafts or self.block > 1 else self.position
        with telemetry.span("lm.decode", **notes) as span:
            self._shared, self._state, self._token, chosen, logits, *counted = (
                self.model.serve_decode(self._shared, self._state, self._token, at, steps))
            with telemetry.sync("lm.tokens"):
                self.tokens, counted = jax.device_get((chosen, dict(*counted)))
            counted = {name: int(count) for name, count in counted.items()}
            advance = steps
            if self.drafts:
                self.tokens, self.accepted = self.tokens
                self.emitted = advance = steps + self.accepted.sum(axis=1)
                chosen = chosen[0]
                span.note(acceptance=counted["drafts_accepted"] / (self.batch * steps))
            elif self.block > 1:
                self.tokens, self.confidence = self.tokens
                advance = self.block * steps
                chosen = chosen[0]
            span.note(**counted)
        self.positions = self.positions + advance
        _LM["decode_steps"] += steps
        for name, count in {**counts, **counted}.items():
            _LM[name] += int(count)
        return self._wrap(chosen), self._wrap(logits)

    def save(self) -> Snapshot:
        """The current positions, to :meth:`rewind` to.  Copies the
        constant-size state (window rings and Mamba states; a retention
        model's whole state; nothing of a model whose whole cache grows with
        the context) and the pending tokens (and drafts); a shared key/value
        cache is not copied, its entries past a saved position are simply
        overwritten later."""
        if self._token is None:
            raise ValueError("nothing to save before the first prefill")
        token, state = _copied((self._token, self._state))
        _LM["state_bytes_copied"] += tree_bytes(state)
        return Snapshot(self.position, token, state, self.positions.copy())

    def rewind(self, snapshot: Snapshot) -> None:
        """Back to saved positions of this session."""
        with telemetry.span("lm.rewind", position=snapshot.position):
            live, self._token, self._state = (self._token, self._state), None, None
            self._token, self._state = _restored(live, (snapshot.token, snapshot.state))
            if snapshot.positions is None:
                self.position = snapshot.position
            else:
                self.positions = np.array(snapshot.positions, np.int64)
        _LM["state_bytes_copied"] += tree_bytes(self._state)
