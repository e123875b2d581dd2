"""HBM residency ledger: per-buffer attribution, watermarks, OOM forensics.

Heat's scarce resource is device memory per worker (PAPER.md §0: one shard
per process, split along one axis), and the ROADMAP's out-of-core item —
"stream what doesn't fit in HBM" — cannot be built or debugged without
measuring what fits.  PRs 6–7 gave the *time* axis a full observability
plane (flight recorder, spans, cost ledger, measured roofline); this module
is the *memory* counterpart, wired through the same telemetry levels:

**Live-buffer ledger.**  Every DNDarray construction (and the factory/
transport/fusion output sites) registers its device buffer here via a
``weakref.finalize`` — nbytes, dtype, split, sharding, a creation site
(the user ``file:line``, reusing the guard's caller-attribution walk), and
a tag (``leaf|pinned|staging|donated|output``).  Entries die with their
buffers; :func:`live_buffers` answers "who holds HBM right now" top-K by
bytes, :func:`census` packages the same answer for OOM postmortems, and
the ``memtrack`` group in ``telemetry.snapshot()`` carries the summary.

**Unified device readers.**  :func:`device_bytes_in_use` /
:func:`min_free_bytes` are the ONE ``device.memory_stats()`` reader
(previously three hand-rolled copies: ``utils/monitor.py``,
``cluster/kmeans.py``, and per-call max loops), tolerant of backends that
return ``None`` (CPU).  :func:`stats_override` lets
tests — and :meth:`FaultInjector.low_hbm` — simulate a memory-starved
device on backends with no stats, so the informed OOM backoff is testable
on the CI mesh.

**Watermark sampling.**  :func:`sample_bytes` reads the max per-device
``bytes_in_use`` (falling back to the ledger's tracked live bytes where
the backend is silent — the source rides the sample, so a ledger-derived
number is never mistaken for a device-measured one).
``telemetry.timed_call`` samples it around the three timed execution
sites (fusion cache-hit path, transport tile loops, ring matmul), giving
``telemetry.programs()`` / ``roofline_report()`` a measured
``peak_bytes`` + memory-amplification column and ``export_trace()`` a
Perfetto counter track.

**Retention detection.**  :func:`memwatch` scopes a region whose
registrations are expected to die by exit; survivors — plus fusion pins
whose owning Expr is gone (``fusion.pin_leaks``) — surface through
:func:`leaks`.

Gating: the ledger registers at ``events`` level only (``off``,
``counters`` and ``trace`` pay one integer compare per would-be
registration, matching telemetry's documented idle cost: a traced run
walks no stack that the timed run does not); watermark sampling rides
``timed_call``'s existing gate (every call at ``events``, every Nth at
``counters`` and ``trace``).
"""

from __future__ import annotations

import gc
import os
import time
import weakref
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

import jax

from . import guard, telemetry

__all__ = [
    "census",
    "device_bytes_in_use",
    "device_peaks",
    "enabled",
    "leaks",
    "live_buffers",
    "memwatch",
    "min_free_bytes",
    "register_buffer",
    "reset",
    "sample_bytes",
    "set_enabled",
    "set_stats_override",
    "stats_override",
    "suggest_budget",
    "summary",
    "tag_buffer",
    "would_fit",
]

# the tag vocabulary: why a buffer is (still) resident
TAGS = ("leaf", "pinned", "staging", "donated", "output")

# kill-switch UNDER the telemetry level: HEAT_TPU_MEMTRACK=0 keeps the
# flight recorder / spans / timings at events level but silences the
# residency ledger and watermark sampler — the control the cb
# memtrack_overhead row toggles to price the ledger alone, and an
# operator's out if ledger cost ever matters on a hot serving path
_ENABLED = [os.environ.get("HEAT_TPU_MEMTRACK", "1").lower()
            not in ("0", "off", "false")]


def set_enabled(on: bool) -> bool:
    """Enable/disable the ledger + sampler (returns the previous state).
    Orthogonal to the telemetry level: disabling keeps every other
    events-level facility live."""
    prev = _ENABLED[0]
    _ENABLED[0] = bool(on)
    return prev


def enabled() -> bool:
    return _ENABLED[0]

# id(buffer) -> record; the finalize on the buffer removes the entry, so
# the ledger holds no strong reference and can never extend a lifetime
_LEDGER: Dict[int, dict] = {}
_LIVE_BYTES = [0]       # sum of nbytes over _LEDGER (mutable module slot)
_PEAK_LIVE = [0]        # high-water mark of _LIVE_BYTES
_REG_SEQ = [0]          # registration counter (memwatch scope marker)
_DEVICE_PEAKS: Dict[str, int] = {}   # device str -> max sampled bytes_in_use
_WATCH_RETAINED: List[dict] = []     # survivors of the last memwatch() scope
# per-tag live bytes + their high-water marks, maintained incrementally
# (register/retag/drop) rather than derived from _LEDGER: a derived scan
# only sees the tag at snapshot time, but the streaming engine's budget
# proof needs the PEAK "staging" residency — the most slab bytes ever
# simultaneously live — which only an incremental counter can record
_LIVE_BY_TAG: Dict[str, int] = {}
_PEAK_BY_TAG: Dict[str, int] = {}


def _reset_state() -> None:
    _LEDGER.clear()
    _LIVE_BYTES[0] = 0
    _PEAK_LIVE[0] = 0
    _REG_SEQ[0] = 0
    _DEVICE_PEAKS.clear()
    _WATCH_RETAINED.clear()
    _LIVE_BY_TAG.clear()
    _PEAK_BY_TAG.clear()


def _tag_add(tag: str, nbytes: int) -> None:
    live = _LIVE_BY_TAG.get(tag, 0) + nbytes
    _LIVE_BY_TAG[tag] = live
    if live > _PEAK_BY_TAG.get(tag, 0):
        _PEAK_BY_TAG[tag] = live


def summary() -> dict:
    """The ``memtrack`` group's derived fields: live count/bytes, the
    ledger high-water mark, a per-tag bytes breakdown, and the sampled
    per-device peaks."""
    by_tag: Dict[str, int] = {}
    by_dtype: Dict[str, int] = {}
    for rec in _LEDGER.values():
        by_tag[rec["tag"]] = by_tag.get(rec["tag"], 0) + rec["nbytes"]
        dt = str(rec["dtype"])
        by_dtype[dt] = by_dtype.get(dt, 0) + rec["nbytes"]
    return {
        "live_buffers": len(_LEDGER),
        "live_bytes": _LIVE_BYTES[0],
        "peak_live_bytes": _PEAK_LIVE[0],
        "bytes_by_tag": by_tag,
        # per-dtype residency: the one-snapshot answer to "what did
        # quantizing the weights actually buy" (int8 vs f32/bf16 bytes)
        "bytes_by_dtype": by_dtype,
        # high-water marks per tag: "staging" is the streaming engine's
        # proof that double-buffered slabs never exceeded their budget
        "peak_bytes_by_tag": dict(_PEAK_BY_TAG),
        "device_peak_bytes": dict(_DEVICE_PEAKS),
    }


_COUNTERS = telemetry.register_group(
    "memtrack",
    {
        # buffers ever registered / released by their finalizer
        "registered": 0,
        "released": 0,
        # re-registrations of an already-ledgered live buffer (an alias
        # wrapped again — e.g. a no-pad _to_physical pass-through)
        "rebinds": 0,
        # watermark reads taken by telemetry.timed_call
        "mem_samples": 0,
    },
    extra=summary,
    on_reset=_reset_state,
)


def reset() -> None:
    """Zero the counters AND drop the ledger/peaks/watch state
    (registry-managed: ``telemetry.reset_group("memtrack")``)."""
    telemetry.reset_group("memtrack")


# ------------------------------------------------------------------ ledger

def _drop(buf_id: int) -> None:
    rec = _LEDGER.pop(buf_id, None)
    if rec is None:
        return
    _LIVE_BYTES[0] -= rec["nbytes"]
    _LIVE_BY_TAG[rec["tag"]] = _LIVE_BY_TAG.get(rec["tag"], 0) - rec["nbytes"]
    _COUNTERS["released"] += 1


def _format_sharding(s) -> Optional[str]:
    if s is None:
        return None
    spec = getattr(s, "spec", None)
    name = type(s).__name__
    return f"{name}({spec})" if spec is not None else name


def register_buffer(value, *, tag: str = "leaf", split=None) -> Optional[int]:
    """Ledger one device buffer (gated: ``events`` level only; the
    idle cost is the one integer compare below).  The creation site is
    the nearest user frame (guard's caller-attribution walk); lifetime is
    tracked by ``weakref.finalize`` on the buffer itself, so the entry
    disappears exactly when XLA can reclaim the memory.  Re-registering a
    live buffer (an alias wrapped into a second DNDarray) keeps the first
    entry — the true creation site — and counts a rebind.  Returns the
    ledger key (``id(value)``) or ``None`` when not ledgered."""
    if telemetry._LEVEL != telemetry._EVENTS or not _ENABLED[0]:
        return None
    try:
        # itemsize * prod(shape), not value.nbytes: jax rederives the
        # nbytes property per read (~5x the cost of this loop) and the
        # ledger sits on every materialization
        nbytes = int(value.dtype.itemsize)
        for dim in value.shape:
            nbytes *= int(dim)
    except Exception:
        return None  # not an array-like payload (tracers, tuples, None)
    buf_id = id(value)
    if buf_id in _LEDGER:
        _COUNTERS["rebinds"] += 1
        return buf_id
    try:
        # a plain ref with a death callback, not weakref.finalize: finalize
        # pays registry + atexit bookkeeping we don't need (~3x the cost),
        # and this sits on every materialization.  The ref rides the
        # record, so dropping the record (reset) also disarms the callback.
        ref = weakref.ref(value, lambda _r, _b=buf_id: _drop(_b))
    except TypeError:
        return None  # backend array type without weakref support
    _REG_SEQ[0] += 1
    # dtype/sharding stay RAW here (both are tiny interned/shared objects,
    # holding them extends no buffer lifetime); _render formats them
    # lazily so the per-materialization hot path pays no string work
    _LEDGER[buf_id] = {
        "id": buf_id,
        "seq": _REG_SEQ[0],
        "nbytes": nbytes,
        "dtype": getattr(value, "dtype", None),
        "shape": tuple(getattr(value, "shape", ())),
        "split": split,
        "sharding": getattr(value, "sharding", None),
        "tag": tag if tag in TAGS else "leaf",
        "site": guard.format_site(guard.capture_site(2)),
        "ts": time.monotonic(),
        "wr": ref,
    }
    _COUNTERS["registered"] += 1
    _LIVE_BYTES[0] += nbytes
    if _LIVE_BYTES[0] > _PEAK_LIVE[0]:
        _PEAK_LIVE[0] = _LIVE_BYTES[0]
    _tag_add(_LEDGER[buf_id]["tag"], nbytes)
    return buf_id


def tag_buffer(value, tag: str) -> None:
    """Retag a live ledger entry (e.g. a leaf about to be DONATED to a
    destructive resplit, or one newly PINNED by a pending lazy DAG).
    No-op off ``events`` level or for unledgered buffers."""
    if telemetry._LEVEL != telemetry._EVENTS or not _ENABLED[0]:
        return
    rec = _LEDGER.get(id(value))
    if rec is not None and tag in TAGS and tag != rec["tag"]:
        _LIVE_BY_TAG[rec["tag"]] = (
            _LIVE_BY_TAG.get(rec["tag"], 0) - rec["nbytes"]
        )
        rec["tag"] = tag
        _tag_add(tag, rec["nbytes"])


def _pinned_ids() -> set:
    try:
        from . import fusion

        return set(fusion._PINNED)
    except Exception:
        return set()


def _render(rec: dict, pinned: set, now: float) -> dict:
    return {
        "id": rec["id"],
        "nbytes": rec["nbytes"],
        "dtype": str(rec["dtype"]) if rec["dtype"] is not None else None,
        "shape": rec["shape"],
        "split": rec["split"],
        "sharding": _format_sharding(rec["sharding"]),
        "tag": rec["tag"],
        "pinned": rec["id"] in pinned,
        "site": rec["site"],
        "age_s": round(now - rec["ts"], 3),
    }


def live_buffers(top: Optional[int] = 10) -> List[dict]:
    """The live ledger, largest first: one dict per buffer with nbytes,
    dtype, shape, split, sharding, tag, live pin state, the creation site
    (``file:line in func``), and age.  ``top`` bounds the list (``None``
    = all)."""
    rows = sorted(_LEDGER.values(), key=lambda r: -r["nbytes"])
    if top is not None:
        rows = rows[: max(int(top), 0)]
    pinned = _pinned_ids()
    now = time.monotonic()
    return [_render(r, pinned, now) for r in rows]


def census(top: int = 8) -> dict:
    """The buffer census an OOM postmortem attaches: total live
    count/bytes plus the top-K buffers with creation sites and pin
    state — "what was resident when the allocation failed"."""
    return {
        "live_buffers": len(_LEDGER),
        "live_bytes": _LIVE_BYTES[0],
        "bytes_by_dtype": summary()["bytes_by_dtype"],
        "top": live_buffers(top),
    }


# --------------------------------------------------- unified device readers

# test/injection hook: a list of fake per-device memory_stats() dicts
# (each with bytes_in_use/bytes_limit) standing in for jax's readers —
# installed by stats_override() / FaultInjector.low_hbm(), so the
# informed backoff and watermark paths are drivable on stats-less CPU
_STATS_OVERRIDE: Optional[List[dict]] = None


def set_stats_override(devices: Optional[List[dict]]) -> Optional[List[dict]]:
    """Install (or clear, with ``None``) simulated per-device
    ``memory_stats()`` readings; returns the previous override."""
    global _STATS_OVERRIDE
    prev = _STATS_OVERRIDE
    _STATS_OVERRIDE = list(devices) if devices is not None else None
    return prev


@contextmanager
def stats_override(devices: List[dict]):
    """Scoped :func:`set_stats_override`::

    >>> with memtrack.stats_override(
    ...     [{"bytes_in_use": 900, "bytes_limit": 1000}]
    ... ):
    ...     assert memtrack.min_free_bytes() == 100
    """
    prev = set_stats_override(devices)
    try:
        yield
    finally:
        set_stats_override(prev)


# (name, device) pairs cached at first use: jax.local_devices() and
# str(device) are rebuilt per call otherwise, and the watermark sampler
# reads stats twice per timed program — the cache keeps a sample in the
# low-microsecond range.  The local device set is fixed per process.
_DEVICE_READERS: Optional[List[tuple]] = None


def _device_readers() -> List[tuple]:
    global _DEVICE_READERS
    if _DEVICE_READERS is None:
        try:
            _DEVICE_READERS = [(str(d), d) for d in jax.local_devices()]
        except Exception:
            return []  # backend not up yet: retry next call, cache nothing
    return _DEVICE_READERS


def _raw_device_stats() -> List[Tuple[str, Optional[dict]]]:
    """``(device, memory_stats() or None)`` per local device — ``None``
    where the backend has no reader (CPU) or the read fails."""
    if _STATS_OVERRIDE is not None:
        return [
            (str(d.get("device", f"injected:{i}")), d)
            for i, d in enumerate(_STATS_OVERRIDE)
        ]
    out: List[Tuple[str, Optional[dict]]] = []
    for name, dev in _device_readers():
        try:
            stats = dev.memory_stats()
        except Exception:
            stats = None
        out.append((name, stats))
    return out


def device_bytes_in_use() -> Tuple[List[Tuple[str, Optional[int]]], Optional[int]]:
    """``(per_device, worst)``: per-device ``bytes_in_use`` readings and
    their max.  The max — not device 0 — is the number that matters on a
    multi-device mesh: uneven splits and replicated operands peak on
    whichever device holds the remainder.  Devices without stats report
    ``None`` and are ignored by the max (``worst`` is ``None`` when no
    device reports).  The ONE reader behind ``utils/monitor``,
    ``cluster/kmeans`` and the watermark sampler."""
    per: List[Tuple[str, Optional[int]]] = []
    worst = None
    for name, stats in _raw_device_stats():
        used = stats.get("bytes_in_use") if stats else None
        used = int(used) if used is not None else None
        per.append((name, used))
        if used is not None and (worst is None or used > worst):
            worst = used
    return per, worst


def min_free_bytes() -> Optional[int]:
    """Tightest per-device headroom: ``min(bytes_limit - bytes_in_use)``
    over devices exposing both — the budget the informed OOM backoff
    sizes its first-retry tile from.  ``None`` when no device reports."""
    tightest = None
    for _name, stats in _raw_device_stats():
        if not stats:
            continue
        limit = stats.get("bytes_limit")
        used = stats.get("bytes_in_use")
        if limit is None or used is None:
            continue
        free = int(limit) - int(used)
        if tightest is None or free < tightest:
            tightest = free
    return tightest


def suggest_budget(
    request: int,
    *,
    fraction: float = 0.25,
    floor: int = 0,
    headroom: int = 0,
    free: Optional[int] = None,
) -> Optional[int]:
    """THE free-HBM budget formula:
    ``max(floor, min(request, (free - headroom) * fraction))``.

    One helper behind every HBM-informed sizing decision — transport's
    informed OOM retry, kmeans' lane-pack residency check, and the
    autotune plane's plan-time tile/staging seeding — so the clamp
    semantics can never drift between sites.  ``request`` is what the
    caller would spend absent memory pressure; ``fraction`` reserves
    slack for everything that isn't this buffer; ``headroom`` is an
    absolute reservation subtracted before the fraction.  Returns
    ``None`` when no device reports memory stats (statsless backends
    keep their static defaults — never a fake budget).  Pass ``free``
    to reuse a reading already taken this call."""
    if free is None:
        # cheap no-op on statsless backends: reuse sample_bytes' latch
        # (set after one full silent device read; overrides beat it)
        if _STATS_OVERRIDE is None and _STATSLESS[0]:
            return None
        free = min_free_bytes()
        if free is None:
            return None
    granted = int((int(free) - int(headroom)) * float(fraction))
    return max(int(floor), min(int(request), granted))


def would_fit(
    nbytes: int,
    *,
    fraction: float = 0.5,
    headroom: int = 0,
) -> Optional[bool]:
    """Admission-control face of :func:`suggest_budget`: does an
    ``nbytes`` staging allocation fit inside the suggested budget?

    Returns ``None`` on statsless backends (CPU) — the caller should
    admit, never shed on fake numbers.  The serving front door's
    ``hbm_pressure`` shed decision routes through here so its clamp
    semantics stay identical to transport's OOM retry and the autotune
    seeding sites."""
    nbytes = int(nbytes)
    granted = suggest_budget(nbytes, fraction=fraction, headroom=headroom)
    if granted is None:
        return None
    return granted >= nbytes


def device_peaks() -> Dict[str, int]:
    """Max sampled ``bytes_in_use`` per device (fed by
    :func:`sample_bytes` via ``telemetry.timed_call``)."""
    return dict(_DEVICE_PEAKS)


# latched after a full device read where NO device reported stats: a
# backend that is silent once (CPU) is silent for the process, and the
# sampler runs twice per timed program — skip the 8-device probe loop.
# Overrides are checked before the latch, so low_hbm() still lands.
_STATSLESS = [False]


def sample_bytes() -> Tuple[Optional[int], Optional[str]]:
    """One watermark reading: ``(bytes, source)``.  Prefers the measured
    device max (``source="device"``, folding per-device peaks as a side
    effect); where the backend is silent, falls back to the ledger's
    tracked live bytes (``source="ledger"`` — only meaningful while the
    ledger records, i.e. ``events`` level).  ``(None, None)`` when
    neither axis has data — an honest unknown, never a fake zero."""
    if not _ENABLED[0]:
        return None, None
    if _STATS_OVERRIDE is None and _STATSLESS[0]:
        per, worst = [], None
    else:
        per, worst = device_bytes_in_use()
        if worst is None and _STATS_OVERRIDE is None and per:
            _STATSLESS[0] = True
    if worst is not None:
        _COUNTERS["mem_samples"] += 1
        for name, used in per:
            if used is not None and used > _DEVICE_PEAKS.get(name, -1):
                _DEVICE_PEAKS[name] = used
        return worst, "device"
    if telemetry._LEVEL == telemetry._EVENTS:
        _COUNTERS["mem_samples"] += 1
        return _LIVE_BYTES[0], "ledger"
    return None, None


# ------------------------------------------------------ retention detection

class _MemWatch:
    """Handle yielded by :func:`memwatch`; ``retained`` fills at exit."""

    __slots__ = ("retained", "_mark")

    def __init__(self, mark: int):
        self.retained: List[dict] = []
        self._mark = mark


@contextmanager
def memwatch():
    """Retention scope: every buffer registered inside and still alive at
    exit is a suspect.  Exit runs one ``gc.collect()`` (a diagnostic
    scope may hold cycles that would free momentarily anyway), then
    records the survivors on the handle's ``retained`` and module-wide
    for :func:`leaks`::

    >>> with telemetry.memwatch() as w:
    ...     scratch = ht.zeros((4096,), split=0)
    ...     keep = ht.ones((8,), split=0)
    ...     del scratch
    >>> [r["site"] for r in w.retained]   # names keep's creation line
    """
    w = _MemWatch(_REG_SEQ[0])
    try:
        yield w
    finally:
        gc.collect()
        pinned = _pinned_ids()
        now = time.monotonic()
        w.retained = [
            _render(rec, pinned, now)
            for rec in sorted(_LEDGER.values(), key=lambda r: -r["nbytes"])
            if rec["seq"] > w._mark
        ]
        _WATCH_RETAINED[:] = w.retained


def leaks() -> List[dict]:
    """Suspected retention, two classes: ``kind="pin"`` — entries in
    fusion's ``_PINNED`` registry whose owning Expr is gone (the
    ``weakref.finalize`` unpin never fired — exactly the class the pin
    lifecycle tests guard); ``kind="retained"`` — buffers registered
    inside the last :func:`memwatch` scope and STILL alive now.  Empty
    means no evidence of leaked residency."""
    out: List[dict] = []
    try:
        from . import fusion

        for rec in fusion.pin_leaks():
            out.append(dict(rec, kind="pin"))
    except Exception:
        pass
    for row in _WATCH_RETAINED:
        if row["id"] in _LEDGER:
            out.append(dict(row, kind="retained"))
    return out
