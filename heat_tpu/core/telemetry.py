"""Unified telemetry: metrics registry, flight recorder, spans, cost ledger.

Five PRs of subsystems left observability scattered: three hand-rolled
``_STATS`` dicts (``fusion.cache_stats()``, ``transport.stats()``,
``overlap.stats()``), guard warnings with no machine-readable trail, a
:class:`~heat_tpu.utils.fault.StallDetector` whose stalls vanish into a
callback, and a bench-only ``@monitor`` decorator.  There was no single
place to answer *"what did this run compile, retry, fall back on, and why
was it slow?"* — the substrate the serving/scale-out roadmap item needs
for admission/backpressure and warm-cache batching.  This module is that
place, exposed as ``ht.telemetry``.  Four parts:

**Metrics registry.**  Every counter group registers ONCE with its
defaults (:func:`register_group`); the registry hands back the live dict
the owning module mutates on its hot path (a plain dict increment — no
wrapper, no lock, no new cost).  :func:`snapshot` returns every group as
one nested dict, :func:`export_prometheus` emits the text exposition
format for scrapers, and :func:`reset_all` / :func:`reset_group` restore
the registered defaults *in place* — nested dicts keep their object
identity, so module-level aliases stay valid, and a counter added to the
defaults is reset automatically (the ``fused_tails`` counter previously
had to be added to ``transport._STATS`` *and* ``reset_stats()`` by hand;
registry-managed reset makes that drift impossible).

**Flight recorder.**  A bounded ring buffer of structured events with
monotonic timestamps and sequence numbers: fusion compile start/end
(fingerprint, root arity, mesh), cache hit/eviction, fallback with
reason, transport OOM retries with the halved tile budget, guard
replay/blame, ring-vs-GSPMD dispatch decisions with their cost-model
inputs, stall heartbeats.  Gated by ``HEAT_TPU_TELEMETRY``:

    ``off``       record nothing (no events, no ledger, no spans)
    ``counters``  cost ledger on, 1 executable call in 16 wall-clocked;
                  no events (the default)
    ``events``    + flight recorder + span events, EVERY executable call
                  wall-clocked (a ``block_until_ready`` and two
                  ``memory_stats()`` reads each) and memtrack's residency
                  ledger on: the debugging level, it changes the timing
    ``trace``     ``counters`` + flight recorder + span events + one
                  ``jax.profiler.TraceAnnotation`` per span (named
                  ``ht:<span>``), so spans land in profiler traces
                  (``monitor.profile_trace``, ``perf/run.py --trace 1``).
                  It adds NO host sync, ``memory_stats()`` read or stack
                  walk that ``counters`` does not make: the traced program
                  is the timed program plus annotations

:func:`events` reads the buffer, :func:`dump` writes a postmortem
document, and :func:`postmortem` is invoked automatically on a guard
``raise``, an exec-error eager fallback, and a detected stall — set
``HEAT_TPU_TELEMETRY_DUMP=/path`` to have those write the document to
disk unprompted.

**Span tracing.**  :func:`span` is a context manager *and* decorator
with nesting (parent ids ride the events, and ``root``, the id of the
outermost open span of the thread, is shared by all spans of one user
call) wired into ``materialize``/``materialize_all``, the transport
kernels, ring dispatch, ``linalg.qr``, ``autotune.decide``/``explore``
and estimator ``.fit`` loops.  In ``trace`` mode each span also enters
``jax.profiler.TraceAnnotation("ht:" + name)``: the prefix tells the
program's spans from the runtime's own host events (``PjitFunction``,
``DevicePut``, ``np.asarray(jax.Array)``); the flight recorder keeps the
bare names.  Open spans are visible across threads (:func:`open_spans`)
— a stall postmortem shows what was in flight.

**Sync spans.**  :func:`sync` marks a place where the host waits for the
device (a scalar readback, a fence): ``with telemetry.sync("kmeans.n_iter"):
n = int(n_iter)``.  It counts in the ``sync`` group (``count``,
``by_site``) from ``counters`` up and is a span named ``sync:<site>``
from ``events`` up, so a profiler trace shows the wait under the
program's name (``ht:sync:kmeans.n_iter``) around the runtime's
``np.asarray(jax.Array)``.

**Device scopes.**  The jitted programs name their stages with
``jax.named_scope`` (trace-time only): ``ht.kmeans.lloyd`` / ``.assign``
/ ``.update``, ``ht.cdist``, ``ht.fused/<op>``, ``ht.qr.gram1`` …
``ht.qr.apply2``, ``ht.qr.panel``, ``ht.tsqr.leaf`` / ``.gather`` /
``.merge`` / ``.apply``.  The profiler's device events carry the scope
path; ``perf/span_reduce.py`` sums device time by it.

**Cost ledger.**  At fusion compile time the op DAG is walked once to
estimate FLOPs and HBM bytes (elementwise: one FLOP per output element;
reductions/composites: one per input element; matmul: ``2·m·k·n`` — the
same accounting the overlap dispatcher's bytes-per-step model uses for
its operands).  The estimate attaches to the compile event and to a
per-program ledger (:func:`programs`), so cb rows can derive
achieved-vs-roofline from telemetry instead of hand-computed constants.

Costs when idle: ``off``/``counters`` mode adds one integer compare per
would-be event; the ledger walk runs only at compile-cache misses (by
definition not the steady state).  The ``telemetry_overhead`` cb row
measures the events-on tax against a <2% bar.
"""

from __future__ import annotations

import copy
import hashlib
import itertools
import json
import os
import re
import sys
import threading
import time
from collections import OrderedDict, deque
from contextlib import contextmanager
from typing import Any, Callable, Dict, List, Optional


__all__ = [
    "annotate_program",
    "autotune_report",
    "current_span",
    "dump",
    "ensure_program",
    "events",
    "clear_events",
    "export_prometheus",
    "export_trace",
    "leaks",
    "level",
    "live_buffers",
    "memwatch",
    "module_name",
    "open_spans",
    "postmortem",
    "program_hit",
    "programs",
    "record_event",
    "record_peak",
    "record_program",
    "record_timing",
    "register_group",
    "reset_all",
    "reset_group",
    "reset_programs",
    "roofline_report",
    "router_report",
    "serving_report",
    "set_capacity",
    "set_level",
    "set_sample_every",
    "snapshot",
    "snapshot_group",
    "span",
    "sync",
    "telemetry_level",
    "timed_call",
    "timing_active",
]


# ------------------------------------------------------------------- levels
# Ordered modes; each includes everything below it.  Integers so the hot
# gate (`if _LEVEL < _EVENTS: return`) is one compare.

_LEVELS = ("off", "counters", "events", "trace")
_OFF, _COUNTERS, _EVENTS, _TRACE = range(4)


def _env_level() -> int:
    raw = os.environ.get("HEAT_TPU_TELEMETRY", "counters").strip().lower()
    if raw in ("off", "0", "false", "no", "none"):
        return _OFF
    if raw in ("", "counters", "on", "default"):
        return _COUNTERS
    if raw == "events":
        return _EVENTS
    if raw == "trace":
        return _TRACE
    return _COUNTERS


_LEVEL = _env_level()


def level() -> str:
    """Current telemetry level: ``off`` | ``counters`` | ``events`` |
    ``trace`` (``HEAT_TPU_TELEMETRY``)."""
    return _LEVELS[_LEVEL]


def set_level(lvl) -> str:
    """Set the level by name (or int rank); returns the previous name."""
    global _LEVEL
    prev = _LEVELS[_LEVEL]
    if isinstance(lvl, str):
        if lvl not in _LEVELS:
            raise ValueError(f"level must be one of {_LEVELS}, got {lvl!r}")
        _LEVEL = _LEVELS.index(lvl)
    else:
        _LEVEL = min(max(int(lvl), _OFF), _TRACE)
    return prev


@contextmanager
def telemetry_level(lvl):
    """Scoped :func:`set_level` (``with telemetry.telemetry_level("events")``)."""
    prev = set_level(lvl)
    try:
        yield
    finally:
        set_level(prev)


def ledger_enabled() -> bool:
    """Whether the cost ledger records (``counters`` level and above)."""
    return _LEVEL >= _COUNTERS


def events_enabled() -> bool:
    """Whether the flight recorder records (``events`` level and above)."""
    return _LEVEL >= _EVENTS


def trace_enabled() -> bool:
    """Whether spans enter ``jax.profiler.TraceAnnotation`` (``trace``)."""
    return _LEVEL >= _TRACE


# ----------------------------------------------------------- metrics registry

class _Group:
    __slots__ = ("name", "live", "defaults", "extra", "on_reset")

    def __init__(self, name, live, defaults, extra, on_reset):
        self.name = name
        self.live = live
        self.defaults = defaults
        self.extra = extra
        self.on_reset = on_reset


_GROUPS: "OrderedDict[str, _Group]" = OrderedDict()


def register_group(
    name: str,
    defaults: Dict[str, Any],
    *,
    extra: Optional[Callable[[], Dict[str, Any]]] = None,
    on_reset: Optional[Callable[[], None]] = None,
) -> Dict[str, Any]:
    """Register a named counter group and return its LIVE dict.

    The owning module mutates the returned dict directly (plain dict
    increments — registration adds zero hot-path cost).  ``defaults`` is
    deep-copied both at registration and on every reset, so the reset
    contract lives in exactly one place: add a counter to the defaults
    and :func:`reset_group` handles it forever.  ``extra`` contributes
    derived read-only fields to snapshots (e.g. a cache's live ``size``);
    ``on_reset`` runs extra reset work (e.g. clearing a side table).

    Re-registering an existing name returns the already-live dict (the
    registration is idempotent across module reloads)."""
    got = _GROUPS.get(name)
    if got is not None:
        return got.live
    live = copy.deepcopy(defaults)
    _GROUPS[name] = _Group(name, live, copy.deepcopy(defaults), extra, on_reset)
    return live


def _reset_in_place(live: dict, defaults: dict) -> None:
    """Restore ``defaults`` into ``live`` without replacing nested dict
    objects, so module-level aliases into the group stay valid."""
    for k in list(live.keys()):
        if k not in defaults:
            del live[k]
    for k, dv in defaults.items():
        cur = live.get(k)
        if isinstance(dv, dict) and isinstance(cur, dict):
            _reset_in_place(cur, dv)
        else:
            live[k] = copy.deepcopy(dv)


def reset_group(name: str) -> None:
    """Restore one group to its registered defaults (in place)."""
    g = _GROUPS[name]
    _reset_in_place(g.live, g.defaults)
    if g.on_reset is not None:
        g.on_reset()


def reset_all() -> None:
    """Restore EVERY registered group to its defaults — the single reset
    that replaces the hand-maintained per-module ones."""
    for name in _GROUPS:
        reset_group(name)


def snapshot_group(name: str) -> Dict[str, Any]:
    """Deep-copied snapshot of one group, with its ``extra`` fields
    merged in."""
    g = _GROUPS[name]
    out = copy.deepcopy(g.live)
    if g.extra is not None:
        out.update(g.extra())
    return out


def snapshot() -> Dict[str, Dict[str, Any]]:
    """Every registered counter group as ONE nested dict:
    ``{"fusion": {...}, "transport": {...}, "overlap": {...}, ...}``."""
    return {name: snapshot_group(name) for name in _GROUPS}


_METRIC_SAFE = re.compile(r"[^a-zA-Z0-9_]")


def _prom_lines(prefix: str, value, lines: List[str], src: str = "") -> None:
    if isinstance(value, bool):
        value = int(value)
    if isinstance(value, (int, float)):
        lines.append(f"# HELP {prefix} heat_tpu telemetry gauge {src or prefix}")
        lines.append(f"# TYPE {prefix} gauge")
        lines.append(f"{prefix} {value}")
        return
    if isinstance(value, dict):
        for k, v in value.items():
            _prom_lines(
                f"{prefix}_{_METRIC_SAFE.sub('_', str(k))}", v, lines,
                src=f"{src}.{k}" if src else str(k),
            )
    # None / strings / other payloads have no numeric exposition — skipped


_LABEL_ESCAPES = {"\\": "\\\\", '"': '\\"', "\n": "\\n"}


def _label_escape(s) -> str:
    return "".join(_LABEL_ESCAPES.get(c, c) for c in str(s))


# per-program roofline gauges emitted for at most this many programs
# (the heaviest by measured total time), keeping scrapes bounded
_PROM_PROGRAMS_MAX = 16


def _program_prom_lines(lines: List[str]) -> None:
    """Labeled ``heat_tpu_program_*`` gauges for the measured programs:
    calls/seconds plus the roofline attribution, keyed by
    ``{fingerprint=...,kind=...}``."""
    try:
        from . import roofline

        rows = roofline.report(programs(), top=_PROM_PROGRAMS_MAX)["rows"]
    except Exception:  # attribution must never break a metrics scrape
        return
    fields = (
        "calls", "total_s", "min_s", "p50_s", "achieved_gflops",
        "achieved_gbps", "frac_compute_roofline", "frac_hbm_roofline",
    )
    for f in fields:
        samples = []
        for r in rows:
            v = r.get(f)
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue
            labels = (
                f'fingerprint="{_label_escape(r["fingerprint"])}"'
                f',kind="{_label_escape(r.get("kind") or "")}"'
            )
            samples.append(f"heat_tpu_program_{f}{{{labels}}} {v}")
        if samples:
            name = f"heat_tpu_program_{f}"
            lines.append(f"# HELP {name} heat_tpu telemetry gauge "
                         f"measured per-program {f}")
            lines.append(f"# TYPE {name} gauge")
            lines.extend(samples)


def _mem_prom_lines(lines: List[str]) -> None:
    """``heat_tpu_mem_*`` gauges from the residency ledger: live bytes,
    live buffer count, the ledger high-water mark, and per-device sampled
    peaks (labeled by device)."""
    try:
        from . import memtrack

        s = memtrack.summary()
        peaks = memtrack.device_peaks()
    except Exception:  # the ledger must never break a metrics scrape
        return
    for name, val, help_ in (
        ("heat_tpu_mem_live_bytes", s["live_bytes"],
         "bytes held by ledgered live buffers"),
        ("heat_tpu_mem_live_buffers", s["live_buffers"],
         "count of ledgered live buffers"),
        ("heat_tpu_mem_peak_live_bytes", s["peak_live_bytes"],
         "high-water mark of ledgered live bytes"),
    ):
        lines.append(f"# HELP {name} heat_tpu telemetry gauge {help_}")
        lines.append(f"# TYPE {name} gauge")
        lines.append(f"{name} {val}")
    if peaks:
        name = "heat_tpu_mem_device_peak_bytes"
        lines.append(f"# HELP {name} heat_tpu telemetry gauge max sampled "
                     f"bytes_in_use per device")
        lines.append(f"# TYPE {name} gauge")
        for dev, val in peaks.items():
            lines.append(f'{name}{{device="{_label_escape(dev)}"}} {val}')
    if s.get("bytes_by_dtype"):
        name = "heat_tpu_mem_bytes_by_dtype"
        lines.append(f"# HELP {name} heat_tpu telemetry gauge ledgered "
                     f"live bytes per buffer dtype")
        lines.append(f"# TYPE {name} gauge")
        for dt, val in sorted(s["bytes_by_dtype"].items()):
            lines.append(f'{name}{{dtype="{_label_escape(dt)}"}} {val}')


def _wire_prom_lines(lines: List[str]) -> None:
    """Labeled per-program wire gauges for ledger entries that ship a
    quantized collective: the f32 bytes the program WOULD have moved
    (``heat_tpu_wire_program_logical_bytes``), what its wire format
    actually moved (``heat_tpu_wire_program_bytes``), and the ratio —
    keyed by ``{fingerprint=...,arm=...}``.  The aggregate ``wire`` group
    counters (``heat_tpu_wire_bytes_logical`` etc.) already ride the
    generic group exposition; these break the same story down per
    program so a dashboard can name the compressed collectives."""
    rows = [
        e for e in programs()
        if e.get("wire") and isinstance(e.get("wire_bytes"), (int, float))
    ]
    if not rows:
        return
    for field, metric, help_ in (
        ("logical_bytes", "heat_tpu_wire_program_logical_bytes",
         "f32 bytes the program's collective would move uncompressed"),
        ("wire_bytes", "heat_tpu_wire_program_bytes",
         "bytes the program's quantized wire format moves"),
    ):
        lines.append(f"# HELP {metric} heat_tpu telemetry gauge {help_}")
        lines.append(f"# TYPE {metric} gauge")
        for e in rows:
            labels = (
                f'fingerprint="{_label_escape(e["fingerprint"])}"'
                f',arm="{_label_escape(e["wire"])}"'
            )
            lines.append(f"{metric}{{{labels}}} {float(e.get(field) or 0.0)}")
    metric = "heat_tpu_wire_program_ratio"
    lines.append(f"# HELP {metric} heat_tpu telemetry gauge logical/wire "
                 f"byte compression ratio")
    lines.append(f"# TYPE {metric} gauge")
    for e in rows:
        wb = float(e.get("wire_bytes") or 0.0)
        lb = float(e.get("logical_bytes") or 0.0)
        if wb <= 0.0:
            continue
        labels = (
            f'fingerprint="{_label_escape(e["fingerprint"])}"'
            f',arm="{_label_escape(e["wire"])}"'
        )
        lines.append(f"{metric}{{{labels}}} {round(lb / wb, 4)}")


def export_prometheus() -> str:
    """Text exposition format (``# HELP`` + ``# TYPE gauge`` + one value
    line per numeric leaf): every registered group flattened as
    ``heat_tpu_<group>_<counter>`` (label-unsafe characters in group and
    counter names escaped to ``_``; the ``# HELP`` line keeps the
    original dotted path), plus labeled per-program
    ``heat_tpu_program_*`` gauges for the measured roofline rows and the
    ``heat_tpu_mem_*`` residency gauges.  Non-numeric fields are
    skipped."""
    lines: List[str] = []
    for name in _GROUPS:
        _prom_lines(
            f"heat_tpu_{_METRIC_SAFE.sub('_', name)}", snapshot_group(name),
            lines, src=name,
        )
    _program_prom_lines(lines)
    _mem_prom_lines(lines)
    _wire_prom_lines(lines)
    return "\n".join(lines) + "\n"


# ----------------------------------------------------------- flight recorder

_RING: "deque[dict]" = deque(maxlen=2048)
_SEQ = itertools.count()
_DROPPED = [0]  # events evicted by the ring bound (list: mutable module slot)


def set_capacity(n: int) -> int:
    """Resize the ring buffer (keeps the newest events that fit).
    Returns the previous capacity."""
    global _RING
    prev = _RING.maxlen
    _RING = deque(_RING, maxlen=max(int(n), 1))
    return prev


# event keys the recorder itself owns; caller fields shadowing them are
# re-keyed with an "x_" prefix instead of corrupting the envelope
_RESERVED_FIELDS = frozenset(("seq", "ts", "kind", "span", "tid"))


def record_event(kind: str, /, **fields) -> Optional[int]:
    """Append one structured event to the flight recorder.

    Returns the event's sequence number, or ``None`` below ``events``
    level (the no-record gate is one integer compare — safe to call on
    hot paths unconditionally).  Events carry a monotonic ``ts``, the
    recording thread's ident (``tid`` — the trace-export lane), the
    calling thread's innermost open span id (``span``), and the caller's
    ``fields`` (a field named like an envelope key — ``kind``/``seq``/
    ``ts``/``span``/``tid`` — is stored re-keyed as ``x_<name>``)."""
    if _LEVEL < _EVENTS:
        return None
    seq = next(_SEQ)
    if len(_RING) == _RING.maxlen:
        _DROPPED[0] += 1
    cur = _span_stack()
    evt = {
        "seq": seq,
        "ts": time.monotonic(),
        "kind": kind,
        "span": cur[-1].id if cur else None,
        "tid": threading.get_ident(),
    }
    for k, v in fields.items():
        evt[f"x_{k}" if k in _RESERVED_FIELDS else k] = v
    _RING.append(evt)
    return seq


def events(kind: Optional[str] = None, since: Optional[int] = None) -> List[dict]:
    """The recorded events, oldest first; ``kind`` filters.  ``since`` is
    an incremental-read cursor: only events with a sequence number
    strictly greater than it are returned, so an external poller can feed
    the last ``seq`` it saw back in instead of re-scanning the ring."""
    got = list(_RING)
    if since is not None:
        got = [e for e in got if e["seq"] > since]
    if kind is not None:
        got = [e for e in got if e["kind"] == kind]
    return got


def clear_events() -> None:
    """Drop the recorded events (tests/benchmarks)."""
    _RING.clear()
    _DROPPED[0] = 0


def dump(file=None) -> None:
    """Write a postmortem document — level, open spans, the full event
    ring, the program ledger, and a counters snapshot — as one JSON
    object.  ``file`` is a path or a writable handle (default stderr)."""
    doc = {
        "telemetry_level": level(),
        "capacity": _RING.maxlen,
        "dropped": _DROPPED[0],
        "open_spans": open_spans(),
        "events": events(),
        "programs": programs(),
        "counters": snapshot(),
    }
    try:
        from . import memtrack

        # who held HBM at dump time: the OOM-forensics census (top-K live
        # buffers with creation sites), riding every postmortem document
        doc["buffers"] = memtrack.census(top=16)
    except Exception:
        doc["buffers"] = None
    if isinstance(file, (str, os.PathLike)):
        with open(file, "w") as fh:
            json.dump(doc, fh, indent=1, default=repr)
        return
    out = file or sys.stderr
    json.dump(doc, out, indent=1, default=repr)
    out.write("\n")


def postmortem(reason: str, **fields) -> None:
    """Automatic degradation dump: called on a guard ``raise``, an
    exec-error eager fallback, and a detected stall.  Records a
    ``postmortem`` event; when ``HEAT_TPU_TELEMETRY_DUMP`` names a path,
    the full :func:`dump` document is written there with a sibling
    ``<path>.trace.json`` Chrome-trace rendering (:func:`export_trace`)
    for Perfetto (a repeated postmortem in one process appends ``.2``,
    ``.3``, ... instead of overwriting the first trail).  No-op below
    ``events`` level."""
    if _LEVEL < _EVENTS:
        return
    record_event("postmortem", reason=reason, **fields)
    path = os.environ.get("HEAT_TPU_TELEMETRY_DUMP", "").strip()
    if not path:
        return
    try:
        final = path
        n = 1
        while os.path.exists(final):
            n += 1
            final = f"{path}.{n}"
        dump(final)
        export_trace(f"{final}.trace.json")
    except OSError:  # a broken dump path must never mask the real failure
        pass


# ------------------------------------------------------------- span tracing

class _SpanState:
    __slots__ = ("id", "name", "parent", "root", "t0")

    def __init__(self, sid, name, parent, root, t0):
        self.id = sid
        self.name = name
        self.parent = parent
        self.root = root
        self.t0 = t0


_SPAN_IDS = itertools.count(1)
_TLS = threading.local()
# thread ident -> that thread's open-span stack; lets the stall watchdog
# (a different thread) see what the workload had in flight
_ALL_STACKS: Dict[int, List[_SpanState]] = {}
# what a program span's profiler annotation is named: the prefix tells the
# program's spans from the runtime's own host events in a profiler trace
ANNOTATION_PREFIX = "ht:"


def _span_stack() -> List[_SpanState]:
    got = getattr(_TLS, "stack", None)
    if got is None:
        got = _TLS.stack = []
    return got


def current_span() -> Optional[dict]:
    """``{"id", "name", "parent"}`` of the calling thread's innermost
    open span, or ``None``."""
    cur = _span_stack()
    if not cur:
        return None
    s = cur[-1]
    return {"id": s.id, "name": s.name, "parent": s.parent}


def open_spans() -> List[dict]:
    """Every open span across ALL threads, outermost first per thread —
    what a stall postmortem shows as "in flight"."""
    out = []
    for tid, stack in list(_ALL_STACKS.items()):
        for s in list(stack):
            out.append(
                {"thread": tid, "id": s.id, "name": s.name, "parent": s.parent}
            )
    return out


class span:
    """Context manager AND decorator marking one timed region.

    ``with telemetry.span("transport.resplit", tile_bytes=tb): ...`` or::

        @telemetry.span("kmeans.fit")
        def fit(self, x): ...

    At ``events`` level, entry/exit append ``span_begin``/``span_end``
    events carrying the span id, its parent id (nesting), ``root`` (the
    id of the outermost open span of the thread: all spans of one user
    call share it), the ``attrs``, and the wall duration; every event
    recorded inside the region carries the span's id.  :meth:`note` adds
    attributes that are only known inside the region (the path a
    dispatcher took) to the ``span_end`` event.  At ``trace`` level the
    region additionally enters ``jax.profiler.TraceAnnotation("ht:" +
    name)`` so it lands in profiler traces (``monitor.profile_trace``).
    Below ``events`` level enter/exit are a single integer compare each —
    spans stay wired on hot paths at zero steady-state cost."""

    __slots__ = ("name", "attrs", "_state", "_annot", "_noted")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self._state = None
        self._annot = None
        self._noted = None

    def __call__(self, fn: Callable) -> Callable:
        import functools

        fresh = self._fresh

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with fresh():
                return fn(*args, **kwargs)

        return wrapped

    def _fresh(self) -> "span":
        """A new span like this one (the decorator opens one per call)."""
        return span(self.name, **self.attrs)

    def note(self, **attrs) -> None:
        """Attach ``attrs`` to this span's ``span_end`` event (no-op
        while the span records nothing)."""
        if self._state is not None:
            self._noted = {**(self._noted or {}), **attrs}

    def __enter__(self) -> "span":
        if _LEVEL < _EVENTS:
            return self
        stack = _span_stack()
        parent = stack[-1].id if stack else None
        sid = next(_SPAN_IDS)
        st = _SpanState(
            sid, self.name, parent, stack[0].root if stack else sid,
            time.monotonic(),
        )
        # record_event BEFORE pushing, so span_begin carries the PARENT id
        # in its own `span` field (the begin belongs to the enclosing span)
        record_event(
            "span_begin", id=st.id, name=self.name, parent=parent,
            root=st.root, **self.attrs,
        )
        stack.append(st)
        _ALL_STACKS[threading.get_ident()] = stack
        self._state = st
        if _LEVEL >= _TRACE:
            try:
                import jax

                self._annot = jax.profiler.TraceAnnotation(
                    ANNOTATION_PREFIX + self.name
                )
                self._annot.__enter__()
            except Exception:
                self._annot = None
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        st = self._state
        if st is None:
            return False
        self._state = None
        if self._annot is not None:
            try:
                self._annot.__exit__(exc_type, exc, tb)
            finally:
                self._annot = None
        stack = _span_stack()
        while stack and stack[-1].id != st.id:  # tolerate unbalanced exits
            stack.pop()
        if stack:
            stack.pop()
        if not stack:
            _ALL_STACKS.pop(threading.get_ident(), None)
        noted, self._noted = self._noted, None
        record_event(
            "span_end", id=st.id, name=st.name, parent=st.parent,
            root=st.root, dur_s=round(time.monotonic() - st.t0, 6),
            **(noted or {}),
            **({"status": "error", "error": exc_type.__name__}
               if exc_type is not None else {}),
        )
        return False


# host syncs: every place the library makes the host wait for the device
_SYNC = register_group("sync", {"count": 0, "by_site": {}})
SYNC_PREFIX = "sync:"


class sync(span):
    """A :class:`span` around a place where the host waits for the device:
    a scalar readback (``int(x)``, ``float(x)``, ``bool(x)``, ``.item()``,
    ``np.asarray``) or a fence (``block_until_ready``)::

        with telemetry.sync("kmeans.n_iter"):
            self._n_iter = int(n_iter)

    At ``counters`` level and above it counts the wait in the ``sync``
    group (``count`` and ``by_site[site]``: plain dict increments, no
    clock, no lock); from ``events`` up it is a span named
    ``sync:<site>``, so the flight recorder times the wait and, at
    ``trace``, the profiler shows it as ``ht:sync:<site>`` on the caller's
    line around the runtime's own readback event.  ``perf/span_reduce.py``
    counts these spans per call and gives them the device's idle time
    they cover.  The lint rule HT002 takes a sync inside this helper as a
    measured site."""

    __slots__ = ("site",)

    def __init__(self, site: str, **attrs):
        span.__init__(self, SYNC_PREFIX + site, **attrs)
        self.site = site

    def _fresh(self) -> "sync":
        return sync(self.site, **self.attrs)

    def __enter__(self) -> "sync":
        if _LEVEL >= _COUNTERS:
            _SYNC["count"] += 1
            by_site = _SYNC["by_site"]
            by_site[self.site] = by_site.get(self.site, 0) + 1
        return span.__enter__(self)


def module_name(name: str) -> Callable:
    """Decorator under ``jax.jit``: the XLA module is named ``jit_<name>``
    and not after the Python function, which keeps its own name for its
    callers.  A module's name is part of its key in JAX's persistent
    compilation cache, the ``jax.named_scope`` paths inside it are not
    (``jax_compilation_cache_include_metadata_in_key`` is off): a jitted
    function whose scopes change gets a new module name with them, or a
    warm cache hands back the executable with the old scopes (measured,
    PERF.md section 6, PR 25)::

        @partial(jax.jit, static_argnames=("k",))
        @telemetry.module_name("ht_lloyd_loop")
        def _lloyd_loop(x, centers, k, max_iter, tol): ...
    """

    def rename(fn: Callable) -> Callable:
        fn.__name__ = name
        return fn

    return rename


# --------------------------------------------------------------- cost ledger

_PROGRAMS: "OrderedDict[str, dict]" = OrderedDict()
_PROGRAMS_MAX = 1024


def fingerprint(parts) -> str:
    """Short stable digest of a canonical program description (the fusion
    engine passes its display-name instruction rendering)."""
    h = hashlib.sha1("|".join(str(p) for p in parts).encode())
    return h.hexdigest()[:12]


def record_program(
    fp: str,
    *,
    kind: str = "fused",
    n_roots: int = 1,
    ops: int = 0,
    flops: float = 0.0,
    hbm_bytes: float = 0.0,
    mesh: Optional[dict] = None,
    **extra,
) -> None:
    """Ledger one compiled program: its cost-model estimate (FLOPs + HBM
    bytes of mandatory traffic) attaches to the fingerprint so cb rows
    and dashboards derive achieved-vs-roofline from telemetry.  Called at
    fusion compile-cache misses and ring-matmul builds; re-recording an
    existing fingerprint refreshes the estimate without touching its hit
    count.  No-op at ``off`` level."""
    if _LEVEL < _COUNTERS:
        return
    got = _PROGRAMS.get(fp)
    hits = got["hits"] if got else 0
    compiles = (got["compiles"] if got else 0) + 1
    _PROGRAMS[fp] = {
        "fingerprint": fp,
        "kind": kind,
        "n_roots": int(n_roots),
        "ops": int(ops),
        "flops": float(flops),
        "hbm_bytes": float(hbm_bytes),
        "mesh": mesh,
        "compiles": compiles,
        "hits": hits,
        **extra,
    }
    _PROGRAMS.move_to_end(fp)
    while len(_PROGRAMS) > _PROGRAMS_MAX:
        old, _ = _PROGRAMS.popitem(last=False)
        _TIMINGS.pop(old, None)


def ensure_program(fp: Optional[str], **kwargs) -> None:
    """Ledger a program only if its fingerprint is new; count a hit
    otherwise.  The transport kernels call this per execution — their jit
    cache is internal (``lru_cache`` around the shard_map build), so
    compiles-vs-hits is approximated as first-sighting-vs-rest."""
    if fp is None or _LEVEL < _COUNTERS:
        return
    got = _PROGRAMS.get(fp)
    if got is None:
        record_program(fp, **kwargs)
    else:
        got["hits"] += 1


def program_hit(fp: Optional[str]) -> None:
    """Count one cache-served execution of a ledgered program."""
    if fp is None or _LEVEL < _COUNTERS:
        return
    got = _PROGRAMS.get(fp)
    if got is not None:
        got["hits"] += 1


def annotate_program(fp: Optional[str], **fields) -> None:
    """Merge extra fields into an existing ledger entry WITHOUT touching
    its compile/hit counts — the streaming engine's measured I/O axis
    (``io_stall_frac``, ``io_bytes``) lands here after each pass, where a
    ``record_program`` re-record would fake a compile.  No-op for unseen
    fingerprints (annotation never creates an entry: a program with no
    recorded cost model has nothing for roofline rows to attribute)."""
    if fp is None or _LEVEL < _COUNTERS:
        return
    got = _PROGRAMS.get(fp)
    if got is not None:
        got.update(fields)


def programs() -> List[dict]:
    """The per-program cost ledger, oldest entry first: one dict per
    compiled program with ``fingerprint``, ``kind``, ``n_roots``,
    ``ops``, ``flops``, ``hbm_bytes``, ``mesh``, ``compiles`` and
    ``hits`` — plus, for programs with measured executions, the wall
    clocks ``calls``, ``total_s``, ``min_s`` and ``p50_s``."""
    return [dict(v, **_timing_view(fp)) for fp, v in _PROGRAMS.items()]


def reset_programs() -> None:
    """Drop the cost ledger (tests/benchmarks)."""
    _PROGRAMS.clear()
    _TIMINGS.clear()


# ------------------------------------------------ measured program timing
# The ledger above is PREDICTED work; this side table holds MEASURED wall
# clocks from the live executable call sites (fusion hit path, transport
# tile loops, the ring matmul).  Kept out of the entry dicts so a
# re-record of a fingerprint (refreshed estimate) never loses history.

_TIMINGS: Dict[str, dict] = {}
_TIMING_SAMPLES = 64  # per-program reservoir backing the p50 estimate
_TICK = itertools.count()


_SAMPLE_EVERY = 16


def set_sample_every(n: int) -> int:
    """Set the ``counters``-level sampling period (every Nth executable
    call is wall-clocked; 16 unless set here).  Returns the previous
    period."""
    global _SAMPLE_EVERY
    prev = _SAMPLE_EVERY
    _SAMPLE_EVERY = max(int(n), 1)
    return prev


def timing_active() -> bool:
    """Whether THIS executable call should be wall-clocked: never below
    ``counters``, every call at ``events``, every Nth call at ``counters``
    and at ``trace`` — a sampled ``block_until_ready`` keeps the
    default-level tax under the cb ``telemetry_overhead`` bar while still
    accumulating honest steady-state samples, and a traced run stays the
    program that ``counters`` runs (no fence of telemetry's own per call)."""
    if _LEVEL < _COUNTERS:
        return False
    if _LEVEL == _EVENTS:
        return True
    return next(_TICK) % _SAMPLE_EVERY == 0


def record_timing(fp: Optional[str], dur_s: float) -> None:
    """Accumulate one measured wall clock under a program fingerprint
    (``calls``/``total_s``/``min_s`` plus a bounded sample reservoir for
    ``p50_s``).  External timers — e.g. a serving layer that measures its
    own request walls — may call this directly."""
    if fp is None or _LEVEL < _COUNTERS:
        return
    t = _TIMINGS.get(fp)
    if t is None:
        t = _TIMINGS[fp] = {
            "calls": 0,
            "total_s": 0.0,
            "min_s": float("inf"),
            "samples": deque(maxlen=_TIMING_SAMPLES),
        }
    t["calls"] += 1
    t["total_s"] += dur_s
    if dur_s < t["min_s"]:
        t["min_s"] = dur_s
    t["samples"].append(dur_s)


def record_peak(fp: Optional[str], peak_bytes, source: Optional[str] = None) -> None:
    """Fold one memory watermark reading into a program's measured view
    (max over samples).  ``source`` says how the number was read:
    ``device`` (a real ``memory_stats()['bytes_in_use']``) or ``ledger``
    (memtrack's tracked live bytes — the stats-less-backend fallback)."""
    if fp is None or peak_bytes is None or _LEVEL < _COUNTERS:
        return
    t = _TIMINGS.get(fp)
    if t is None:
        t = _TIMINGS[fp] = {
            "calls": 0,
            "total_s": 0.0,
            "min_s": float("inf"),
            "samples": deque(maxlen=_TIMING_SAMPLES),
        }
    if int(peak_bytes) > t.get("peak_bytes", -1):
        t["peak_bytes"] = int(peak_bytes)
        t["mem_source"] = source


def _timing_view(fp: str) -> dict:
    t = _TIMINGS.get(fp)
    if t is None:
        return {}
    out = {}
    if t["calls"]:
        ordered = sorted(t["samples"])
        out = {
            "calls": t["calls"],
            "total_s": round(t["total_s"], 6),
            "min_s": round(t["min_s"], 6),
            "p50_s": round(ordered[len(ordered) // 2], 6),
        }
    if "peak_bytes" in t:
        out["peak_bytes"] = t["peak_bytes"]
        out["mem_source"] = t.get("mem_source")
    return out


def timed_call(fp: Optional[str], fn: Callable, *args, observer=None):
    """Run ``fn(*args)`` (a jitted executable); when the sampling gate
    fires, block until the outputs are ready and accumulate the wall
    clock under ``fp``, sampling the memory watermark
    (:func:`memtrack.sample_bytes`) on entry and exit so the program
    gains a measured ``peak_bytes`` and the flight recorder a
    ``mem_sample`` trail (the Perfetto counter track).  With ``fp=None``
    or an idle gate this is a plain call — async dispatch is only
    serialized on sampled calls.  ``observer`` (optional callable taking
    the duration in seconds) also sees each SAMPLED wall clock — the
    hook the autotune plane uses to watch a sticky winner for
    degradation without adding its own ``block_until_ready``."""
    if fp is None or not timing_active():
        return fn(*args)
    from . import memtrack

    b0, src0 = memtrack.sample_bytes()
    if b0 is not None:
        record_event("mem_sample", fingerprint=fp, bytes_in_use=b0, source=src0)
    import jax

    t0 = time.perf_counter()
    out = fn(*args)
    # an asynchronous device error (OOM, runtime fault) surfaces here and
    # must propagate: a poisoned result is neither timed nor returned
    with sync("telemetry.timed_call"):
        jax.block_until_ready(out)
    dur = time.perf_counter() - t0
    record_timing(fp, dur)
    if observer is not None:
        try:
            observer(dur)
        except Exception:  # an observer must never break the computation
            pass
    b1, src1 = memtrack.sample_bytes()
    if b1 is not None:
        record_event("mem_sample", fingerprint=fp, bytes_in_use=b1, source=src1)
    peak = max((b for b in (b0, b1) if b is not None), default=None)
    record_peak(fp, peak, src1 or src0)
    return out


def roofline_report(top: Optional[int] = None, peaks: Optional[dict] = None) -> dict:
    """Measured-vs-peak attribution for every ledgered program with
    measured time: ``{"device", "peaks", "rows", "memory_bound_tail"}``,
    rows sorted by total measured time, each carrying achieved GFLOP/s
    and GB/s, the roofline fractions, and a compute/memory-bound verdict
    (``unknown-peak`` when the device peaks are unknown — see
    :mod:`heat_tpu.core.roofline` and ``HEAT_TPU_PEAKS``).  Rows whose
    fingerprint carries a program-audit finding (unmodeled collective,
    host transfer, dead donation) are marked ``audited_dirty`` — their
    measured time is not trustworthy attribution."""
    from . import roofline

    rep = roofline.report(programs(), top=top, peaks=peaks)
    try:
        from ..analysis import program_audit

        dirty = program_audit.dirty_fingerprints()
    except Exception:  # the analyzer must never break attribution
        dirty = set()
    if dirty:
        for row in rep.get("rows", ()):
            if row.get("fingerprint") in dirty:
                row["audited_dirty"] = True
    return rep


# ------------------------------------------------------------- memory axis
# The residency ledger lives in core/memtrack.py (the memory counterpart
# of roofline.py); these delegators surface its queries on the telemetry
# façade so callers need one import for both axes.

def live_buffers(top: Optional[int] = 10) -> List[dict]:
    """The live HBM residency ledger, largest buffer first — nbytes,
    dtype, shape, split, sharding, tag, pin state, and the user creation
    site (see :func:`heat_tpu.core.memtrack.live_buffers`)."""
    from . import memtrack

    return memtrack.live_buffers(top=top)


def leaks() -> List[dict]:
    """Suspected retained memory: orphaned fusion pins and buffers that
    outlived a ``memwatch()`` scope (see
    :func:`heat_tpu.core.memtrack.leaks`)."""
    from . import memtrack

    return memtrack.leaks()


def memwatch():
    """Retention-detection scope (see
    :func:`heat_tpu.core.memtrack.memwatch`)::

        with telemetry.memwatch() as w:
            ...
        assert not w.retained
    """
    from . import memtrack

    return memtrack.memwatch()


def autotune_report(top: Optional[int] = None) -> dict:
    """The tuning plane's table, rendered for dashboards: one row per
    (fingerprint, device kind) with per-arm steady-state times, the
    sticky winner, and where it came from (explored / cached / prior).
    Delegates to :func:`heat_tpu.core.autotune.report` — surfaced here
    so the ops story (``snapshot()`` / ``roofline_report()`` /
    ``autotune_report()``) lives behind one module."""
    from . import autotune

    return autotune.report(top=top)


def serving_report() -> dict:
    """Snapshot of the ``serving`` counter group (registered by
    :mod:`heat_tpu.serving` on import): accepted/rejected/batch/shed
    counters plus per-endpoint latency p50/p99.  Empty dict until the
    serving front door has been imported — surfaced here so the ops
    story (``snapshot()`` / ``roofline_report()`` / ``autotune_report()``
    / ``serving_report()``) lives behind one module."""
    if "serving" not in _GROUPS:
        return {}
    return snapshot_group("serving")


def router_report() -> dict:
    """Snapshot of the ``router`` counter group (registered by
    :mod:`heat_tpu.serving.router` on import): dispatch/spill/failover/
    retry counters, circuit-breaker transitions (ejections, half-opens,
    probes, recoveries) and rolling-swap outcomes.  Empty dict until the
    fleet router has been imported — surfaced here so the ops story
    (``snapshot()`` / ``serving_report()`` / ``router_report()``) lives
    behind one module."""
    if "router" not in _GROUPS:
        return {}
    return snapshot_group("router")


def reset() -> None:
    """Full telemetry reset: counters, events, and the ledger."""
    reset_all()
    clear_events()
    reset_programs()


# --------------------------------------------------------------- trace export

# event keys owned by the recorder envelope / span identity; everything
# else a span or event carries becomes Chrome-trace ``args``
_TRACE_ENVELOPE = frozenset(("seq", "ts", "kind", "span", "tid", "id",
                             "name", "parent"))


def export_trace(file=None) -> List[dict]:
    """Render the flight recorder as Chrome-trace JSON (the array-of-
    events form Perfetto's legacy JSON importer loads): one ``B``/``E``
    duration-event pair per span (one lane per recording thread, so
    nesting renders as a flame), and an ``i`` instant event for every
    non-span event — guard blames, OOM retries, fallbacks, dispatch
    decisions, stall heartbeats.  Timestamps are microseconds relative to
    the oldest recorded event.  Spans still open at export are closed at
    the last recorded timestamp with ``status: open``; a span whose begin
    was evicted from the ring is synthesized from the end event's
    recorded duration (its nesting may render approximate).  Returns the
    event list; ``file`` (path or handle) additionally writes it as
    JSON."""
    evs = events()
    pid = os.getpid()
    out: List[dict] = []
    lanes: Dict[int, int] = {}

    def lane(raw_tid) -> int:
        got = lanes.get(raw_tid)
        if got is None:
            got = lanes[raw_tid] = len(lanes)
            out.append({
                "ph": "M", "ts": 0, "pid": pid, "tid": got,
                "name": "thread_name", "cat": "__metadata",
                "args": {"name": f"thread-{got}"},
            })
        return got

    t0 = evs[0]["ts"] if evs else 0.0

    def us(ts: float) -> float:
        return round((ts - t0) * 1e6, 3)

    begun: Dict[int, dict] = {}
    for e in evs:
        tid = lane(e.get("tid", 0))
        args = {k: v for k, v in e.items() if k not in _TRACE_ENVELOPE}
        kind = e["kind"]
        if kind == "span_begin":
            begun[e["id"]] = e
            out.append({"ph": "B", "ts": us(e["ts"]), "pid": pid, "tid": tid,
                        "cat": "span", "name": e["name"], "args": args})
        elif kind == "span_end":
            if e["id"] not in begun:
                out.append({
                    "ph": "B",
                    "ts": us(e["ts"] - float(e.get("dur_s") or 0.0)),
                    "pid": pid, "tid": tid, "cat": "span", "name": e["name"],
                    "args": {"synthesized": "begin evicted from ring"},
                })
            begun.pop(e["id"], None)
            out.append({"ph": "E", "ts": us(e["ts"]), "pid": pid, "tid": tid,
                        "cat": "span", "name": e["name"], "args": args})
        elif kind == "mem_sample":
            # counter track: Perfetto renders the "C" series as a memory
            # timeline beside the span lanes (one track per recording lane)
            out.append({"ph": "C", "ts": us(e["ts"]), "pid": pid, "tid": tid,
                        "cat": "memory", "name": "memory",
                        "args": {"bytes_in_use": e.get("bytes_in_use", 0)}})
        else:
            out.append({"ph": "i", "s": "t", "ts": us(e["ts"]), "pid": pid,
                        "tid": tid, "cat": "event", "name": kind,
                        "args": args})
    if evs:
        t_last = us(evs[-1]["ts"])
        # close innermost-first so each lane's B/E stack stays balanced
        for e in reversed(list(begun.values())):
            out.append({"ph": "E", "ts": t_last, "pid": pid,
                        "tid": lane(e.get("tid", 0)), "cat": "span",
                        "name": e["name"], "args": {"status": "open"}})
    if isinstance(file, (str, os.PathLike)):
        with open(file, "w") as fh:
            json.dump(out, fh, indent=1, default=repr)
    elif file is not None:
        json.dump(out, file, indent=1, default=repr)
    return out


# The recorder/ledger's own health gauges, registered as a group so they
# ride snapshot()/export_prometheus() like any subsystem group (the
# `events_dropped` count is the ring's eviction pressure — a poller
# seeing it grow between scrapes knows its `since=` cursor lost data).
register_group(
    "telemetry",
    {},
    extra=lambda: {
        "level": level(),
        "capacity": _RING.maxlen,
        "events": len(_RING),
        "events_dropped": _DROPPED[0],
        "programs": len(_PROGRAMS),
    },
)
