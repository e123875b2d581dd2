"""From a profiler trace to the program's own spans and device scopes.

``trace_reduce`` looks at the program from outside: the device's busy union,
the compiler's operation names, the runtime's host events.  This reducer reads
what the program says about itself (``heat_tpu/core/telemetry.py``, PR 25):

- **program spans**: host events named ``ht:<span>`` on the calling thread
  (``telemetry.span`` in ``trace`` mode), among them the **sync spans**
  ``ht:sync:<site>`` around every place where the host waits for the device;
- **device scopes**: the ``jax.named_scope`` path that each event of a device's
  ``XLA Ops`` line carries in its ``tf_op`` stat (``jit(_lloyd_loop)/…/
  ht.kmeans.lloyd/while/body/…/ht.kmeans.assign/ht.cdist/dot_general``).

``load_xplane`` is ``trace_reduce.load_xplane`` with that path kept as a fourth
field of a device operation's event.  ``reduce`` gives, clipped to the window
span ``trace_reduce.WINDOW``: per program span name its count, total seconds
and **self** seconds (duration minus what its child program spans cover); the
fullest device's idle seconds by **innermost program span** (``trace_reduce``'s
``idle_gaps`` pick the innermost host event of any kind, which is the
runtime's ``np.asarray(jax.Array)``), split exactly where a gap crosses a span's
edge, so that the parts add up to window minus busy; the largest single idle
stretch per span (a call that comes back late from a readback shows here); and
per scope the union of the fullest device's operation intervals under it (a
union, because a ``while`` operation's interval contains its body's).

``for_run(run)`` is what the per-layer readers call (``perf/layer_metrics/
host_syncs_per_call.py`` and the four beside it): the reduction of the newest
trace under ``perf/out/trace/``, or None when the run has no device trace
(``--rehearse-cpu``), when the file is not this run's (no window span, or
another number of ``perf.call`` spans than the run's own reduction counted), or
when the program under test writes no ``ht:`` span at all (a commit before
PR 25): the readers then return None and the line leaves their metrics out.

``python3 perf/span_report.py <trace dir>`` prints all of it as tables.
"""

from __future__ import annotations

import glob
import os

from perf import trace_reduce
from perf.trace_reduce import CALL, OPS_LINE, WINDOW

PERF_DIR = os.path.dirname(os.path.abspath(__file__))
PREFIX = "ht:"          # telemetry.ANNOTATION_PREFIX
SYNC = PREFIX + "sync:"  # telemetry.SYNC_PREFIX under it
SCOPE = "ht."           # a jax.named_scope of the library
FUSED = "ht.fused"      # its next path component is the fused op's name
SCOPE_STAT = "tf_op"    # the stat of a device event that holds the scope path
OUTSIDE = "(no program span)"


def newest_xplane(root: str | None = None) -> str | None:
    """The newest ``.xplane.pb`` under ``perf/out/trace/*/`` (a run writes one,
    into its own cell's directory, just before the readers are called)."""
    root = root or os.path.join(PERF_DIR, "out", "trace")
    found = glob.glob(os.path.join(root, "*", "plugins", "profile", "*", "*.xplane.pb"))
    return max(found, key=os.path.getmtime) if found else None


def _varint(buf, i: int):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message's top level: an int
    for a varint, the bytes for a length-delimited or a fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield tag >> 3, value


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _map_entries(entries: list):
    """A protobuf map's ``(key, value message)`` pairs."""
    for entry in entries:
        pair = dict(_fields(entry))
        yield pair.get(1, 0), pair.get(2, b"")


def scope_paths(path: str) -> dict:
    """Per device plane of an ``.xplane.pb``, for each event of its operation
    line in the file's order, ``(operation's name, scope path)``.

    The path is the ``tf_op`` stat of the event's **metadata** (XLA's
    ``op_name``: the ``jax.named_scope`` stack and the primitive), which
    ``jax.profiler.ProfileData`` does not hand out: it gives an event's own
    stats only.  So the file's few message types are read here from the wire
    format (tsl/profiler/protobuf/xplane.proto: XSpace.planes = 1; XPlane.name
    = 2, lines = 3, event_metadata = 4, stat_metadata = 5; XLine.name = 2,
    events = 4; XEvent.metadata_id = 1; XEventMetadata.name = 2, stats = 5;
    XStat.metadata_id = 1, str_value = 5, ref_value = 7; XStatMetadata.name =
    2), and nothing but JAX is needed to read a trace."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out = {}
    for number, plane in _fields(space):
        if number != 1:
            continue
        name, lines, events_md, stats_md = "", [], [], []
        for num, value in _fields(plane):
            if num == 2:
                name = _text(value)
            elif num == 3:
                lines.append(value)
            elif num == 4:
                events_md.append(value)
            elif num == 5:
                stats_md.append(value)
        if not name.startswith("/device:"):
            continue
        stat_names = {
            key: _text(dict(_fields(md)).get(2, b"")) for key, md in _map_entries(stats_md)
        }
        wanted = {key for key, stat in stat_names.items() if stat == SCOPE_STAT}
        described = {}
        for key, md in _map_entries(events_md):
            op, scope = "", ""
            for num, value in _fields(md):
                if num == 2:
                    op = _text(value)
                elif num == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) in wanted:
                        scope = _text(stat[5]) if 5 in stat else stat_names.get(stat.get(7), "")
            described[key] = (op, scope)
        for line in lines:
            parts = list(_fields(line))
            if any(num == 2 and _text(value) == OPS_LINE for num, value in parts):
                out[name] = [
                    described.get(dict(_fields(value)).get(1, 0), ("", ""))
                    for num, value in parts if num == 4
                ]
    return out


def load_xplane(path: str) -> dict:
    """The trace as plain data, as ``trace_reduce.load_xplane`` gives it; an
    event of a device's operation line gains a fourth field, its scope path
    (empty where the file gives none, or where the file's events and
    ``ProfileData``'s do not pair up one to one by name)."""
    trace = trace_reduce.load_xplane(path)
    try:
        paths = scope_paths(path)
    except (ValueError, IndexError, KeyError):
        paths = {}
    for plane in trace["planes"]:
        described = paths.get(plane["name"])
        for line in plane["lines"]:
            if line["name"] != OPS_LINE or not described:
                continue
            events = line["events"]
            if len(described) != len(events) or any(
                op != ev[0] for (op, _), ev in zip(described, events)
            ):
                continue
            for (_, scope), ev in zip(described, events):
                # "op_name:op_type", the type empty in JAX's programs
                ev.append(scope.rsplit(":", 1)[0])
    return trace


def _clipped(events: list, lo: float, hi: float) -> list:
    """``(start, end, event)`` of the events that overlap ``[lo, hi]``."""
    out = []
    for event in events:
        s, e = max(event[1], lo), min(event[1] + event[2], hi)
        if e > s:
            out.append((s, e, event))
    return out


def _total(intervals: list) -> float:
    return sum(e - s for s, e in trace_reduce._union([[s, e] for s, e in intervals]))


def _nest(spans: list, lo: float, hi: float):
    """From one thread's program spans ``(start, end, name)``, properly
    nested, to ``(table, segments)``: per name ``count``, ``total`` and
    ``self`` nanoseconds, and the window cut into ``(start, end, name)``
    pieces by innermost span (``OUTSIDE`` where none is open)."""
    table, segments, stack = {}, [], []
    edge = lo

    def advance(to):
        nonlocal edge
        if to > edge:
            name = stack[-1][2] if stack else OUTSIDE
            segments.append((edge, to, name))
            if stack:
                table[name]["self"] += to - edge
            edge = to

    for s, e, name in sorted(spans, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][1] <= s:
            advance(stack[-1][1])
            stack.pop()
        advance(s)
        row = table.setdefault(name, {"count": 0, "total": 0.0, "self": 0.0})
        row["count"] += 1
        row["total"] += e - s
        # a child may not outlast its parent: the clocks of two events'
        # ends can differ by a nanosecond
        stack.append((s, min(e, stack[-1][1]) if stack else e, name))
    while stack:
        advance(stack[-1][1])
        stack.pop()
    advance(hi)
    return table, segments


def scopes_of(path: str) -> list:
    """The library's scopes on one operation's path, outermost first:
    components that start with ``ht.``; ``ht.fused`` brings the fused op's
    name with it (``ht.fused/euclid_cdist``)."""
    parts = path.split("/")
    out = []
    for i, part in enumerate(parts):
        if not part.startswith(SCOPE):
            continue
        out.append(part)
        if part == FUSED and i + 1 < len(parts):
            out.append(part + "/" + parts[i + 1])
    return out


def reduce(trace: dict) -> dict:
    """Numbers of the traced window; seconds unless named otherwise.

    ``window_s``, ``calls`` (``perf.call`` spans), ``spans`` (``ht:`` name ->
    ``count``, ``total_s``, ``self_s``, ``idle_s``, ``max_gap_s``),
    ``syncs`` (``ht:sync:*`` spans closed inside the window), ``busy_s`` and
    ``idle_s`` of the fullest device, ``idle_outside_s`` (idle while no
    program span was open; with the spans' ``idle_s`` it adds up to
    ``idle_s``), ``sync_idle_s`` (idle while the innermost program span was a
    sync span), ``scopes`` (scope -> union of device seconds; empty where the
    operations carry no path) and ``program_spans`` (how many ``ht:`` events
    the calling thread has in the window)."""
    lo, hi, caller = trace_reduce._window(trace)
    host = _clipped(caller["events"], lo, hi)
    calls = sum(1 for _, _, ev in host if ev[0] == CALL)
    program = [(s, e, ev[0]) for s, e, ev in host if ev[0].startswith(PREFIX)]
    syncs = sum(
        1 for ev in caller["events"]
        if ev[0].startswith(SYNC) and lo < ev[1] + ev[2] <= hi
    )
    table, segments = _nest(program, lo, hi)

    fullest, busy = None, []
    for plane in trace["planes"]:
        if not plane["name"].startswith("/device:"):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = _clipped(lines.get(OPS_LINE) or lines.get(trace_reduce.MODULES_LINE, []), lo, hi)
        union = trace_reduce._union([[s, e] for s, e, _ in ops])
        if fullest is None or sum(e - s for s, e in union) > sum(e - s for s, e in busy):
            fullest, busy = ops, union
    fullest = fullest or []  # a CPU rehearsal's trace has no device plane: all idle

    # idle stretches of the fullest device, cut at the segments' edges
    gaps, edge = [], lo
    for s, e in busy + [[hi, hi]]:
        if s > edge:
            gaps.append((edge, s))
        edge = max(edge, e)
    idle = {}
    i = 0
    for gs, ge in gaps:
        while i < len(segments) and segments[i][1] <= gs:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < ge:
            s, e = max(gs, segments[j][0]), min(ge, segments[j][1])
            row = idle.setdefault(segments[j][2], [0.0, 0.0])
            row[0] += e - s
            row[1] = max(row[1], e - s)
            j += 1

    by_scope = {}
    for s, e, ev in fullest:
        for scope in scopes_of(ev[3]) if len(ev) > 3 else ():
            by_scope.setdefault(scope, []).append((s, e))

    spans = {
        name: {
            "count": row["count"],
            "total_s": row["total"] * 1e-9,
            "self_s": row["self"] * 1e-9,
            "idle_s": idle.get(name, (0.0, 0.0))[0] * 1e-9,
            "max_gap_s": idle.get(name, (0.0, 0.0))[1] * 1e-9,
        }
        for name, row in table.items()
    }
    busy_s = sum(e - s for s, e in busy) * 1e-9
    return {
        "window_s": (hi - lo) * 1e-9,
        "calls": calls,
        "program_spans": len(program),
        "spans": spans,
        "syncs": syncs,
        "busy_s": busy_s,
        "idle_s": sum(e - s for s, e in gaps) * 1e-9,
        "idle_outside_s": idle.get(OUTSIDE, (0.0, 0.0))[0] * 1e-9,
        "max_gap_outside_s": idle.get(OUTSIDE, (0.0, 0.0))[1] * 1e-9,
        "sync_idle_s": sum(v["idle_s"] for k, v in spans.items() if k.startswith(SYNC)),
        "scopes": {k: _total(v) * 1e-9 for k, v in by_scope.items()},
    }


def for_run(run: dict):
    """The reduction for the readers, or None (see the module's docstring).
    Reduced once a run: the result is kept in ``run`` itself."""
    if "span_reduce" not in run:
        run["span_reduce"] = _for_trace(run.get("trace"))
    return run["span_reduce"]


def _for_trace(outside):
    """``reduce`` of the newest trace if it is the one ``outside`` (the run's
    ``trace_reduce.reduce``) was made from and holds program spans."""
    path = newest_xplane() if outside else None
    if not path:
        return None
    try:
        got = reduce(load_xplane(path))
    except (ValueError, OSError):
        return None
    if got["calls"] != outside.get("calls") or not got["program_spans"]:
        return None
    return got


def span_self_ms_per_call(run: dict, names: tuple):
    """Self milliseconds per call of the program spans ``names`` together; 0.0
    where the traced calls never entered them; None without a reduction."""
    got = for_run(run)
    if not got or not got["calls"]:
        return None
    total = sum(got["spans"].get(PREFIX + n, {}).get("self_s", 0.0) for n in names)
    return total / got["calls"] * 1e3
