"""From a profiler trace to numbers.  The only reader of ``.xplane.pb`` here.

``load_xplane`` turns JAX's trace file into plain data (planes, lines, events
as ``[name, start_ns, duration_ns]``) and ``reduce`` turns that into what the
per-layer metrics read: per device the union of the intervals in which an
operation ran, time per operation, program executions, collective time; and
the idle gaps of the fullest device, each named by the innermost host span
(``jax.profiler.TraceAnnotation``, which the program's telemetry spans also
enter in ``trace`` mode) that the calling thread was in.

Everything is clipped to the span named ``WINDOW``, which ``perf/run.py``
holds open around the traced calls, so host clock and trace clock need no
mapping.  ``perf/tests`` checks ``reduce`` on a small recorded trace kept as
that plain data.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

WINDOW = "perf.window"
CALL = "perf.call"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# the chip's trace names an asynchronous collective "async-collective-start"
# and "-done" whatever its kind (the TSQR all-gather reads so; PR 24)
COLLECTIVE = re.compile(
    r"^%?(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|ragged-all-to-all|async-collective)"
)
# how many spans back from a point the innermost one is looked for
_LOOKBACK = 256


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_xplane(path: str) -> dict:
    """The trace as plain data.  Device planes keep their operation and
    program lines; host planes keep every thread that has named spans."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [
                [ev.name, float(ev.start_ns), float(ev.duration_ns)]
                for ev in line.events
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals: list) -> list:
    """Sorted, merged ``[start, end]`` intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def _clip(events: list, lo: float, hi: float) -> list:
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e))
    return out


def _window(trace: dict):
    """The ``WINDOW`` span and the host line (thread) that holds it."""
    for plane in trace["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == WINDOW:
                    return start, start + dur, line
    raise ValueError(f"the trace holds no {WINDOW!r} span")


def _innermost(starts: list, spans: list, at: float) -> str:
    """Name of the shortest span of one thread that contains ``at``."""
    i = bisect.bisect_right(starts, at) - 1
    best, best_len = None, None
    for j in range(i, max(-1, i - _LOOKBACK), -1):
        name, s, e = spans[j]
        if s <= at < e and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best or "(no span)"


def reduce(trace: dict) -> dict:
    """Numbers of the traced window; seconds unless named otherwise.

    ``devices``: one entry per device plane with ``busy_s`` (union of the
    operation line, or of the program line where a plane has no operations),
    ``programs`` (executions on the program line), ``collective_s`` and
    ``ops`` (name -> seconds).  ``fullest`` indexes the busiest device.
    ``idle_gaps`` sums the fullest device's idle time by host span name, and
    ``calls`` counts the ``CALL`` spans inside the window.
    """
    lo, hi, caller = _window(trace)
    window_s = (hi - lo) * 1e-9
    devices = []
    for plane in trace["planes"]:
        if not plane["name"].startswith("/device:"):
            continue
        lines = {ln["name"]: _clip(ln["events"], lo, hi) for ln in plane["lines"]}
        ops = lines.get(OPS_LINE, [])
        modules = lines.get(MODULES_LINE, [])
        busy = _union([[s, e] for _, s, e in (ops or modules)])
        per_op, collective = {}, 0.0
        for name, s, e in ops:
            per_op[name] = per_op.get(name, 0.0) + (e - s) * 1e-9
            if COLLECTIVE.match(name):
                collective += (e - s) * 1e-9
        devices.append({
            "name": plane["name"],
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "busy": busy,
            "programs": len(modules),
            "collective_s": collective,
            "ops": per_op,
        })
    if not devices:
        raise ValueError("the trace holds no device plane")
    fullest = max(range(len(devices)), key=lambda i: devices[i]["busy_s"])

    spans = sorted(
        ((n, s, e) for n, s, e in _clip(caller["events"], lo, hi) if n != WINDOW),
        key=lambda t: t[1],
    )
    starts = [s for _, s, _ in spans]
    gaps, edge = {}, lo
    for s, e in devices[fullest]["busy"] + [[hi, hi]]:
        if s > edge:
            name = _innermost(starts, spans, (edge + s) / 2)
            gaps[name] = gaps.get(name, 0.0) + (s - edge) * 1e-9
        edge = max(edge, e)
    for dev in devices:
        del dev["busy"]
    return {
        "window_s": window_s,
        "devices": devices,
        "fullest": fullest,
        "idle_gaps": gaps,
        "calls": sum(1 for n, _, _ in spans if n == CALL),
    }


def top(table: dict, n: int = 10) -> list:
    """The ``n`` largest entries of a name -> seconds table, as pairs."""
    return [[k, v] for k, v in sorted(table.items(), key=lambda kv: -kv[1])[:n]]
