"""Benchmark monitoring harness (SURVEY.md §5).

The reference meters its CI benchmarks with the external ``perun`` energy/
runtime monitor (`benchmarks/cb/cluster.py:2-5`, extras ``cb: perun>=0.2.0``).
The TPU rebuild ships the equivalent in-tree: an ``@monitor()`` decorator that
records wall time and device memory per call, can capture a ``jax.profiler``
trace (Perfetto-viewable) when asked, and emits one JSON line per measurement
— the same publish-to-dashboard shape as the reference's perun pipeline.
"""

from __future__ import annotations

import contextlib
import functools
import json
import sys
import time
from typing import Any, Callable, Dict, List, Optional

import jax

from ..core import memtrack, telemetry

__all__ = ["monitor", "measurements", "record", "report", "reset", "profile_trace"]

_MEASUREMENTS: List[Dict[str, Any]] = []


def _device_memory() -> Optional[int]:
    """Max bytes in use across the LOCAL devices, where the backend
    exposes it (TPU does; CPU returns None) — the unified
    :func:`memtrack.device_bytes_in_use` reader."""
    _per, worst = memtrack.device_bytes_in_use()
    return worst


def monitor(name: Optional[str] = None, emit: bool = True) -> Callable:
    """Decorator: measure each call's wall time + device memory delta.

    Mirrors perun's ``@monitor()`` usage in the reference's benchmark suite;
    one JSON line per call goes to stderr (so stdout stays machine-parsable
    for harnesses like bench.py) and into :func:`measurements`."""

    def deco(fn: Callable) -> Callable:
        label = name or fn.__name__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            mem0 = _device_memory()
            t0 = time.perf_counter()
            try:
                out = fn(*args, **kwargs)
            except Exception as err:
                # the failed call IS a measurement: record how long it ran
                # and that it died, then re-raise — a crash mid-suite must
                # not erase the row (it used to vanish entirely)
                wall = time.perf_counter() - t0
                entry = {
                    "name": label, "wall_s": round(wall, 6),
                    "status": "error", "error": type(err).__name__,
                }
                _MEASUREMENTS.append(entry)
                telemetry.record_event(
                    "measurement", name=label, wall_s=entry["wall_s"],
                    status="error", error=type(err).__name__,
                )
                if emit:
                    print(json.dumps(entry), file=sys.stderr)
                raise
            # drain async dispatch so the clock covers the device work;
            # an asynchronous device error surfaces here and propagates
            with telemetry.sync("monitor.drain"):  # the measurement barrier
                jax.block_until_ready(out)
            wall = time.perf_counter() - t0
            mem1 = _device_memory()
            entry = {"name": label, "wall_s": round(wall, 6)}
            if mem1 is not None:
                entry["device_bytes_in_use"] = mem1
                if mem0 is not None:
                    entry["device_bytes_delta"] = mem1 - mem0
            _MEASUREMENTS.append(entry)
            telemetry.record_event(
                "measurement", name=label, wall_s=entry["wall_s"],
            )
            if emit:
                print(json.dumps(entry), file=sys.stderr)
            return out

        return wrapper

    return deco


def record(name: str, wall_s: float, emit: bool = True, **fields) -> None:
    """Record a measurement whose timing was computed externally — e.g. a
    chain-delta slope where the harness timed two rep counts and took the
    difference so a fixed readback cost cancels (bench.py's method).
    ``fields`` should say how (method=, k1=, k2=, ...) so the artifact is
    self-describing."""
    entry = {"name": name, "wall_s": round(float(wall_s), 6), **fields}
    mem = _device_memory()
    if mem is not None:
        entry["device_bytes_in_use"] = mem
    _MEASUREMENTS.append(entry)
    if emit:
        print(json.dumps(entry), file=sys.stderr)


def measurements() -> List[Dict[str, Any]]:
    """All measurements recorded since the last :func:`reset`."""
    return list(_MEASUREMENTS)


def annotate_last(**fields) -> None:
    """Attach extra fields to the most recent measurement (e.g. the
    iteration count a workload actually ran, for honest derived rates)."""
    if not _MEASUREMENTS:
        raise RuntimeError("no measurement to annotate")
    _MEASUREMENTS[-1].update(fields)


def report(file=None) -> None:
    """Write every measurement as one JSON line (default: stderr)."""
    out = file or sys.stderr
    for entry in _MEASUREMENTS:
        print(json.dumps(entry), file=out)


def reset() -> None:
    _MEASUREMENTS.clear()


@contextlib.contextmanager
def profile_trace(log_dir: str):
    """Capture a ``jax.profiler`` trace of the enclosed block into
    ``log_dir`` (open with Perfetto / TensorBoard)."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()
