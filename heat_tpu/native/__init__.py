"""Native (C++) host runtime: I/O engine, prefetch pipeline, host RNG.

The reference's native substrate is external (ATen kernels, the MPI library
— SURVEY.md §2, L0); its in-repo code is pure Python.  Here the *device*
native path is XLA/Pallas, and this package is the **host** native path —
the pieces that sit between storage and ``jax.device_put`` where Python
would serialize: byte-range CSV parsing (reference: heat/core/io.py:713),
threaded slab prefetch (reference: heat/utils/data/partial_dataset.py:32),
and a Threefry counter stream for host-side shuffles (reference:
heat/core/random.py:876-1053).

The shared library builds lazily with g++ on first import and caches next
to the sources; every consumer falls back to pure Python/NumPy when the
toolchain or build is unavailable, so the framework never hard-requires it.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

__all__ = [
    "available",
    "lib",
    "csv_parse",
    "csv_parse_range",
    "csv_row_bounds",
    "read_bytes",
    "threefry_fill",
    "threefry_permutation",
    "PrefetchPipeline",
]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "src")
_SO = os.path.join(_HERE, "_heat_native.so")
_SOURCES = ("io_engine.cpp", "prefetch.cpp", "threefry.cpp")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_build_failed = False


def _needs_build() -> bool:
    if not os.path.exists(_SO):
        return True
    try:
        so_mtime = os.path.getmtime(_SO)
        return any(
            os.path.getmtime(os.path.join(_SRC, s)) > so_mtime for s in _SOURCES
        )
    except OSError:
        # sources stripped from the install; use the prebuilt .so as-is
        return False


def _build() -> bool:
    # generic code only, no host-CPU tuning flag: a built tree may be
    # copied to a machine with another CPU
    cmd = [
        "g++", "-O3", "-shared", "-fPIC", "-std=c++17",
        "-pthread", "-o", _SO,
    ] + [os.path.join(_SRC, s) for s in _SOURCES]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.TimeoutExpired):
        return False
    return proc.returncode == 0 and os.path.exists(_SO)


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _build_failed
    with _lock:
        if _lib is not None:
            return _lib
        if _build_failed:
            return None
        if _needs_build() and not _build():
            _build_failed = True
            return None
        try:
            lib = ctypes.CDLL(_SO)
        except OSError:
            _build_failed = True
            return None

        lib.ht_file_size.restype = ctypes.c_long
        lib.ht_file_size.argtypes = [ctypes.c_char_p]
        lib.ht_csv_parse.restype = ctypes.c_long
        lib.ht_csv_parse.argtypes = [
            ctypes.c_char_p, ctypes.c_long, ctypes.c_char, ctypes.c_int,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
            ctypes.POINTER(ctypes.c_long),
        ]
        lib.ht_csv_parse_range.restype = ctypes.c_long
        lib.ht_csv_parse_range.argtypes = [
            ctypes.c_char_p, ctypes.c_long, ctypes.c_long, ctypes.c_char,
            ctypes.c_int,
            ctypes.POINTER(ctypes.POINTER(ctypes.c_float)),
            ctypes.POINTER(ctypes.c_long),
        ]
        lib.ht_csv_row_bounds.restype = ctypes.c_long
        lib.ht_csv_row_bounds.argtypes = [
            ctypes.c_char_p, ctypes.c_long, ctypes.c_long,
            ctypes.POINTER(ctypes.c_long), ctypes.POINTER(ctypes.c_long),
        ]
        lib.ht_read_bytes.restype = ctypes.c_long
        lib.ht_read_bytes.argtypes = [
            ctypes.c_char_p, ctypes.c_long, ctypes.c_long, ctypes.c_void_p,
            ctypes.c_int,
        ]
        lib.ht_free.restype = None
        lib.ht_free.argtypes = [ctypes.c_void_p]
        lib.ht_prefetch_open.restype = ctypes.c_void_p
        lib.ht_prefetch_open.argtypes = [
            ctypes.c_char_p, ctypes.c_long, ctypes.c_long, ctypes.c_long,
            ctypes.c_int,
        ]
        lib.ht_prefetch_next.restype = ctypes.c_long
        lib.ht_prefetch_next.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_long,
        ]
        lib.ht_prefetch_close.restype = None
        lib.ht_prefetch_close.argtypes = [ctypes.c_void_p]
        lib.ht_threefry_fill_u64.restype = None
        lib.ht_threefry_fill_u64.argtypes = [
            ctypes.c_uint64, ctypes.c_uint64, ctypes.c_long, ctypes.c_void_p,
            ctypes.c_int,
        ]
        lib.ht_threefry_permutation.restype = None
        lib.ht_threefry_permutation.argtypes = [
            ctypes.c_uint64, ctypes.c_long, ctypes.c_void_p,
        ]
        _lib = lib
        return _lib


def available() -> bool:
    """True when the native library is built and loadable."""
    return _load() is not None


def lib() -> ctypes.CDLL:
    l = _load()
    if l is None:
        raise RuntimeError("heat_tpu native library unavailable")
    return l


_DEFAULT_THREADS = min(os.cpu_count() or 1, 16)


def csv_parse(path: str, header_lines: int = 0, sep: str = ",") -> Optional[np.ndarray]:
    """Parse a CSV into a float32 (rows, cols) array with the native
    multi-threaded byte-range parser.  None when native is unavailable or
    the file is ragged (caller falls back to NumPy)."""
    l = _load()
    if l is None:
        return None
    out = ctypes.POINTER(ctypes.c_float)()
    rows = ctypes.c_long()
    n = l.ht_csv_parse(
        path.encode(), header_lines, sep.encode()[:1], _DEFAULT_THREADS,
        ctypes.byref(out), ctypes.byref(rows),
    )
    if n < 0:
        # -1: I/O error; -2: ragged rows — NumPy fallback produces the
        # user-facing error either way
        return None
    try:
        if rows.value == 0:
            return None
        arr = np.ctypeslib.as_array(out, shape=(n,)).copy()
    finally:
        l.ht_free(out)
    return arr.reshape(rows.value, n // rows.value)


def csv_row_bounds(path: str, header_lines: int, nshards: int):
    """Shard row-boundaries for an even ``ceil(rows/nshards)`` partition of
    the file's data rows (the mesh chunk rule): returns
    ``(bounds, nrows)`` where ``bounds[k]:bounds[k+1]`` is shard ``k``'s
    line-aligned byte range.  None when native is unavailable or the scan
    fails."""
    l = _load()
    if l is None:
        return None
    bounds = (ctypes.c_long * (nshards + 1))()
    nrows = ctypes.c_long()
    ret = l.ht_csv_row_bounds(
        path.encode(), header_lines, nshards, bounds, ctypes.byref(nrows)
    )
    if ret != 0:
        return None
    return list(bounds), nrows.value


def csv_parse_range(
    path: str, start: int, end: int, sep: str = ","
) -> Optional[np.ndarray]:
    """Parse the line-aligned byte range [start, end) into a float32
    (rows, cols) array.  None on error/ragged rows; shape (0, 0) array for
    an empty range."""
    l = _load()
    if l is None:
        return None
    if end <= start:
        return np.empty((0, 0), dtype=np.float32)
    out = ctypes.POINTER(ctypes.c_float)()
    rows = ctypes.c_long()
    n = l.ht_csv_parse_range(
        path.encode(), start, end, sep.encode()[:1], _DEFAULT_THREADS,
        ctypes.byref(out), ctypes.byref(rows),
    )
    if n < 0:
        return None
    if rows.value == 0:
        return np.empty((0, 0), dtype=np.float32)
    try:
        arr = np.ctypeslib.as_array(out, shape=(n,)).copy()
    finally:
        l.ht_free(out)
    return arr.reshape(rows.value, n // rows.value)


def read_bytes(path: str, offset: int, size: int) -> Optional[np.ndarray]:
    """Threaded pread of ``size`` bytes at ``offset`` into a uint8 array."""
    l = _load()
    if l is None:
        return None
    buf = np.empty(size, dtype=np.uint8)
    got = l.ht_read_bytes(
        path.encode(), offset, size, buf.ctypes.data_as(ctypes.c_void_p),
        _DEFAULT_THREADS,
    )
    if got != size:
        return None
    return buf


def threefry_fill(
    seed: int, counter: int, n: int, nthreads: Optional[int] = None
) -> Optional[np.ndarray]:
    """n uint64s of the (seed, counter) Threefry-2x64 stream.

    The stream is a pure function of (seed, counter, index) — identical for
    any ``nthreads`` (the reference's any-rank-count reproducibility
    invariant, heat/core/random.py:55-201)."""
    l = _load()
    if l is None:
        return None
    out = np.empty(n, dtype=np.uint64)
    l.ht_threefry_fill_u64(
        seed & (2**64 - 1), counter & (2**64 - 1), n,
        out.ctypes.data_as(ctypes.c_void_p),
        _DEFAULT_THREADS if nthreads is None else nthreads,
    )
    return out


def threefry_permutation(seed: int, n: int) -> Optional[np.ndarray]:
    """Deterministic permutation of [0, n) from the seeded stream."""
    l = _load()
    if l is None:
        return None
    out = np.empty(n, dtype=np.int64)
    l.ht_threefry_permutation(seed & (2**64 - 1), n, out.ctypes.data_as(ctypes.c_void_p))
    return out


class PrefetchPipeline:
    """Iterator over byte slabs of a file, read ahead by a C++ thread.

    >>> for slab in PrefetchPipeline(path, slab_bytes=8 << 20):
    ...     device_buf = jax.device_put(slab.view(np.float32), sharding)
    """

    def __init__(
        self,
        path: str,
        offset: int = 0,
        nbytes: int = -1,
        slab_bytes: int = 8 << 20,
        depth: int = 2,
    ):
        l = _load()
        if l is None:
            raise RuntimeError("heat_tpu native library unavailable")
        self._lib = l
        self._slab_bytes = slab_bytes
        self._handle = l.ht_prefetch_open(path.encode(), offset, nbytes, slab_bytes, depth)
        if not self._handle:
            raise OSError(f"cannot open {path!r}")

    def __iter__(self):
        return self

    def __next__(self) -> np.ndarray:
        if self._handle is None:
            raise StopIteration
        buf = np.empty(self._slab_bytes, dtype=np.uint8)
        got = self._lib.ht_prefetch_next(
            self._handle, buf.ctypes.data_as(ctypes.c_void_p), self._slab_bytes
        )
        if got == 0:
            self.close()
            raise StopIteration
        if got < 0:
            self.close()
            raise OSError("prefetch reader failed")
        return buf[:got]

    def close(self) -> None:
        if self._handle is not None:
            self._lib.ht_prefetch_close(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
