"""Out-of-core streaming engine: double-buffered host→device slabs under a
measured HBM residency budget (round 22; ROADMAP frontier assumption 3,
reference: heat/utils/data/partial_dataset.py's prefetch-thread model).

The transport engine already bounds *staging* at O(tile); this module
applies the same discipline to *residency*, so an array larger than device
memory becomes a measured, overlapped streaming schedule instead of a
crash.  Three layers:

**Chunk sources.**  :func:`open_source` wraps HDF5 datasets, NetCDF
variables, ``.npy`` memory maps, and in-memory arrays behind one tiny
handle (``shape`` / ``np_dtype`` / ``read(lo, hi)`` / ``close``).  All
rank-local slab math funnels through :func:`read_rows` — the ONE chunk
reader previously copied three times (``core/io.py:load_hdf5``,
``cluster/packing.py:load_hdf5_packed``, ``utils/data/partial_dataset``) —
and every read still routes through ``io._read_region``, so the existing
test spies see streaming reads too.

**Residency plan.**  :func:`plan_pass` sizes the slab and the host
prefetch depth from the budget resolution chain: explicit argument >
``HEAT_TPU_STREAM_BUDGET`` > measured headroom
(``memtrack.suggest_budget``, ledgered via ``autotune.note_budget_seed``)
> a static default.  Three device slabs are transiently live under double
buffering (computing, prefetched, and the consumer's just-released loop
reference), so a slab is at most ``budget // 3`` bytes; the slab-size
*fraction* is an
autotune arm (:data:`STREAM_ARMS`) per (source-geometry
fingerprint, device kind) — the tuner, not a constant, picks the slab
that maximizes overlap, and every arm is numerically identical so tuning
state can never change results.

**The pass.**  :class:`StreamPass` runs a daemon reader thread (host
reads into a bounded queue, poison-pill shutdown, exceptions propagated
to the consumer) while the consumer generator wraps each host slab into a
``split=0`` DNDarray — ``jax.device_put`` dispatches asynchronously, and
the next slab is fetched *before* the current one is yielded, so slab
``k+1``'s read + transfer hides behind slab ``k``'s compute.  Slabs are a
fixed row count (a multiple of the mesh size, tail zero-padded) so one
compiled program serves every slab — the no-retrace law holds across the
pass.  Consumed slabs are simply dropped by the consumer; their ledger
entries die with the buffers, and the ``staging`` tag's high-water mark
(``memtrack.summary()["peak_bytes_by_tag"]``) is the budget proof.

Telemetry: ``heat_tpu_stream_*`` gauges, ``stream_slab`` /
``stream_pass`` flight-recorder events, and a measured prefetch-overlap
fraction — ``1 - stall/io``, where *stall* is consumer time blocked on
the queue (the first fetch, the unavoidable cold pipeline fill, is
excluded and reported separately) and *io* is reader time on disk.  An
injected or real ``RESOURCE_EXHAUSTED`` during a slab transfer shrinks
the slab (halved, floored at one row per device) and re-chunks the
in-flight host rows instead of dying — the streaming face of the
informed-OOM-retry contract.
"""

from __future__ import annotations

import os
import queue as queue_mod
import threading
import time
from typing import Any, Callable, NamedTuple, Optional, Tuple

import numpy as np

from . import autotune, factories, guard, memtrack, telemetry
from ..parallel.mesh import sanitize_comm

__all__ = [
    "ChunkSource",
    "DEFAULT_BUDGET",
    "STREAM_ARMS",
    "Slab",
    "StreamPass",
    "StreamPlan",
    "finish_pass",
    "open_source",
    "plan_pass",
    "read_rows",
    "residency_budget",
    "stats",
]

# static residency default when nothing measured and no env override: two
# 128 MiB slabs — small enough to be safe on every supported device,
# large enough that host read syscall overhead amortizes
DEFAULT_BUDGET = 256 << 20

_STATS = telemetry.register_group(
    "stream",
    {
        "sources": 0,        # chunk sources opened
        "passes": 0,         # completed streaming passes
        "slabs": 0,          # device slabs produced
        "bytes_read": 0,     # host bytes read off disk/memory
        "oom_retries": 0,    # slab transfers retried after OOM
        "slab_shrinks": 0,   # slab-row halvings (OOM backoff)
        "io_s": 0.0,         # reader-thread seconds on host reads
        "stall_s": 0.0,      # consumer seconds blocked on the queue
        #                      (cold pipeline fill excluded; see below)
        "fill_s": 0.0,       # the excluded first-fetch pipeline fill
    },
)


def stats() -> dict:
    """Snapshot of the ``stream`` counter group (exported to Prometheus
    as ``heat_tpu_stream_*`` gauges)."""
    return telemetry.snapshot_group("stream")


# ------------------------------------------------------------ chunk reading


def read_rows(
    source,
    lo: int,
    hi: int,
    *,
    split_axis: int = 0,
    base: Optional[tuple] = None,
    copy: bool = False,
) -> np.ndarray:
    """THE rank-local slab read: rows ``[lo, hi)`` of ``split_axis``,
    full extent elsewhere, as a host ndarray.  Every h5py/NetCDF/npy/
    in-memory slab read in the repo funnels through here (satellite:
    previously three independent copies of this arithmetic), and through
    ``io._read_region`` below it, so the loaders' never-more-than-a-slab
    test spies cover streaming too.

    ``base`` is an optional tuple of already-normalized slices (one per
    dim, as ``io._normalize_slices`` produces): ``lo``/``hi`` then index
    *logical* rows within ``base[split_axis]``, honoring its step — the
    contract ``load_hdf5`` needs for user-sliced loads.  ``copy=True``
    forces a materialized copy (mmap-backed NetCDF/npy sources, where the
    view must not outlive the handle); memory maps are always copied.
    """
    from . import io as ht_io  # lazy: io imports this module at top level

    if base is None:
        shape = source.shape
        sel = tuple(
            slice(lo, hi) if d == split_axis else slice(0, n)
            for d, n in enumerate(shape)
        )
    else:
        bs = base[split_axis]
        step = bs.step if bs.step is not None else 1
        start = bs.start if bs.start is not None else 0
        sel = list(base)
        sel[split_axis] = slice(start + lo * step, start + hi * step, step)
        sel = tuple(sel)
    out = ht_io._read_region(source, sel)
    if copy or isinstance(out, np.memmap):
        out = np.array(out)
    return np.asarray(out)


class ChunkSource:
    """A row-sliceable host source: ``shape``, ``np_dtype``,
    ``read(lo, hi)`` → host ndarray of rows ``[lo, hi)``, ``close()``.
    Context manager; ``close`` is idempotent."""

    shape: Tuple[int, ...] = ()
    np_dtype: np.dtype = np.dtype(np.float32)

    def read(self, lo: int, hi: int) -> np.ndarray:
        raise NotImplementedError

    def close(self) -> None:
        pass

    def __enter__(self) -> "ChunkSource":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    def _cast(self, arr: np.ndarray) -> np.ndarray:
        if arr.dtype != self.np_dtype:
            arr = arr.astype(self.np_dtype)
        return arr


class _ArraySource(ChunkSource):
    """In-memory ndarray / live h5py dataset / memory map — anything with
    ``shape`` and basic slicing."""

    def __init__(self, obj, np_dtype=None):
        self._obj = obj
        self.shape = tuple(obj.shape)
        own = np.dtype(getattr(obj, "dtype", np.float32))
        self.np_dtype = np.dtype(np_dtype) if np_dtype is not None else own

    def read(self, lo: int, hi: int) -> np.ndarray:
        return self._cast(read_rows(self._obj, lo, hi))


class _H5Source(ChunkSource):
    def __init__(self, path: str, dataset: str, np_dtype=None):
        import h5py

        self._handle = h5py.File(path, "r")
        try:
            self._dset = self._handle[dataset]
        except Exception:
            self._handle.close()
            raise
        self.shape = tuple(self._dset.shape)
        self.np_dtype = (
            np.dtype(np_dtype) if np_dtype is not None
            else np.dtype(self._dset.dtype)
        )

    def read(self, lo: int, hi: int) -> np.ndarray:
        return self._cast(read_rows(self._dset, lo, hi))

    def close(self) -> None:
        if self._handle is not None:
            self._handle.close()
            self._handle = None


class _NetCDFSource(ChunkSource):
    def __init__(self, path: str, variable: str, np_dtype=None):
        try:
            import netCDF4

            self._handle = netCDF4.Dataset(path, "r")
            self._scipy = False
        except ImportError:
            from scipy.io import netcdf_file

            self._handle = netcdf_file(path, "r", mmap=True)
            self._scipy = True
        self._var = self._handle.variables[variable]
        self.shape = tuple(self._var.shape)
        self.np_dtype = (
            np.dtype(np_dtype) if np_dtype is not None
            else np.dtype(self._var.dtype)
        )

    def read(self, lo: int, hi: int) -> np.ndarray:
        # copy=True: classic-format reads are views into the file mmap
        return self._cast(read_rows(self._var, lo, hi, copy=True))

    def close(self) -> None:
        if self._handle is None:
            return
        import warnings

        self._var = None
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            self._handle.close()
        self._handle = None


def open_source(source, dataset: Optional[str] = None, *, np_dtype=None) -> ChunkSource:
    """Open a streamable row source.  Accepts a path (``.h5``/``.hdf5``
    and ``.nc``/``.nc4``/``.netcdf`` need ``dataset``; ``.npy`` memory-
    maps), an in-memory ndarray / h5py dataset / any ``shape`` +
    ``__getitem__`` object, or an already-open :class:`ChunkSource`
    (returned as-is — caller keeps ownership)."""
    if isinstance(source, ChunkSource):
        return source
    _STATS["sources"] += 1
    if isinstance(source, str):
        ext = os.path.splitext(source)[-1].lower().strip()
        if ext in (".h5", ".hdf5"):
            if dataset is None:
                raise ValueError("HDF5 sources need a dataset name")
            return _H5Source(source, dataset, np_dtype)
        if ext in (".nc", ".nc4", ".netcdf"):
            if dataset is None:
                raise ValueError("NetCDF sources need a variable name")
            return _NetCDFSource(source, dataset, np_dtype)
        if ext == ".npy":
            return _ArraySource(np.load(source, mmap_mode="r"), np_dtype)
        raise ValueError(f"unsupported streaming source extension {ext!r}")
    if hasattr(source, "shape") and hasattr(source, "__getitem__"):
        return _ArraySource(source, np_dtype)
    raise TypeError(f"cannot stream from {type(source)}")


# -------------------------------------------------------------- the budget


def residency_budget(budget: Optional[int] = None) -> int:
    """Resolve the streaming residency budget in bytes: explicit argument
    > ``HEAT_TPU_STREAM_BUDGET`` (strict parse, lint HT001) > measured
    headroom via :func:`memtrack.suggest_budget` (half the free HBM —
    ledgered through ``autotune.note_budget_seed`` when it shrinks the
    default) > :data:`DEFAULT_BUDGET` on statsless backends."""
    if budget is not None:
        return int(budget)
    if os.environ.get("HEAT_TPU_STREAM_BUDGET", "").strip():
        return autotune.env_bytes("HEAT_TPU_STREAM_BUDGET", DEFAULT_BUDGET)
    granted = memtrack.suggest_budget(DEFAULT_BUDGET, fraction=0.5)
    if granted is None or granted <= 0:
        return DEFAULT_BUDGET
    if granted < DEFAULT_BUDGET:
        autotune.note_budget_seed("stream.slab", granted, DEFAULT_BUDGET)
    return granted


class StreamPlan(NamedTuple):
    site: str            # consumer dispatch site ("kmeans_fit", ...)
    rows: int            # total logical rows in the source
    row_bytes: int       # bytes per logical row at the streaming dtype
    slab_rows: int       # device slab rows (multiple of the mesh size)
    depth: int           # host prefetch queue capacity, in slabs
    budget: int          # resolved residency budget, bytes
    arm: str             # STREAM_ARMS member that sized slab_rows
    key: Optional[Tuple[str, str]]  # tuning-table key (None: tuner off)


# round 22: the arms are SLAB SIZES, not lowerings: "slab_full" is the
# budget-derived maximum slab (budget//2 rows, two slabs live under double
# buffering), "slab_half"/"slab_quarter" trade residency for pipeline
# granularity (smaller slabs hide host reads better when the device step
# is short).  Every arm computes the identical result — each pass runs
# ONE arm and observes its wall, so the tuner converges on whichever slab
# maximizes prefetch overlap for this (source geometry, device kind).
STREAM_ARMS = ("slab_full", "slab_half", "slab_quarter")
_ARM_DIV = {"slab_full": 1, "slab_half": 2, "slab_quarter": 4}


def _round_down(x: int, m: int) -> int:
    return (x // m) * m


def _pick_arm(key: Tuple[str, str]) -> str:
    """Least-sampled arm first while exploring: all arms are numerically
    identical, so each pass runs ONE arm and rotation — not the repeated
    prior ``decide`` would return — is what fills every arm's samples."""
    e = autotune.table().get(key)
    counts = {
        a: len(e["arms"].get(a, [])) if e else 0
        for a in STREAM_ARMS
    }
    return min(STREAM_ARMS, key=lambda a: counts[a])


def plan_pass(
    src: ChunkSource,
    *,
    comm=None,
    site: str = "stream",
    budget: Optional[int] = None,
) -> StreamPlan:
    """Size one streaming pass over ``src``: resolve the budget, consult
    the tuner for the slab fraction, derive slab rows (multiple of the
    mesh size, two slabs resident under double buffering) and the host
    prefetch depth (what's left of the budget, clamped to [1, 4])."""
    comm = sanitize_comm(comm)
    shape = src.shape
    if not shape:
        raise ValueError("streaming sources must have at least one dim")
    rows = int(shape[0])
    row_bytes = int(src.np_dtype.itemsize)
    for n in shape[1:]:
        row_bytes *= int(n)
    b = residency_budget(budget)
    n_dev = comm.size
    # THREE slabs are transiently live (measured, not assumed): the slab
    # being computed on, the prefetched next one, and the consumer's
    # just-finished loop reference, which Python rebinds only after the
    # generator has already dispatched the next transfer → budget/3 each.
    # The floor is one row per device; below it streaming cannot shard.
    max_rows = max(n_dev, _round_down((b // 3) // max(row_bytes, 1), n_dev))
    arm, key = "slab_full", None
    if autotune.enabled():
        # geometry: rows bucket coarse (streaming length doesn't change
        # the right slab), features/dtype/mesh exact, budget bucketed to
        # a power of two so headroom jitter can't fragment the table
        key = autotune.key(
            "stream", site, rows.bit_length(), shape[1:], str(src.np_dtype),
            n_dev, int(b).bit_length(),
        )
        d = autotune.decide(
            key, _pick_arm(key), desc=f"stream {site} {shape}",
            arms=STREAM_ARMS,
        )
        arm = d.arm
    slab_rows = max(n_dev, _round_down(max_rows // _ARM_DIV[arm], n_dev))
    slab_bytes = slab_rows * row_bytes
    depth = max(1, min(4, b // max(slab_bytes, 1) - 1))
    return StreamPlan(site, rows, row_bytes, slab_rows, depth, b, arm, key)


# ---------------------------------------------------------------- the pass


class Slab(NamedTuple):
    index: int      # 0-based slab number within the pass
    x: Any          # DNDarray, shape (slab_rows, *features), split=0
    valid: int      # rows [0, valid) are real; the rest are zero padding
    base: int       # global row offset of this slab's row 0


class _Reader(threading.Thread):
    """Daemon host-read loop: slabs into a bounded queue, ``None`` poison
    pill on exhaustion OR failure (the error rides ``self.error`` to the
    consumer — satellite: the old partial_dataset thread had neither a
    shutdown path nor error propagation)."""

    def __init__(self, src: ChunkSource, q: "queue_mod.Queue",
                 slab_rows: int, rows: int, stop: threading.Event):
        super().__init__(daemon=True, name="heat-tpu-stream-reader")
        self._src = src
        self._q = q
        self._slab_rows = slab_rows
        self._rows = rows
        # NOT named _stop: threading.Thread owns a private _stop method
        self._halt = stop
        self.error: Optional[BaseException] = None
        self.io_s = 0.0
        self.bytes_read = 0

    def _put(self, item) -> None:
        while not self._halt.is_set():
            try:
                self._q.put(item, timeout=0.05)
                return
            except queue_mod.Full:
                continue

    def run(self) -> None:
        try:
            lo = 0
            while lo < self._rows and not self._halt.is_set():
                hi = min(lo + self._slab_rows, self._rows)
                t0 = time.perf_counter()
                host = self._src.read(lo, hi)
                self.io_s += time.perf_counter() - t0
                self.bytes_read += host.nbytes
                self._put((lo, host))
                lo = hi
        except BaseException as e:
            self.error = e
        finally:
            self._put(None)


class StreamPass:
    """One single-use streaming pass: iterate to get :class:`Slab`\\ s.

    The iterator prefetches — slab ``k+1`` is dequeued, transferred
    (async ``device_put`` inside ``factories.array``) and tagged
    ``staging`` *before* slab ``k`` is yielded, so its host read and
    wire time hide behind the consumer's device compute on ``k``.  Slab
    shape is constant across the pass (tail zero-padded), so the
    consumer's jitted step compiles once.  On ``RESOURCE_EXHAUSTED``
    during a transfer the slab halves (floored at one row per device)
    and the in-flight host rows re-chunk at the new size — later slabs
    run in a new compiled bucket, the documented cost of surviving.

    Use as an iterator or context manager; ``close()`` (idempotent,
    called automatically at exhaustion / generator close) stops and
    joins the reader thread."""

    def __init__(self, src: ChunkSource, *, comm=None,
                 plan: Optional[StreamPlan] = None, site: str = "stream",
                 budget: Optional[int] = None):
        self._src = open_source(src)
        self.comm = sanitize_comm(comm)
        self.plan = plan if plan is not None else plan_pass(
            self._src, comm=self.comm, site=site, budget=budget,
        )
        self.slab_rows = self.plan.slab_rows
        self.stall_s = 0.0
        self.fill_s = 0.0
        self.slabs = 0
        self.oom_retries = 0
        self._host: Optional[np.ndarray] = None
        self._off = 0
        self._hbase = 0
        self._got_first = False
        self._t0 = time.perf_counter()
        self._t1: Optional[float] = None
        self._stop = threading.Event()
        self._q: "queue_mod.Queue" = queue_mod.Queue(maxsize=self.plan.depth)
        self._reader = _Reader(
            self._src, self._q, self.plan.slab_rows, self.plan.rows,
            self._stop,
        )
        self._reader.start()

    # -- lifecycle

    def close(self) -> None:
        """Stop and join the reader (poison-pill + stop event); safe to
        call repeatedly and from ``__del__`` — abandoning a pass mid-way
        leaks neither a thread nor an open source handle it started."""
        if self._t1 is None:
            self._t1 = time.perf_counter()
        if self._stop.is_set():
            return
        self._stop.set()
        # drain so a reader blocked on a full queue sees the stop event
        try:
            while True:
                self._q.get_nowait()
        except queue_mod.Empty:
            pass
        self._reader.join(timeout=5.0)
        _STATS["io_s"] += self._reader.io_s
        _STATS["stall_s"] += self.stall_s
        _STATS["fill_s"] += self.fill_s
        _STATS["bytes_read"] += self._reader.bytes_read

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    def __enter__(self) -> "StreamPass":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False

    # -- measured report

    @property
    def wall_s(self) -> float:
        end = self._t1 if self._t1 is not None else time.perf_counter()
        return end - self._t0

    def overlap_frac(self) -> float:
        """Fraction of the reader's host-read time hidden behind device
        compute: ``1 - stall/io``.  The first fetch (cold pipeline fill —
        nothing to overlap with yet) is excluded from the stall and
        reported separately as ``fill_s``."""
        io = self._reader.io_s
        if io <= 0.0:
            return 1.0
        return max(0.0, 1.0 - self.stall_s / io)

    def report(self) -> dict:
        return {
            "slabs": self.slabs,
            "slab_rows": self.slab_rows,
            "bytes_read": self._reader.bytes_read,
            "io_s": round(self._reader.io_s, 6),
            "stall_s": round(self.stall_s, 6),
            "fill_s": round(self.fill_s, 6),
            "wall_s": round(self.wall_s, 6),
            "overlap_frac": round(self.overlap_frac(), 4),
            "oom_retries": self.oom_retries,
        }

    # -- slab production

    def _wrap(self, rows_np: np.ndarray):
        guard.fire("stream.slab")
        x = factories.array(rows_np, split=0, comm=self.comm)
        memtrack.tag_buffer(x.larray, "staging")
        return x

    def _shrink(self, exc: BaseException) -> None:
        n_dev = self.comm.size
        if self.slab_rows <= n_dev:
            raise exc
        new = max(n_dev, _round_down(self.slab_rows // 2, n_dev))
        _STATS["oom_retries"] += 1
        _STATS["slab_shrinks"] += 1
        self.oom_retries += 1
        telemetry.record_event(
            "stream_oom_retry", site=self.plan.site,
            slab_rows=self.slab_rows, retry_rows=new,
            error=str(exc)[:160],
        )
        self.slab_rows = new

    def _fetch(self) -> Optional[Slab]:
        while True:
            if self._host is None or self._off >= self._host.shape[0]:
                t0 = time.perf_counter()
                item = self._q.get()
                dt = time.perf_counter() - t0
                if self._got_first:
                    self.stall_s += dt
                else:
                    self._got_first = True
                    self.fill_s += dt
                if item is None:
                    if self._reader.error is not None:
                        raise RuntimeError(
                            "stream reader failed for "
                            f"{self.plan.site!r}"
                        ) from self._reader.error
                    return None
                self._hbase, self._host = item
                self._off = 0
            take = min(self.slab_rows, self._host.shape[0] - self._off)
            rows_np = self._host[self._off : self._off + take]
            base = self._hbase + self._off
            if take < self.slab_rows:
                pad = np.zeros(
                    (self.slab_rows - take,) + rows_np.shape[1:],
                    rows_np.dtype,
                )
                rows_np = np.concatenate([rows_np, pad])
            try:
                x = self._wrap(rows_np)
            except Exception as e:
                if not _is_oom(e):
                    raise
                # halve and re-cut THIS slab's rows at the new size —
                # the outer loop re-enters with _off unchanged
                self._shrink(e)
                continue
            self._off += take
            slab = Slab(self.slabs, x, take, base)
            self.slabs += 1
            _STATS["slabs"] += 1
            telemetry.record_event(
                "stream_slab", site=self.plan.site, index=slab.index,
                rows=self.slab_rows, valid=take, base=base,
                arm=self.plan.arm,
            )
            return slab

    def __iter__(self):
        try:
            nxt = self._fetch()
            while nxt is not None:
                cur = nxt
                # prefetch before yielding: slab k+1's dequeue + async
                # device_put dispatch while the caller computes on k
                nxt = self._fetch()
                yield cur
        finally:
            self.close()


def _is_oom(e: BaseException) -> bool:
    if "RESOURCE_EXHAUSTED" in str(e):
        return True
    try:
        from ..utils.fault import InjectedOOM

        return isinstance(e, InjectedOOM)
    except Exception:
        return False


def finish_pass(sp: StreamPass) -> dict:
    """Close out one completed pass: fold its wall into the tuner (the
    arm's measured sample), count it, flight-record the summary, and
    return the measured report (the consumer attaches ``overlap_frac`` /
    ``io_bytes`` to its program row via ``telemetry.annotate_program``)."""
    sp.close()
    rep = sp.report()
    _STATS["passes"] += 1
    pl = sp.plan
    if pl.key is not None and autotune.enabled():
        autotune.observe(pl.key, pl.arm, sp.wall_s)
    telemetry.record_event(
        "stream_pass", site=pl.site, arm=pl.arm, budget=pl.budget,
        **rep,
    )
    return rep
