"""Statistical operations (reference: heat/core/statistics.py, 2000 LoC).

The reference's hand-built distributed machinery — custom MPI reduce ops
carrying (value, index) pairs for argmax/argmin (statistics.py:1338, 1374),
pairwise moment merging for mean/var across ranks (``__merge_moments``,
:1044, Bennett et al.) — all collapses into single jnp reductions that XLA
partitions and all-reduces over ICI.  ``median``/``percentile`` use the
sort-based global path the reference uses, via XLA's distributed-capable sort.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import numpy as np

import jax.numpy as jnp

from . import _operations, sanitation, telemetry, types
from .dndarray import DNDarray, _ensure_split
from .stride_tricks import sanitize_axis

__all__ = [
    "argmax",
    "argmin",
    "average",
    "bincount",
    "bucketize",
    "cov",
    "digitize",
    "histc",
    "histogram",
    "kurtosis",
    "max",
    "maximum",
    "mean",
    "median",
    "min",
    "minimum",
    "mpi_argmax",
    "mpi_argmin",
    "percentile",
    "skew",
    "std",
    "var",
]


# Module-level singleton kernels: the fusion engine fingerprints op-DAGs by
# the function OBJECT (qualnames are unsafe — the old per-call lambdas here
# closed over ddof, so two same-named closures could mean different math).
# One stable object per statistic, with ddof & friends as static kwargs,
# makes repeated mean/var/std pipelines hit the compile cache instead of
# re-tracing every call.

def _float_acc(t):
    """The float-cast policy of the statistics family: integers accumulate
    in the default float type, floats keep their precision."""
    return t if jnp.issubdtype(t.dtype, jnp.inexact) else t.astype(jnp.float32)


def _argmax_kernel(t, axis=None, keepdims=False):
    return jnp.argmax(t, axis=axis, keepdims=keepdims)


def _argmin_kernel(t, axis=None, keepdims=False):
    return jnp.argmin(t, axis=axis, keepdims=keepdims)


def _mean_kernel(t, axis=None, keepdims=False, dtype=None):
    return jnp.mean(_float_acc(t), axis=axis, keepdims=keepdims, dtype=dtype)


def _std_kernel(t, axis=None, keepdims=False, dtype=None, ddof=0):
    return jnp.std(_float_acc(t), axis=axis, ddof=ddof, keepdims=keepdims, dtype=dtype)


def _var_kernel(t, axis=None, keepdims=False, dtype=None, ddof=0):
    return jnp.var(_float_acc(t), axis=axis, ddof=ddof, keepdims=keepdims, dtype=dtype)


for _k, _n in [
    (_argmax_kernel, "argmax"), (_argmin_kernel, "argmin"),
    (_mean_kernel, "mean"), (_std_kernel, "std"), (_var_kernel, "var"),
]:
    _operations.fusion.register_op(_k, _n, kind="reduction")


def argmax(x, axis=None, out=None, keepdims=False) -> DNDarray:
    """Index of the maximum (reference: statistics.py:46 — twin-payload MPI op
    there, one jnp.argmax here)."""
    return _operations._reduce_op(
        _argmax_kernel, x, axis=axis, out=out, keepdims=keepdims
    )


def argmin(x, axis=None, out=None, keepdims=False) -> DNDarray:
    """Index of the minimum (reference: statistics.py:117)."""
    return _operations._reduce_op(
        _argmin_kernel, x, axis=axis, out=out, keepdims=keepdims
    )


def average(x, axis=None, weights=None, returned=False):
    """Weighted average (reference: statistics.py:189)."""
    sanitation.sanitize_in(x)
    w = weights.larray if isinstance(weights, DNDarray) else weights
    result, wsum = jnp.average(x.larray, axis=axis, weights=w, returned=True)
    axis_s = sanitize_axis(x.shape, axis)
    split = x.split
    if split is not None:
        if axis_s is None or split == axis_s:
            split = None
        elif axis_s is not None and axis_s < split:
            split -= 1
    avg = _ensure_split(
        DNDarray(result, tuple(result.shape), types.canonical_heat_type(result.dtype), split, x.device, x.comm),
        split,
    )
    if returned:
        ws = _ensure_split(
            DNDarray(jnp.broadcast_to(wsum, result.shape), tuple(result.shape), types.canonical_heat_type(wsum.dtype), split, x.device, x.comm),
            split,
        )
        return avg, ws
    return avg


def bincount(x, weights=None, minlength: int = 0) -> DNDarray:
    """Occurrence counts of non-negative ints (reference: statistics.py:323)."""
    sanitation.sanitize_in(x)
    w = weights.larray if isinstance(weights, DNDarray) else weights
    result = jnp.bincount(x.larray, weights=w, minlength=minlength)
    return DNDarray(result, tuple(result.shape), types.canonical_heat_type(result.dtype), None, x.device, x.comm)


def bucketize(input, boundaries, out_int32: bool = False, right: bool = False, out=None) -> DNDarray:
    """Bucket index of each element (reference: statistics.py:394)."""
    sanitation.sanitize_in(input)
    b = boundaries.larray if isinstance(boundaries, DNDarray) else jnp.asarray(boundaries)
    # torch.bucketize: right=False → boundaries[i-1] < v <= boundaries[i]
    # (= searchsorted side='left'); right=True → side='right'
    side = "right" if right else "left"
    result = jnp.searchsorted(b, input.larray, side=side)
    if out_int32:
        result = result.astype(jnp.int32)
    wrapped = _ensure_split(
        DNDarray(result, tuple(result.shape), types.canonical_heat_type(result.dtype), input.split, input.device, input.comm),
        input.split,
    )
    if out is not None:
        out.larray = wrapped.larray
        return out
    return wrapped


def cov(m, y=None, rowvar: bool = True, bias: bool = False, ddof=None) -> DNDarray:
    """Covariance matrix (reference: statistics.py:467)."""
    sanitation.sanitize_in(m)
    yv = y.larray if isinstance(y, DNDarray) else y
    result = jnp.cov(m.larray, yv, rowvar=rowvar, bias=bias, ddof=ddof)
    result = jnp.atleast_2d(result)
    return DNDarray(result, tuple(result.shape), types.canonical_heat_type(result.dtype), None, m.device, m.comm)


def digitize(x, bins, right: bool = False) -> DNDarray:
    """Bin index of each element (reference: statistics.py:542)."""
    sanitation.sanitize_in(x)
    b = bins.larray if isinstance(bins, DNDarray) else jnp.asarray(bins)
    result = jnp.digitize(x.larray, b, right=right)
    return _ensure_split(
        DNDarray(result, tuple(result.shape), types.canonical_heat_type(result.dtype), x.split, x.device, x.comm),
        x.split,
    )


def histc(input, bins: int = 100, min: float = 0.0, max: float = 0.0, out=None) -> DNDarray:
    """Histogram with equal-width bins (reference: statistics.py:617)."""
    sanitation.sanitize_in(input)
    lo, hi = float(min), float(max)
    if lo == 0.0 and hi == 0.0:
        with telemetry.sync("statistics.histc_range"):  # host bounds (NumPy parity)
            lo = float(jnp.min(input.larray))
            hi = float(jnp.max(input.larray))
    hist, _ = jnp.histogram(input.larray, bins=bins, range=(lo, hi))
    hist = hist.astype(input.dtype.jax_type())
    wrapped = DNDarray(hist, tuple(hist.shape), input.dtype, None, input.device, input.comm)
    if out is not None:
        out.larray = hist
        return out
    return wrapped


def histogram(a, bins: int = 10, range=None, normed=None, weights=None, density=None):
    """NumPy-style histogram (reference: statistics.py:680; ``normed`` is the
    deprecated pre-NumPy-1.24 alias the reference still accepts)."""
    sanitation.sanitize_in(a)
    if normed is not None and density is None:
        density = normed
    w = weights.larray if isinstance(weights, DNDarray) else weights
    hist, edges = jnp.histogram(a.larray, bins=bins, range=range, weights=w, density=density)
    h = DNDarray(hist, tuple(hist.shape), types.canonical_heat_type(hist.dtype), None, a.device, a.comm)
    e = DNDarray(edges, tuple(edges.shape), types.canonical_heat_type(edges.dtype), None, a.device, a.comm)
    return h, e


def kurtosis(x, axis=None, unbiased: bool = True, Fischer: bool = True) -> DNDarray:
    """Kurtosis (reference: statistics.py:728 — pairwise moment merging there,
    a fused global moment computation here)."""
    return _moment_stat(x, axis, order=4, unbiased=unbiased, fischer=Fischer)


def skew(x, axis=None, unbiased: bool = True) -> DNDarray:
    """Skewness (reference: statistics.py:1679)."""
    return _moment_stat(x, axis, order=3, unbiased=unbiased)


def _moment_kernel(t, axis=None, order=3, n=1, unbiased=True, fischer=True):
    """Standardized central moment of order 3 (skew) / 4 (kurtosis) with
    the reference's bias corrections — all host-static decisions (order,
    sample count, bias mode) ride as kwargs so the singleton function
    object fingerprints stably in the fusion op table."""
    t = _float_acc(t)
    mu = jnp.mean(t, axis=axis, keepdims=True)
    centered = t - mu
    m2 = jnp.mean(centered**2, axis=axis)
    mk = jnp.mean(centered**order, axis=axis)
    if order == 3:
        g = mk / (m2**1.5)
        if unbiased and n > 2:
            g = g * np.sqrt(n * (n - 1)) / (n - 2)
    else:
        g = mk / (m2**2)
        if unbiased and n > 3:
            g = ((n**2 - 1) * g - 3 * (n - 1) ** 2) / ((n - 2) * (n - 3)) + 3
        if fischer:
            g = g - 3
    return jnp.asarray(g)


_operations.fusion.register_op(_moment_kernel, "moment", kind="composite")


def _moment_stat(x, axis, order: int, unbiased: bool, fischer: bool = True) -> DNDarray:
    """Shared skew/kurtosis entry.  Under fusion the whole multi-pass
    moment computation (mean, centering, two powers, two means, bias
    correction) joins the lazy DAG as ONE composite node — so
    ``materialize(skew_chain, kurtosis_chain)`` shares the input leaf and
    compiles a single program, and a chain feeding the moment fuses
    through instead of materializing first."""
    sanitation.sanitize_in(x)
    fusion = _operations.fusion
    axis_s = sanitize_axis(x.shape, axis)
    n = x.size if axis_s is None else x.shape[axis_s]
    split = x.split
    if split is not None:
        if axis_s is None or split == axis_s:
            split = None
        elif axis_s < split:
            split -= 1
    if fusion.enabled():
        try:
            nx = _operations._lazy_operand(x, x.comm)
            res = fusion.node(
                _moment_kernel, (nx,), axis=axis_s, order=int(order),
                n=int(n), unbiased=bool(unbiased), fischer=bool(fischer),
            )
            out_split = None if len(res.aval.shape) == 0 else split
            return fusion.defer(
                res, tuple(res.aval.shape),
                types.canonical_heat_type(res.aval.dtype),
                out_split, x.device, x.comm,
            )
        except fusion.Unfusable:
            fusion.count_fallback()
    result = _moment_kernel(
        x.larray, axis=axis_s, order=int(order), n=int(n),
        unbiased=bool(unbiased), fischer=bool(fischer),
    )
    if result.ndim == 0:
        split = None
    return _ensure_split(
        DNDarray(result, tuple(result.shape), types.canonical_heat_type(result.dtype), split, x.device, x.comm),
        split,
    )


def max(x, axis=None, out=None, keepdims=False) -> DNDarray:
    """Maximum (reference: statistics.py:782)."""
    return _operations._reduce_op(jnp.max, x, axis=axis, out=out, keepdims=keepdims)


def maximum(x1, x2, out=None, where=None) -> DNDarray:
    """Elementwise maximum (reference: statistics.py:841)."""
    return _operations._binary_op(jnp.maximum, x1, x2, out=out, where=where)


def mean(x, axis=None, keepdims: bool = False) -> DNDarray:
    """Arithmetic mean (reference: statistics.py:892 — merged-moments
    Allreduce there, one partitioned jnp.mean here; ``keepdims`` is a
    numpy-parity extension the reference lacks).  Under fusion, a pipeline
    like ``(x - x.mean(0)) / x.std(0)`` accumulates into one lazy DAG and
    lowers as a single cached executable."""
    return _operations._reduce_op(_mean_kernel, x, axis=axis, keepdims=keepdims)


def median(x, axis=None, keepdims=False) -> DNDarray:
    """Median via the global-sort path (reference: statistics.py:1018)."""
    return percentile(x, 50.0, axis=axis, keepdims=keepdims)


def min(x, axis=None, out=None, keepdims=False) -> DNDarray:
    """Minimum (reference: statistics.py:1115)."""
    return _operations._reduce_op(jnp.min, x, axis=axis, out=out, keepdims=keepdims)


def minimum(x1, x2, out=None, where=None) -> DNDarray:
    return _operations._binary_op(jnp.minimum, x1, x2, out=out, where=where)


def _percentile_of_sorted(sv, q, axis: int, n: int, method: str, keepdims: bool):
    """Select percentiles from an already (distributed-)sorted axis: only
    O(len(q)) slices are gathered, never the data axis."""
    q_arr = jnp.asarray(q, jnp.float32)
    scalar_q = q_arr.ndim == 0
    pos = q_arr / 100.0 * (n - 1)
    lo = jnp.clip(jnp.floor(pos).astype(jnp.int32), 0, n - 1)
    hi = jnp.clip(jnp.ceil(pos).astype(jnp.int32), 0, n - 1)
    if method == "lower":
        out = jnp.take(sv, lo, axis=axis)
    elif method == "higher":
        out = jnp.take(sv, hi, axis=axis)
    elif method == "nearest":
        out = jnp.take(sv, jnp.round(pos).astype(jnp.int32), axis=axis)
    else:
        vlo = jnp.take(sv, lo, axis=axis)
        vhi = jnp.take(sv, hi, axis=axis)
        if method == "midpoint":
            out = (vlo + vhi) / 2
        else:  # linear
            frac = (pos - lo).reshape(
                (1,) * axis + q_arr.shape + (1,) * (sv.ndim - axis - 1)
            )
            out = vlo + (vhi - vlo) * frac
    # numpy layout: q dims lead the reduced shape
    if not scalar_q:
        out = jnp.moveaxis(out, axis, 0)
    if keepdims:
        out = jnp.expand_dims(out, axis + (0 if scalar_q else 1))
    return out


def percentile(x, q, axis=None, out=None, interpolation: str = "linear", keepdims=False) -> DNDarray:
    """q-th percentile along axis (reference: statistics.py:1409 — a global
    sort there).  When the reduction axis is the split axis, the distributed
    merge-split sort (parallel/sort.py) orders the axis in place and only the
    q-th slices are gathered, so the computation scales past one device's
    memory."""
    sanitation.sanitize_in(x)
    axis_s = sanitize_axis(x.shape, axis)
    qv = q.larray if isinstance(q, DNDarray) else q
    if axis_s is None and x.ndim == 1:
        axis_s = 0
    if (
        isinstance(axis_s, int)
        and axis_s == x.split
        and x.comm.size > 1
        and x.is_distributed()
        and interpolation in ("linear", "lower", "higher", "nearest", "midpoint")
    ):
        from .manipulations import sort as _sort

        xf = x if jnp.issubdtype(x.larray.dtype, jnp.inexact) else x.astype(types.float32)
        sv, _ = _sort(xf, axis=axis_s)
        result = _percentile_of_sorted(
            sv.larray, qv, axis_s, x.shape[axis_s], interpolation, keepdims
        )
        # numpy/jnp percentile propagates NaN; the sorted-selection path
        # would instead pick a finite value (NaNs sink to the sorted tail).
        # Mask lanes that contain NaN so split and local paths agree
        # (advisor round 2).  The sort already established the fact: NaNs
        # order last among valid elements, so one O(lanes) slice — the
        # last valid sorted element per lane — is the mask; no extra
        # full-axis reduction.
        if jnp.issubdtype(xf.larray.dtype, jnp.floating):
            last_valid = jnp.take(sv.larray, x.shape[axis_s] - 1, axis=axis_s)
            nan_lane = jnp.isnan(last_valid)
            if keepdims:
                nan_lane = jnp.expand_dims(nan_lane, axis_s)
            result = jnp.where(nan_lane, jnp.array(jnp.nan, result.dtype), result)
    else:
        result = jnp.percentile(
            x.larray.astype(jnp.float32) if not jnp.issubdtype(x.larray.dtype, jnp.inexact) else x.larray,
            jnp.asarray(qv), axis=axis_s, method=interpolation, keepdims=keepdims,
        )
    wrapped = DNDarray(
        result, tuple(result.shape), types.canonical_heat_type(result.dtype), None, x.device, x.comm
    )
    if out is not None:
        out.larray = wrapped.larray
        return out
    return wrapped


def std(x, axis=None, ddof: int = 0, keepdims: bool = False) -> DNDarray:
    """Standard deviation (reference: statistics.py:1724).  ``ddof`` rides
    as a static kwarg on the singleton kernel so every call shares one
    fusion fingerprint per ddof value."""
    return _operations._reduce_op(
        _std_kernel, x, axis=axis, keepdims=keepdims, ddof=ddof
    )


def var(x, axis=None, ddof: int = 0, keepdims: bool = False) -> DNDarray:
    """Variance (reference: statistics.py:1857 — Bennett merged moments there,
    one partitioned jnp.var here)."""
    return _operations._reduce_op(
        _var_kernel, x, axis=axis, keepdims=keepdims, ddof=ddof
    )


def _mpi_argreduce(a, b, cmp):
    """Shared body of :func:`mpi_argmax`/:func:`mpi_argmin`: each operand is
    a flat array whose first half holds values and second half indices; the
    winner per element is chosen by ``cmp``, ties resolve to the lower
    global index."""
    lhs, rhs = jnp.asarray(a), jnp.asarray(b)
    (lv, li), (rv, ri) = jnp.split(lhs, 2), jnp.split(rhs, 2)
    take_l, take_r = cmp(lv, rv), cmp(rv, lv)
    values = jnp.where(take_l, lv, rv)
    indices = jnp.where(take_l, li, jnp.where(take_r, ri, jnp.minimum(li, ri)))
    return jnp.concatenate((values, indices))


def mpi_argmax(a, b, _=None):
    """Combine two packed ``(values, indices)`` argmax payloads
    (reference: statistics.py:1338, a custom MPI reduce op over raw byte
    buffers).  XLA reduces arbitrary computations, so :func:`argmax` never
    needs this; it is kept as a functional combiner for code written against
    the reference API."""
    return _mpi_argreduce(a, b, jnp.greater)


def mpi_argmin(a, b, _=None):
    """Combine two packed ``(values, indices)`` argmin payloads
    (reference: statistics.py:1374); see :func:`mpi_argmax`."""
    return _mpi_argreduce(a, b, jnp.less)


# method bindings (the reference binds these on DNDarray too)
DNDarray.argmax = lambda self, axis=None, out=None, keepdims=False: argmax(self, axis, out, keepdims)
DNDarray.argmin = lambda self, axis=None, out=None, keepdims=False: argmin(self, axis, out, keepdims)
DNDarray.max = lambda self, axis=None, out=None, keepdims=False: max(self, axis, out, keepdims)
DNDarray.min = lambda self, axis=None, out=None, keepdims=False: min(self, axis, out, keepdims)
DNDarray.mean = lambda self, axis=None: mean(self, axis)
DNDarray.std = lambda self, axis=None, ddof=0: std(self, axis, ddof)
DNDarray.var = lambda self, axis=None, ddof=0: var(self, axis, ddof)
