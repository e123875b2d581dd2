"""Shared k-clustering base (reference: heat/cluster/_kcluster.py, 254 LoC).

Init strategies match the reference (:87-194): ``"random"`` stratified point
sampling, ``"probability_based"`` (kmeans++) distance-weighted sampling, or
directly passed centroids.  Where the reference walks displacement tables and
Bcasts the chosen rows rank by rank, here a gather from the global array is
one XLA op (the sampled rows end up replicated, exactly like the Bcast)."""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp

from ..core import random as ht_random
from ..core.base import BaseEstimator, ClusteringMixin
from ..core.dndarray import DNDarray, _ensure_split
from ..core import telemetry, types
from ..ops.cdist import cdist as ops_cdist

__all__ = ["_KCluster"]


def _masked_medians(x, labels, k: int, fallback):
    """Per-cluster, per-feature median of the rows assigned to each cluster.

    The naive masked formulation (reference: kmedians.py:57 builds a
    per-cluster selection) would materialize an ``(n, k, f)`` tensor for a
    NaN-median — 20 GB at 1e7x64x8.  Instead: one ``(n, f)`` sort per cluster
    (non-members pushed to +inf sort to the end), then the two middle rows of
    the member prefix are picked by dynamic index.  Empty clusters fall back
    to ``fallback[j]``."""

    def body(j, meds):
        mask = labels == j
        cnt = jnp.sum(mask)
        svals = jnp.sort(jnp.where(mask[:, None], x, jnp.inf), axis=0)
        lo = jnp.maximum((cnt - 1) // 2, 0)
        hi = cnt // 2
        med = (
            jax.lax.dynamic_index_in_dim(svals, lo, 0, keepdims=False)
            + jax.lax.dynamic_index_in_dim(svals, hi, 0, keepdims=False)
        ) * 0.5
        return meds.at[j].set(jnp.where(cnt > 0, med, fallback[j]))

    return jax.lax.fori_loop(0, k, body, jnp.zeros((k, x.shape[1]), x.dtype))


def _l1_dist(x, centers):
    """(n, k) Manhattan distances; the broadcast |x-c| fuses into the
    reduction (no (n, k, f) buffer)."""
    return jnp.sum(jnp.abs(x[:, None, :] - centers[None, :, :]), axis=-1)


def _l1_assign(x, centers):
    """Labels by Manhattan distance."""
    return jnp.argmin(_l1_dist(x, centers), axis=1)


@partial(jax.jit, static_argnames=("k",))
def _kmeanspp_init(arr, us, k: int):
    """Distance-weighted (kmeans++) seeding, fused on-device (reference:
    _kcluster.py:141 draws one sample per round with a Bcast; a per-round
    ``.item()`` readback would stall the device k times, so all k rounds
    run in one XLA program fed by a single batch of uniforms).

    Matches the reference's weighting — Euclidean distance to the nearest
    chosen center, for every estimator (the reference's probability_based
    branch always uses ``spatial.cdist``, _kcluster.py:161) — carried as a
    running min so each round costs one (n, 1) distance column rather than
    an (n, k) recomputation.  Divergence from the reference, on purpose: the
    reference mins over all k centroid slots including the still-zero
    placeholders, so distance-to-origin leaks into its weights; here
    unchosen slots do not participate."""
    n, _ = arr.shape
    first = jnp.minimum((us[0] * n).astype(jnp.int32), n - 1)
    c0 = jax.lax.dynamic_index_in_dim(arr, first, 0, keepdims=False)
    centers = jnp.zeros((k, arr.shape[1]), arr.dtype).at[0].set(c0)
    d = ops_cdist(arr, c0[None, :], sqrt=True)[:, 0]

    def body(j, carry):
        centers, d = carry
        cum = jnp.cumsum(d / jnp.sum(d))
        nxt = jnp.minimum(jnp.searchsorted(cum, us[j]), n - 1)
        cj = jax.lax.dynamic_index_in_dim(arr, nxt, 0, keepdims=False)
        d = jnp.minimum(d, ops_cdist(arr, cj[None, :], sqrt=True)[:, 0])
        return centers.at[j].set(cj), d

    centers, _ = jax.lax.fori_loop(1, k, body, (centers, d))
    return centers


@partial(jax.jit, static_argnames=("k", "snap_to_sample"))
def _median_loop(x, centers, k: int, max_iter, tol, snap_to_sample: bool):
    """On-device KMedians/KMedoids iteration loop (one XLA program; see
    kmeans._lloyd_while for why there is no host readback inside the
    loop).

    ``snap_to_sample=False``: KMedians — centers move to per-cluster medians.
    ``snap_to_sample=True``: KMedoids — the median is snapped to the nearest
    actual sample (reference: kmedoids.py:56 "closest sample to the median").
    """

    def cond(state):
        _, shift, it = state
        return jnp.logical_and(it < max_iter, shift > tol)

    def body(state):
        centers, _, it = state
        labels = _l1_assign(x, centers)
        new = _masked_medians(x, labels, k, centers)
        if snap_to_sample:
            counts = jnp.sum(labels[:, None] == jnp.arange(k)[None, :], axis=0)
            d2 = ops_cdist(x, new, sqrt=False)
            idx = jnp.argmin(d2, axis=0)
            new = jnp.where(counts[:, None] > 0, x[idx], centers)
        shift = jnp.sum((new - centers) ** 2)
        return new, shift, it + 1

    init = (centers, jnp.array(jnp.inf, x.dtype), 0)
    return jax.lax.while_loop(cond, body, init)


class _KCluster(ClusteringMixin, BaseEstimator):
    """Base class for k-statistics clustering (KMeans/KMedians/KMedoids)."""

    def __init__(
        self,
        metric: Callable,
        n_clusters: int,
        init: Union[str, DNDarray],
        max_iter: int,
        tol: float,
        random_state: Optional[int],
    ):
        self.n_clusters = n_clusters
        self.init = init
        self.max_iter = max_iter
        self.tol = tol
        self.random_state = random_state

        self._metric = metric
        self._cluster_centers = None
        self._labels = None
        self._inertia = None
        self._n_iter = None

    @property
    def cluster_centers_(self) -> DNDarray:
        """Coordinates of the cluster centers (replicated)."""
        return self._cluster_centers

    @property
    def labels_(self) -> DNDarray:
        return self._labels

    @property
    def inertia_(self) -> float:
        return self._inertia

    @property
    def n_iter_(self) -> int:
        return self._n_iter

    def _initialize_cluster_centers(self, x: DNDarray):
        """Pick initial centroids (reference: _kcluster.py:87)."""
        if self.random_state is not None:
            ht_random.seed(self.random_state)
        k = self.n_clusters
        n = x.shape[0]
        if n < k:
            raise ValueError(
                f"n_samples={n} should be >= n_clusters={k}"
            )
        arr = x.larray

        if isinstance(self.init, DNDarray):
            if self.init.ndim != 2:
                raise ValueError("passed centroids need to be two-dimensional")
            if self.init.shape[0] != k or self.init.shape[1] != x.shape[1]:
                raise ValueError("passed centroids do not match cluster count or data shape")
            self._cluster_centers = self.init.resplit(None)
            return

        if not jnp.issubdtype(arr.dtype, jnp.floating):
            arr = arr.astype(jnp.float32)
        if isinstance(self.init, str) and self.init == "random":
            # one sample per stratum [i*n/k, (i+1)*n/k) — the reference's
            # equal-distribution draw (_kcluster.py:101-123); one batched
            # uniform draw, indices never leave the device
            # uniforms stay float32: cast to a half-precision data dtype
            # would quantize the sampled indices to ~1.7k distinct rows
            # scope the draw to x's communicator: a sub-mesh fit must not mix
            # world-mesh arrays into the jitted init (comm.Split consumers)
            us = ht_random.rand(k, comm=x.comm).larray.astype(jnp.float32)
            lo = jnp.arange(k) * (n // k)
            width = jnp.maximum(jnp.asarray(n // k), 1)
            idx = jnp.minimum(lo + (us * width).astype(jnp.int32), n - 1)
            centroids = arr[idx]
        elif isinstance(self.init, str) and self.init in ("probability_based", "kmeans++"):
            # scope the draw to x's communicator: a sub-mesh fit must not mix
            # world-mesh arrays into the jitted init (comm.Split consumers)
            us = ht_random.rand(k, comm=x.comm).larray.astype(jnp.float32)
            centroids = _kmeanspp_init(arr, us, k)
        else:
            raise ValueError(
                f'init needs to be "random", "kmeans++"/"probability_based" or a '
                f"DNDarray, but was {self.init!r}"
            )

        self._cluster_centers = DNDarray(
            centroids, tuple(centroids.shape),
            types.canonical_heat_type(centroids.dtype), None, x.device, x.comm,
        )

    def _labels_by_kernel(self, x: DNDarray) -> Optional[DNDarray]:
        """A hook of :meth:`_assign_to_cluster`: the labels of ``x`` by a
        kernel that computes this estimator's metric, as the concrete
        ``(n, 1)`` array the lazy path would give, or None where there is no
        such kernel or it declines ``x``."""
        return None

    def _assign_to_cluster(self, x: DNDarray, return_inertia: bool = False):
        """Assign each sample to its closest centroid (reference:
        _kcluster.py:196).  With ``return_inertia`` the min-distance sum
        rides along as a second root of the SAME fused program — the
        cdist subtree is shared through the scheduler's CSE, so labels and
        inertia cost one compile and one dispatch, not two cdists.

        The one entry for labels, ``fit``'s and ``predict``'s alike; its span
        ``kmeans.labels`` notes what made them (``assign`` = ``fused``, the
        estimator's kernel, | ``classic``, the lazy distances and argmin)."""
        from ..core import fusion, statistics

        with telemetry.span("kmeans.labels") as sp:
            labels = None if return_inertia else self._labels_by_kernel(x)
            sp.note(assign="classic" if labels is None else "fused")
            if labels is not None:
                return labels
            # the distance update rides the fusion engine: a GSPMD cdist defers a
            # lazy DAG and this argmin extends it, so distances + labels lower as
            # one cached executable per (shape, sharding) key
            distances = self._metric(x, self._cluster_centers)
            labels = statistics.argmin(distances, axis=1, keepdims=True)
            if return_inertia:
                inertia = statistics.min(distances, axis=1).sum()
                fusion.materialize(labels, inertia)
                with telemetry.sync("kcluster.inertia"):  # one scalar per fit
                    inertia_val = float(jnp.asarray(inertia.larray).reshape(()))
            if labels.split != x.split:
                out = DNDarray(
                    labels.larray, labels.gshape, labels.dtype, x.split, x.device, x.comm
                )
                labels = _ensure_split(out, x.split)
            if return_inertia:
                return labels, inertia_val
            return labels

    def _update_centroids(self, x: DNDarray, matching_centroids: DNDarray):
        raise NotImplementedError()

    def fit(self, x: DNDarray):
        raise NotImplementedError()

    def _fit_median_loop(self, x: DNDarray, snap_to_sample: bool):
        """Shared KMedians/KMedoids fit body: initialize, run the on-device
        :func:`_median_loop`, rebuild center/label metadata."""
        from ..core import sanitation

        sanitation.sanitize_in(x)
        if x.ndim != 2:
            raise ValueError(f"input needs to be 2-D, but was {x.ndim}-D")
        self._initialize_cluster_centers(x)
        arr = x.larray
        if not jnp.issubdtype(arr.dtype, jnp.floating):
            arr = arr.astype(jnp.float32)
        centers = self._cluster_centers.larray.astype(arr.dtype)
        centers, _, n_iter = _median_loop(
            arr, centers, self.n_clusters, self.max_iter, self.tol,
            snap_to_sample=snap_to_sample,
        )
        with telemetry.sync("kcluster.n_iter"):  # one scalar per fit
            self._n_iter = int(n_iter)
        self._cluster_centers = DNDarray(
            centers, tuple(centers.shape),
            types.canonical_heat_type(centers.dtype), None, x.device, x.comm,
        )
        self._labels, self._inertia = self._assign_to_cluster(x, return_inertia=True)
        return self

    def predict(self, x: DNDarray) -> DNDarray:
        """Closest-cluster index for each sample (reference: _kcluster.py)."""
        from ..core import sanitation

        sanitation.sanitize_in(x)
        if self._cluster_centers is None:
            raise RuntimeError(
                f"{type(self).__name__} is not fitted yet; call fit() before predict()"
            )
        return self._assign_to_cluster(x)
