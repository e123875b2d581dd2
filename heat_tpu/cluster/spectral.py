"""Spectral clustering (reference: heat/cluster/spectral.py, 217 LoC).

Pipeline matches the reference (:103-189): RBF similarity → graph Laplacian →
Lanczos low-rank eigendecomposition (distributed matmuls) → eigensolve of the
small tridiagonal T → KMeans on the spectral embedding.

Round 19: ``affinity="knn"`` swaps the dense RBF similarity for a sparse
k-NN graph (``sparse.knn_graph``) and keeps the WHOLE pipeline sparse —
DCSR Laplacian (``graph.laplacian_sparse``), Lanczos over the tuned SpMV
program, zero densifications of the affinity matrix.  The dense
(n, n) similarity never exists; HBM residency is O(nnz)."""

from __future__ import annotations

from typing import Optional

import jax.numpy as jnp

from ..core.base import BaseEstimator, ClusteringMixin
from ..core.dndarray import DNDarray, _ensure_split
from ..core import telemetry, types
from ..core.linalg import solver
from ..graph.laplacian import Laplacian
from ..sparse.dcsr_matrix import DCSR_matrix
from ..sparse.knn import knn_graph
from ..spatial import distance
from .kmeans import KMeans

__all__ = ["Spectral"]


class Spectral(ClusteringMixin, BaseEstimator):
    """Spectral clustering on a similarity graph (reference: spectral.py:12)."""

    def __init__(
        self,
        n_clusters: Optional[int] = None,
        gamma: float = 1.0,
        metric: str = "rbf",
        laplacian: str = "fully_connected",
        threshold: float = 1.0,
        boundary: str = "upper",
        n_lanczos: int = 300,
        assign_labels: str = "kmeans",
        affinity: str = "rbf",
        n_neighbors: int = 10,
        **params,
    ):
        self.n_clusters = n_clusters
        self.gamma = gamma
        self.metric = metric
        self.laplacian = laplacian
        self.threshold = threshold
        self.boundary = boundary
        self.n_lanczos = n_lanczos
        self.assign_labels = assign_labels
        self.affinity = affinity
        self.n_neighbors = n_neighbors

        if metric != "rbf":
            raise NotImplementedError(f"only the rbf metric is supported, got {metric!r}")
        if affinity not in ("rbf", "knn"):
            raise NotImplementedError(
                f'affinity must be "rbf" (dense) or "knn" (sparse), got {affinity!r}'
            )
        sigma = (1.0 / (2.0 * gamma)) ** 0.5
        if affinity == "knn":
            # sparse path: k-NN graph with RBF edge weights; bucketed
            # slab capacity so serving requests share compiled programs
            similarity = lambda x: knn_graph(
                x, n_neighbors, weights="rbf", sigma=sigma,
                bucket_cap=True, split=x.split if x.split == 0 else None,
            )
        else:
            similarity = lambda x: distance.rbf(
                x, sigma=sigma, quadratic_expansion=True
            )
        self._laplacian = Laplacian(
            similarity,
            definition="norm_sym",
            mode=laplacian,
            threshold_key=boundary,
            threshold_value=threshold,
        )
        if assign_labels == "kmeans":
            kmeans_params = params.get("params", {"n_clusters": n_clusters, "init": "kmeans++"})
            if n_clusters is not None:
                kmeans_params["n_clusters"] = n_clusters
            self._cluster = KMeans(**kmeans_params)
        else:
            raise NotImplementedError(
                f"only kmeans label assignment is supported, got {assign_labels!r}"
            )
        self._labels = None

    @property
    def labels_(self) -> DNDarray:
        return self._labels

    def _spectral_embedding(self, x: DNDarray):
        """Eigenvectors of the Laplacian via Lanczos (reference:
        spectral.py:103-149).  The sparse (knn) path runs the recurrence
        over the tuned SpMV program with a DETERMINISTIC start vector —
        a serving endpoint must embed identical batches identically."""
        L = self._laplacian.construct(x)
        n = L.shape[0]
        m = min(self.n_lanczos, n)
        if isinstance(L, DCSR_matrix):
            # deterministic, structureless v0 (sin ramp): generic w.r.t.
            # the Laplacian eigenbasis, unlike the all-ones vector which
            # is D^1/2-close to the trivial eigenvector
            raw = jnp.sin(jnp.arange(1, n + 1, dtype=jnp.float32))
            v0 = DNDarray(
                raw, (n,), types.float32, None, x.device, x.comm,
            )
            V, T = solver.lanczos(L, m, v0=v0)
        else:
            V, T = solver.lanczos(L, m)
        # eigensolve the small tridiagonal T; approximate eigenpairs of L
        evals, evecs = jnp.linalg.eigh(T.larray)
        eigenvectors = jnp.matmul(V.larray, evecs)
        return evals, eigenvectors, x

    def fit(self, x: DNDarray) -> "Spectral":
        """Embed and cluster (reference: spectral.py:150-189)."""
        from ..core import sanitation

        sanitation.sanitize_in(x)
        if x.ndim != 2:
            raise ValueError(f"input needs to be 2-D, but was {x.ndim}-D")
        evals, evecs, _ = self._spectral_embedding(x)

        if self.n_clusters is None:
            # largest eigen-gap heuristic (reference: spectral.py:166)
            gaps = jnp.diff(evals)
            with telemetry.sync("spectral.eigen_gap"):  # the host-side cluster count
                self.n_clusters = int(jnp.argmax(gaps)) + 1
            self._cluster.n_clusters = self.n_clusters

        components = evecs[:, : self.n_clusters]
        emb = DNDarray(
            components, tuple(components.shape),
            types.canonical_heat_type(components.dtype), x.split, x.device, x.comm,
        )
        emb = _ensure_split(emb, x.split)
        self._cluster.fit(emb)
        self._labels = self._cluster.labels_
        return self

    def predict(self, x: DNDarray) -> DNDarray:
        """Embed ``x`` and assign to the fitted KMeans centroids (reference:
        spectral.py:190-230 recomputes the eigenspectrum of ``x`` and calls
        the fitted clusterer's predict)."""
        from ..core import sanitation

        sanitation.sanitize_in(x)
        if self._labels is None:
            raise RuntimeError("fit the model first")
        if x.split is not None and x.split != 0:
            raise NotImplementedError("Not implemented for other splitting-axes")
        _, evecs, _ = self._spectral_embedding(x)
        components = evecs[:, : self.n_clusters]
        emb = DNDarray(
            components, tuple(components.shape),
            types.canonical_heat_type(components.dtype), x.split, x.device, x.comm,
        )
        return self._cluster.predict(_ensure_split(emb, x.split))
