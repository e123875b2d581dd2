"""Gaussian naive Bayes (reference: heat/naive_bayes/gaussianNB.py, 529 LoC).

``fit``/``partial_fit`` with incremental mean/variance merging across batches
(reference: _update_mean_variance, the per-rank/per-batch Chan-merge) and
``predict``/``predict_log_proba``.  The per-class masked moments become
one-hot matmuls on the MXU; the cross-device reductions are XLA psums."""

from __future__ import annotations

from typing import Optional

import numpy as np

import jax.numpy as jnp

from ..core.base import BaseEstimator, ClassificationMixin
from ..core.dndarray import DNDarray, _ensure_split
from ..core import telemetry, types

__all__ = ["GaussianNB"]


class GaussianNB(ClassificationMixin, BaseEstimator):
    """Gaussian naive Bayes classifier (reference: gaussianNB.py:12)."""

    def __init__(self, priors=None, var_smoothing: float = 1e-9):
        self.priors = priors
        self.var_smoothing = var_smoothing
        self.classes_ = None
        self.theta_ = None  # per-class feature means (n_classes, n_features)
        self.var_ = None  # per-class feature variances
        self.class_count_ = None
        self.class_prior_ = None
        self.epsilon_ = None

    def _masked_moments(self, x, y_onehot, sample_weight=None):
        """Per-class counts, means, variances via one-hot matmuls.

        Variance is computed from *centered* samples (x − mean of the
        sample's class): the E[x²]−mean² form cancels catastrophically in
        float32 for offset data."""
        w = y_onehot if sample_weight is None else y_onehot * sample_weight[:, None]
        counts = jnp.sum(w, axis=0)  # (c,)
        sums = jnp.matmul(w.T, x)  # (c, f)
        means = sums / jnp.maximum(counts, 1)[:, None]
        centered = x - jnp.matmul(y_onehot, means)  # per-sample class mean
        sq = jnp.matmul(w.T, centered * centered)
        var = sq / jnp.maximum(counts, 1)[:, None]
        return counts, means, jnp.maximum(var, 0.0)

    def fit(self, x: DNDarray, y: DNDarray, sample_weight: Optional[DNDarray] = None) -> "GaussianNB":
        """Fit from scratch (reference: gaussianNB.py:70)."""
        self.classes_ = None
        self.theta_ = None
        return self.partial_fit(x, y, classes=None, sample_weight=sample_weight)

    def partial_fit(
        self,
        x: DNDarray,
        y: DNDarray,
        classes: Optional[DNDarray] = None,
        sample_weight: Optional[DNDarray] = None,
    ) -> "GaussianNB":
        """Incremental fit on a batch (reference: gaussianNB.py:200): merges
        the batch's per-class moments into the running ones (Chan et al.
        pairwise update, as the reference does across ranks and batches)."""
        from ..core import sanitation

        sanitation.sanitize_in(x)
        sanitation.sanitize_in(y)
        if x.ndim != 2:
            raise ValueError(f"expected x to be 2-D, but was {x.ndim}-D")
        xv = x.larray
        if not jnp.issubdtype(xv.dtype, jnp.floating):
            xv = xv.astype(jnp.float32)
        yv = y.larray.reshape(-1)

        if self.classes_ is None:
            if classes is not None:
                cls = classes.larray if isinstance(classes, DNDarray) else jnp.asarray(classes)
            else:
                cls = jnp.unique(yv)
            self.classes_ = DNDarray(
                cls, tuple(cls.shape), types.canonical_heat_type(cls.dtype), None, y.device, y.comm
            )
            nc, nf = cls.shape[0], x.shape[1]
            self._counts = jnp.zeros((nc,), dtype=xv.dtype)
            self._means = jnp.zeros((nc, nf), dtype=xv.dtype)
            self._vars = jnp.zeros((nc, nf), dtype=xv.dtype)

        cls = self.classes_.larray
        onehot = (yv[:, None] == cls[None, :]).astype(xv.dtype)
        sw = None
        if sample_weight is not None:
            sw = (sample_weight.larray if isinstance(sample_weight, DNDarray) else jnp.asarray(sample_weight)).reshape(-1).astype(xv.dtype)
        n_new, mu_new, var_new = self._masked_moments(xv, onehot, sw)

        # pairwise moment merge (reference: _update_mean_variance)
        n_old, mu_old, var_old = self._counts, self._means, self._vars
        n_tot = n_old + n_new
        safe = jnp.maximum(n_tot, 1)[:, None]
        delta = mu_new - mu_old
        mu_tot = mu_old + delta * (n_new / jnp.maximum(n_tot, 1))[:, None]
        m_old = var_old * n_old[:, None]
        m_new = var_new * n_new[:, None]
        m_tot = m_old + m_new + (delta**2) * ((n_old * n_new)[:, None] / safe)
        var_tot = m_tot / safe
        self._counts, self._means, self._vars = n_tot, mu_tot, var_tot

        # finalize public attributes
        with telemetry.sync("gaussiannb.epsilon"):  # one scalar finalizing fit
            self.epsilon_ = self.var_smoothing * float(jnp.max(jnp.var(xv, axis=0)))
        self.class_count_ = DNDarray(
            n_tot, tuple(n_tot.shape), types.canonical_heat_type(n_tot.dtype), None, x.device, x.comm
        )
        if self.priors is not None:
            pri = self.priors.larray if isinstance(self.priors, DNDarray) else jnp.asarray(self.priors)
        else:
            pri = n_tot / jnp.sum(n_tot)
        self.class_prior_ = DNDarray(
            pri, tuple(pri.shape), types.canonical_heat_type(pri.dtype), None, x.device, x.comm
        )
        self.theta_ = DNDarray(
            mu_tot, tuple(mu_tot.shape), types.canonical_heat_type(mu_tot.dtype), None, x.device, x.comm
        )
        self.var_ = DNDarray(
            var_tot, tuple(var_tot.shape), types.canonical_heat_type(var_tot.dtype), None, x.device, x.comm
        )
        return self

    def fit_stream(
        self,
        source,
        y,
        dataset: Optional[str] = None,
        *,
        classes=None,
        sample_weight=None,
        comm=None,
        budget: Optional[int] = None,
    ) -> "GaussianNB":
        """Fit from a source that does not fit in HBM: one streaming pass
        (core/stream.py), each slab folded in through :meth:`partial_fit`
        — the Chan merge is the streaming algorithm already, the engine
        just feeds it double-buffered slabs under the residency budget.

        ``y`` (and optional ``sample_weight``) are in-memory — labels are
        a vector, the features are what doesn't fit.  Slab tails are
        zero-padded by the engine; pad rows enter with weight 0 and the
        first class's label, so they touch no moment.  ``epsilon_`` is
        finalized from the pooled total variance reconstructed off the
        per-class stats (law of total variance), matching what a single
        in-memory call computes from the whole batch — NOT the last
        slab's variance."""
        from ..core import factories, stream
        from ..parallel.mesh import sanitize_comm

        comm = sanitize_comm(comm)
        src = stream.open_source(source, dataset=dataset,
                                 np_dtype=np.float32)
        own = src is not source  # passthrough ChunkSource stays caller-owned
        self.classes_ = None  # fresh fit, like fit()
        self.theta_ = None
        try:
            if len(src.shape) != 2:
                raise ValueError(
                    f"expected x to be 2-D, but was {len(src.shape)}-D"
                )
            n, f = src.shape
            y_host = np.asarray(
                y.larray if isinstance(y, DNDarray) else y
            ).reshape(-1)
            if y_host.shape[0] != n:
                raise ValueError(
                    f"y has {y_host.shape[0]} labels for {n} samples"
                )
            w_host = None
            if sample_weight is not None:
                w_host = np.asarray(
                    sample_weight.larray
                    if isinstance(sample_weight, DNDarray) else sample_weight,
                    np.float32,
                ).reshape(-1)
            if classes is not None:
                cls_np = np.asarray(
                    classes.larray if isinstance(classes, DNDarray)
                    else classes
                )
            else:
                cls_np = np.unique(y_host)
            cls_dnd = factories.array(cls_np, split=None, comm=comm)
            pl = stream.plan_pass(src, comm=comm, site="gnb_fit",
                                  budget=budget)
            sp = stream.StreamPass(src, comm=comm, plan=pl)
            for slab in sp:
                rows = slab.x.shape[0]
                lo, hi = slab.base, slab.base + slab.valid
                yk = y_host[lo:hi]
                w = np.zeros(rows, np.float32)
                w[: slab.valid] = 1.0 if w_host is None else w_host[lo:hi]
                if slab.valid < rows:
                    yk = np.concatenate([
                        yk, np.full(rows - slab.valid, cls_np[0], yk.dtype),
                    ])
                y_dnd = factories.array(yk, split=0, comm=comm)
                self.partial_fit(slab.x, y_dnd, classes=cls_dnd,
                                 sample_weight=w)
                del slab  # drop the loop reference: 3-slab residency cap
            rep = stream.finish_pass(sp)
            self.last_stream_report = dict(rep, arm=pl.arm, budget=pl.budget)
            fp = telemetry.fingerprint(
                ("stream_gnb", pl.slab_rows, f, len(cls_np), comm.size)
            )
            telemetry.ensure_program(
                fp, kind="stream_gnb", dtype="float32",
                flops=6.0 * n * f * len(cls_np),
                hbm_bytes=float(n) * f * 4,
            )
            telemetry.record_timing(fp, rep["wall_s"])
            telemetry.annotate_program(
                fp, io_stall_frac=round(1.0 - rep["overlap_frac"], 4),
                io_bytes=rep["bytes_read"],
            )
        finally:
            if own:
                src.close()
        # epsilon_ from the pooled variance of the WHOLE stream via the law
        # of total variance over the final per-class moments
        n_c, mu_c, var_c = self._counts, self._means, self._vars
        tot = jnp.maximum(jnp.sum(n_c), 1)
        mu = jnp.sum(n_c[:, None] * mu_c, axis=0) / tot
        total_var = jnp.sum(
            n_c[:, None] * (var_c + (mu_c - mu[None, :]) ** 2), axis=0
        ) / tot
        with telemetry.sync("gaussiannb.epsilon"):  # one scalar finalizing fit
            self.epsilon_ = self.var_smoothing * float(jnp.max(total_var))
        return self

    def _joint_log_likelihood(self, x: DNDarray):
        xv = x.larray
        if not jnp.issubdtype(xv.dtype, jnp.floating):
            xv = xv.astype(jnp.float32)
        var = self._vars + self.epsilon_
        mu = self._means
        # (n, c): sum over features of the per-class Gaussian log pdf
        log_prior = jnp.log(jnp.maximum(self.class_prior_.larray, 1e-300))
        n_ij = -0.5 * jnp.sum(jnp.log(2.0 * np.pi * var), axis=1)[None, :]
        quad = -0.5 * jnp.sum(
            ((xv[:, None, :] - mu[None, :, :]) ** 2) / var[None, :, :], axis=2
        )
        return log_prior[None, :] + n_ij + quad

    def logsumexp(self, a: DNDarray, axis=None, b=None, keepdims: bool = False,
                  return_sign: bool = False):
        """Numerically stable ``log(sum(b * exp(a)))`` (reference:
        gaussianNB.py:407, adapted there from scikit-learn)."""
        av = a.larray if isinstance(a, DNDarray) else jnp.asarray(a)
        bv = b.larray if isinstance(b, DNDarray) else b
        m = jnp.max(av, axis=axis, keepdims=True)
        m = jnp.where(jnp.isfinite(m), m, 0.0)
        e = jnp.exp(av - m)
        if bv is not None:
            e = e * bv
        s = jnp.sum(e, axis=axis, keepdims=keepdims)
        sign = jnp.sign(s)
        if not keepdims:
            m = jnp.squeeze(m, axis=axis) if axis is not None else jnp.squeeze(m)
        out_v = jnp.log(jnp.abs(s) if return_sign else s) + m
        from ..core import factories

        if isinstance(a, DNDarray):
            split = a.split if out_v.ndim == a.larray.ndim else None
            out = factories.array(out_v, split=split, device=a.device, comm=a.comm)
            if return_sign:
                return out, factories.array(sign, split=split, device=a.device, comm=a.comm)
            return out
        if return_sign:
            return factories.array(out_v), factories.array(sign)
        return factories.array(out_v)

    def predict_log_proba(self, x: DNDarray) -> DNDarray:
        """Per-class log probabilities (reference: gaussianNB.py:480)."""
        jll = self._joint_log_likelihood(x)
        norm = jll - jnp.max(jll, axis=1, keepdims=True)
        log_prob = norm - jnp.log(jnp.sum(jnp.exp(norm), axis=1, keepdims=True))
        out = DNDarray(
            log_prob, tuple(log_prob.shape), types.canonical_heat_type(log_prob.dtype),
            x.split, x.device, x.comm,
        )
        return _ensure_split(out, x.split)

    def predict_proba(self, x: DNDarray) -> DNDarray:
        """Per-class probabilities (reference: gaussianNB.py:~510)."""
        lp = self.predict_log_proba(x)
        out = jnp.exp(lp.larray)
        res = DNDarray(out, tuple(out.shape), lp.dtype, lp.split, lp.device, lp.comm)
        return _ensure_split(res, lp.split)

    def predict(self, x: DNDarray) -> DNDarray:
        """Most probable class per sample (reference: gaussianNB.py:~530)."""
        if self.theta_ is None:
            raise RuntimeError("fit the model first")
        jll = self._joint_log_likelihood(x)
        winner = jnp.argmax(jll, axis=1)
        labels = self.classes_.larray[winner]
        out = DNDarray(
            labels, tuple(labels.shape), types.canonical_heat_type(labels.dtype),
            x.split, x.device, x.comm,
        )
        return _ensure_split(out, x.split)
