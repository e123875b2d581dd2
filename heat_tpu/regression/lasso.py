"""LASSO regression (reference: heat/regression/lasso.py, 184 LoC).

Coordinate-descent with soft thresholding (reference: soft_threshold
:90-107, fit :121).  Each coordinate step is a distributed matvec; the
feature loop is compiled into one ``lax.fori_loop`` so a full sweep is a
single XLA program instead of n_features eager rounds of Allreduce."""

from __future__ import annotations

from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp

from ..core.base import BaseEstimator, RegressionMixin
from ..core.dndarray import DNDarray, _ensure_split
from ..core import autotune, telemetry, types
from ..ops import lasso_sweep
from ..parallel.collectives import jit_shard_map_cached, on_each_device

__all__ = ["Lasso"]


@jax.jit
def _cd_sweep(X, y, theta, lam):
    """One full coordinate-descent sweep over all features.

    The residual r = y − Xθ is maintained incrementally (one rank-1 update per
    coordinate) instead of recomputing Xθ per coordinate — O(f·m) per sweep
    rather than O(f²·m)."""
    m = X.shape[0]
    n = X.shape[1]
    r0 = y - X @ theta

    def body(j, carry):
        th, r = carry
        xj = X[:, j]
        rho = jnp.dot(xj, r + th[j] * xj) / m
        # soft threshold (intercept j==0 unpenalized, reference: lasso.py:100)
        new = jnp.where(
            j == 0,
            rho,
            jnp.sign(rho) * jnp.maximum(jnp.abs(rho) - lam, 0.0),
        )
        r = r + (th[j] - new) * xj
        return th.at[j].set(new), r

    theta, _ = jax.lax.fori_loop(0, n, body, (theta, r0))
    return theta


@partial(jax.jit, static_argnames=("kernel",))
def _cd_fit(X, y, theta, lam, max_iter, tol, kernel: str = ""):
    """Coordinate-descent sweeps until ``max |Δθ| < tol`` or ``max_iter``,
    entirely on-device: a per-sweep host readback of the convergence
    scalar would stall the device between sweeps (same pattern as
    cluster._kcluster._median_loop).

    ``kernel`` (``""``/``"tpu"``/``"interpret"``, static) routes each
    sweep through the fused Pallas kernel (``ops/lasso_sweep.py``) —
    residual resident in VMEM across all coordinates — instead of the
    XLA ``fori_loop`` lowering.  Callers gate on
    ``lasso_sweep.sweep_mode``; the autotune ``kernel`` arm in
    :meth:`Lasso.fit` measures it against the classic sweep."""

    def cond(state):
        _, diff, it = state
        return jnp.logical_and(it < max_iter, diff >= tol)

    if kernel:
        Xt, yt = lasso_sweep.prepare(X, y)

    def body(state):
        th, _, it = state
        if kernel:
            new = lasso_sweep.sweep_prepared(
                Xt, yt, th, lam, X.shape[0],
                interpret=(kernel == "interpret"),
            )
        else:
            new = _cd_sweep(X, y, th, lam)
        return new, jnp.max(jnp.abs(new - th)), it + 1

    init = (theta, jnp.array(jnp.inf, X.dtype), 0)
    return jax.lax.while_loop(cond, body, init)


def _cd_fit_on_each_device(mesh, kernel: str):
    """``jit_shard_map_cached`` builder: :func:`_cd_fit` through the
    Pallas sweep on a multi-device mesh (replicated operands; see
    ``collectives.on_each_device``)."""
    return on_each_device(partial(_cd_fit, kernel=kernel), mesh)


class Lasso(RegressionMixin, BaseEstimator):
    """L1-regularized least squares via coordinate descent (reference:
    lasso.py:10).  ``lam`` is the regularization strength; fitting augments
    the design matrix with an unpenalized intercept column, as the reference's
    examples do."""

    def __init__(self, lam: float = 0.1, max_iter: int = 100, tol: float = 1e-6):
        self.__lam = lam
        self.max_iter = max_iter
        self.tol = tol
        self.__theta = None
        self.n_iter = None

    @property
    def coef_(self) -> Optional[DNDarray]:
        """Feature coefficients (without intercept)."""
        return None if self.__theta is None else self.__theta[1:]

    @property
    def intercept_(self) -> Optional[DNDarray]:
        return None if self.__theta is None else self.__theta[0]

    @property
    def lam(self) -> float:
        return self.__lam

    @lam.setter
    def lam(self, arg: float):
        self.__lam = arg

    @property
    def theta(self):
        return self.__theta

    def soft_threshold(self, rho: DNDarray) -> Union[DNDarray, float]:
        """Soft threshold operator (reference: lasso.py:90)."""
        out = jnp.sign(rho.larray) * jnp.maximum(jnp.abs(rho.larray) - self.__lam, 0.0)
        return DNDarray(out, tuple(out.shape), rho.dtype, rho.split, rho.device, rho.comm)

    def rmse(self, gt: DNDarray, yest: DNDarray) -> float:
        """Root mean squared error (reference: lasso.py:109)."""
        with telemetry.sync("lasso.rmse"):  # scalar metric API: the sync is the contract
            return float(jnp.sqrt(jnp.mean((gt.larray - yest.larray) ** 2)))

    @telemetry.span("lasso.fit")
    def fit(self, x: DNDarray, y: DNDarray) -> "Lasso":
        """Coordinate descent until the coefficient change < tol (reference:
        lasso.py:121)."""
        from ..core import sanitation

        sanitation.sanitize_in(x)
        sanitation.sanitize_in(y)
        if x.ndim != 2:
            raise ValueError(f"x needs to be 2-D, but was {x.ndim}-D")

        X = x.larray
        if not jnp.issubdtype(X.dtype, jnp.floating):
            X = X.astype(jnp.float32)
        yv = y.larray.reshape(-1).astype(X.dtype)
        # augment with intercept column
        ones = jnp.ones((X.shape[0], 1), dtype=X.dtype)
        Xa = jnp.concatenate([ones, X], axis=1)

        theta0 = jnp.zeros(Xa.shape[1], dtype=X.dtype)
        ma, na = Xa.shape

        def fit_fn(km: str = ""):
            if km and x.comm.size > 1:
                fn = jit_shard_map_cached(
                    _cd_fit_on_each_device, x.comm.mesh, km
                )
            else:
                fn = partial(_cd_fit, kernel=km)
            return fn(Xa, yv, theta0, self.__lam, self.max_iter, self.tol)

        # round 15: the fused VMEM-resident sweep as a measured autotune
        # arm beside the classic lowering (the reference arm)
        kmode = lasso_sweep.sweep_mode(ma, na, Xa.dtype, x.split, x.comm.size)
        if kmode != "off" and autotune.enabled():
            dt = str(Xa.dtype)
            theta, _, n_iter = autotune.run(
                autotune.key("kernel", "lasso_sweep", ma, na, dt, x.comm.size),
                {"classic": fit_fn, "kernel": partial(fit_fn, kmode)},
                prior="classic", desc=f"lasso {ma}x{na} {dt}",
                site="lasso_sweep",
                cost={"kernel": dict(
                    sig=("lasso_sweep_fused", ma, na, dt),
                    kind="kernel_lasso_sweep", ops=1,
                    flops=4.0 * ma * na,
                    hbm_bytes=float(ma * na * Xa.dtype.itemsize),
                    mesh={"devices": x.comm.size}, dtype=dt,
                )},
            )
        else:
            theta, _, n_iter = fit_fn()
        with telemetry.sync("lasso.n_iter"):  # one scalar per fit
            self.n_iter = int(n_iter)

        self.__theta = DNDarray(
            theta.reshape(-1, 1), (theta.shape[0], 1),
            types.canonical_heat_type(theta.dtype), None, x.device, x.comm,
        )
        return self

    def predict(self, x: DNDarray) -> DNDarray:
        """ŷ = [1, x] @ θ (reference: lasso.py predict)."""
        if self.__theta is None:
            raise RuntimeError("fit the model first")
        X = x.larray
        if not jnp.issubdtype(X.dtype, jnp.floating):
            X = X.astype(jnp.float32)
        ones = jnp.ones((X.shape[0], 1), dtype=X.dtype)
        Xa = jnp.concatenate([ones, X], axis=1)
        yest = jnp.matmul(Xa, self.__theta.larray.reshape(-1))
        out = DNDarray(
            yest.reshape(-1, 1), (yest.shape[0], 1),
            types.canonical_heat_type(yest.dtype), x.split, x.device, x.comm,
        )
        return _ensure_split(out, x.split if x.split == 0 else None)
