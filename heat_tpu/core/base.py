"""Estimator base API (reference: heat/core/base.py:13-267).

Scikit-learn-style parameter handling and task mixins, unchanged in spirit:
this layer is device-agnostic."""

from __future__ import annotations

import inspect
from typing import Any, Dict, List, Optional, TypeVar

from . import telemetry
from .dndarray import DNDarray

__all__ = [
    "BaseEstimator",
    "ClassificationMixin",
    "ClusteringMixin",
    "RegressionMixin",
    "TransformMixin",
    "is_classifier",
    "is_clusterer",
    "is_estimator",
    "is_regressor",
    "is_transformer",
]

self_T = TypeVar("self_T")


class BaseEstimator:
    """Base for all estimators (reference: base.py:13)."""

    @classmethod
    def _parameter_names(cls) -> List[str]:
        init = cls.__init__
        if init is object.__init__:
            return []
        sig = inspect.signature(init)
        return [
            p.name
            for p in sig.parameters.values()
            if p.name != "self" and p.kind not in (p.VAR_POSITIONAL, p.VAR_KEYWORD)
        ]

    def get_params(self, deep: bool = True) -> Dict[str, Any]:
        """Parameters of this estimator (reference: base.py:27)."""
        params = {}
        for key in self._parameter_names():
            value = getattr(self, key, None)
            if deep and hasattr(value, "get_params"):
                for sub_key, sub_value in value.get_params().items():
                    params[f"{key}__{sub_key}"] = sub_value
            params[key] = value
        return params

    def set_params(self: self_T, **params: Any) -> self_T:
        """Set parameters (reference: base.py:60)."""
        if not params:
            return self
        valid = self.get_params(deep=True)
        for key, value in params.items():
            head, _, tail = key.partition("__")
            if head not in valid:
                raise ValueError(f"invalid parameter {head} for estimator {self}")
            if tail:
                getattr(self, head).set_params(**{tail: value})
            else:
                setattr(self, head, value)
        return self

    def __repr__(self, N_CHAR_MAX: int = 700) -> str:
        params = ", ".join(f"{k}={v!r}" for k, v in self.get_params(deep=False).items())
        return f"{self.__class__.__name__}({params})"


class ClassificationMixin:
    """fit/predict/score for classifiers (reference: base.py:98)."""

    def fit(self, x: DNDarray, y: DNDarray):
        raise NotImplementedError()

    def fit_predict(self, x: DNDarray, y: DNDarray) -> DNDarray:
        self.fit(x, y)
        return self.predict(x)

    def predict(self, x: DNDarray) -> DNDarray:
        raise NotImplementedError()

    def score(self, x: DNDarray, y: DNDarray, sample_weight=None) -> float:
        """Mean accuracy of ``predict(x)`` vs ``y``."""
        pred = self.predict(x)
        with telemetry.sync("estimator.score"):  # scalar metric API: the sync is the contract
            return float((pred.larray.reshape(-1) == y.larray.reshape(-1)).mean())


class ClusteringMixin:
    """fit/fit_predict for clusterers (reference: base.py:145)."""

    def fit(self, x: DNDarray):
        raise NotImplementedError()

    def fit_predict(self, x: DNDarray) -> DNDarray:
        self.fit(x)
        return self.predict(x)


class RegressionMixin:
    """fit/predict/score for regressors (reference: base.py:176)."""

    def fit(self, x: DNDarray, y: DNDarray):
        raise NotImplementedError()

    def fit_predict(self, x: DNDarray, y: DNDarray) -> DNDarray:
        self.fit(x, y)
        return self.predict(x)

    def predict(self, x: DNDarray) -> DNDarray:
        raise NotImplementedError()

    def score(self, x: DNDarray, y: DNDarray, sample_weight=None) -> float:
        """R^2 score."""
        import jax.numpy as jnp

        pred = self.predict(x).larray.reshape(-1)
        yv = y.larray.reshape(-1)
        ss_res = jnp.sum((yv - pred) ** 2)
        ss_tot = jnp.sum((yv - jnp.mean(yv)) ** 2)
        with telemetry.sync("estimator.score"):
            return float(1.0 - ss_res / ss_tot)


class TransformMixin:
    """fit/transform for transformers (reference: base.py analog)."""

    def fit(self, x: DNDarray):
        raise NotImplementedError()

    def transform(self, x: DNDarray) -> DNDarray:
        raise NotImplementedError()

    def fit_transform(self, x: DNDarray) -> DNDarray:
        self.fit(x)
        return self.transform(x)


def is_estimator(obj: Any) -> bool:
    """(reference: base.py:221)."""
    return isinstance(obj, BaseEstimator)


def is_classifier(obj: Any) -> bool:
    return is_estimator(obj) and isinstance(obj, ClassificationMixin)


def is_clusterer(obj: Any) -> bool:
    """(reference: base.py:245)."""
    return is_estimator(obj) and isinstance(obj, ClusteringMixin)


def is_regressor(obj: Any) -> bool:
    return is_estimator(obj) and isinstance(obj, RegressionMixin)


def is_transformer(obj: Any) -> bool:
    return is_estimator(obj) and isinstance(obj, TransformMixin)
