"""Logical operations (reference: heat/core/logical.py, 549 LoC)."""

from __future__ import annotations

import jax.numpy as jnp

from . import _operations, telemetry, types
from .dndarray import DNDarray

__all__ = [
    "all",
    "allclose",
    "any",
    "isclose",
    "isfinite",
    "isinf",
    "isnan",
    "isneginf",
    "isposinf",
    "logical_and",
    "logical_not",
    "logical_or",
    "logical_xor",
    "signbit",
]


def all(x, axis=None, out=None, keepdims=False) -> DNDarray:
    """True where all elements along axis are truthy (reference: MPI.LAND
    reduce, logical.py:~30)."""
    return _operations._reduce_op(jnp.all, x, axis=axis, out=out, keepdims=keepdims)


def allclose(x, y, rtol: float = 1e-05, atol: float = 1e-08, equal_nan: bool = False) -> bool:
    """Global closeness verdict (reference: logical.py:~100)."""
    a = x.larray if isinstance(x, DNDarray) else jnp.asarray(x)
    b = y.larray if isinstance(y, DNDarray) else jnp.asarray(y)
    with telemetry.sync("logical.allclose"):  # a Python bool by NumPy parity
        return bool(jnp.allclose(a, b, rtol=rtol, atol=atol, equal_nan=equal_nan))


def any(x, axis=None, out=None, keepdims=False) -> DNDarray:
    """True where any element along axis is truthy (reference: MPI.LOR)."""
    return _operations._reduce_op(jnp.any, x, axis=axis, out=out, keepdims=keepdims)


def isclose(x, y, rtol: float = 1e-05, atol: float = 1e-08, equal_nan: bool = False) -> DNDarray:
    return _operations._binary_op(
        jnp.isclose, x, y, fn_kwargs={"rtol": rtol, "atol": atol, "equal_nan": equal_nan}
    )


def isfinite(x) -> DNDarray:
    return _operations._local_op(jnp.isfinite, x, no_cast=True)


def isinf(x) -> DNDarray:
    return _operations._local_op(jnp.isinf, x, no_cast=True)


def isnan(x) -> DNDarray:
    return _operations._local_op(jnp.isnan, x, no_cast=True)


def isneginf(x, out=None) -> DNDarray:
    return _operations._local_op(jnp.isneginf, x, out=out, no_cast=True)


def isposinf(x, out=None) -> DNDarray:
    return _operations._local_op(jnp.isposinf, x, out=out, no_cast=True)


def logical_and(x, y) -> DNDarray:
    return _operations._binary_op(jnp.logical_and, x, y)


def logical_not(x, out=None) -> DNDarray:
    return _operations._local_op(jnp.logical_not, x, out=out, no_cast=True)


def logical_or(x, y) -> DNDarray:
    return _operations._binary_op(jnp.logical_or, x, y)


def logical_xor(x, y) -> DNDarray:
    return _operations._binary_op(jnp.logical_xor, x, y)


def signbit(x, out=None) -> DNDarray:
    return _operations._local_op(jnp.signbit, x, out=out, no_cast=True)


DNDarray.all = lambda self, axis=None, out=None, keepdims=False: all(self, axis, out, keepdims)
DNDarray.any = lambda self, axis=None, out=None, keepdims=False: any(self, axis, out, keepdims)

# fusion op table (see arithmetics.py)
from . import fusion as _fusion  # noqa: E402

for _fn, _name in [
    (jnp.logical_and, "logical_and"), (jnp.logical_or, "logical_or"),
    (jnp.logical_xor, "logical_xor"), (jnp.logical_not, "logical_not"),
    (jnp.isclose, "isclose"), (jnp.isfinite, "isfinite"),
    (jnp.isinf, "isinf"), (jnp.isnan, "isnan"),
    (jnp.isneginf, "isneginf"), (jnp.isposinf, "isposinf"),
    (jnp.signbit, "signbit"),
]:
    _fusion.register_op(_fn, _name, kind="predicate")
for _fn, _name in [(jnp.all, "all"), (jnp.any, "any")]:
    _fusion.register_op(_fn, _name, kind="reduction")

