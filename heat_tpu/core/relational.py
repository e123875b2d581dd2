"""Relational operations (reference: heat/core/relational.py, 420 LoC)."""

from __future__ import annotations

import jax.numpy as jnp

from . import _operations, telemetry
from .dndarray import DNDarray

__all__ = ["eq", "equal", "ge", "greater", "greater_equal", "gt", "le", "less", "less_equal", "lt", "ne", "not_equal"]


def eq(x, y) -> DNDarray:
    """Elementwise ==."""
    return _operations._binary_op(jnp.equal, x, y)


def equal(x, y) -> bool:
    """True iff shapes and all elements match (reference: global Allreduce of
    the local verdicts; here one jnp.all over the sharded comparison)."""
    if isinstance(x, DNDarray) and isinstance(y, DNDarray):
        if tuple(x.shape) != tuple(y.shape):
            return False
        with telemetry.sync("relational.equal"):  # a Python bool by NumPy parity
            return bool(jnp.all(x.larray == y.larray))
    a = x.larray if isinstance(x, DNDarray) else x
    b = y.larray if isinstance(y, DNDarray) else y
    try:
        with telemetry.sync("relational.equal"):
            return bool(jnp.all(jnp.equal(a, b)))
    except (ValueError, TypeError):
        return False


def ge(x, y) -> DNDarray:
    return _operations._binary_op(jnp.greater_equal, x, y)


greater_equal = ge


def gt(x, y) -> DNDarray:
    return _operations._binary_op(jnp.greater, x, y)


greater = gt


def le(x, y) -> DNDarray:
    return _operations._binary_op(jnp.less_equal, x, y)


less_equal = le


def lt(x, y) -> DNDarray:
    return _operations._binary_op(jnp.less, x, y)


less = lt


def ne(x, y) -> DNDarray:
    return _operations._binary_op(jnp.not_equal, x, y)


not_equal = ne


def _bind_operators():
    DNDarray.__eq__ = lambda self, other: eq(self, other)
    DNDarray.__ne__ = lambda self, other: ne(self, other)
    DNDarray.__lt__ = lambda self, other: lt(self, other)
    DNDarray.__le__ = lambda self, other: le(self, other)
    DNDarray.__gt__ = lambda self, other: gt(self, other)
    DNDarray.__ge__ = lambda self, other: ge(self, other)


_bind_operators()

# fusion op table (see arithmetics.py): comparisons are elementwise nodes —
# a relational tail on a fused chain stays in the same executable, and the
# Python-control-flow __bool__ on the result is the materialization boundary
from . import fusion as _fusion  # noqa: E402

for _fn, _name in [
    (jnp.equal, "eq"), (jnp.not_equal, "ne"), (jnp.less, "lt"),
    (jnp.less_equal, "le"), (jnp.greater, "gt"), (jnp.greater_equal, "ge"),
]:
    _fusion.register_op(_fn, _name, kind="comparison")

