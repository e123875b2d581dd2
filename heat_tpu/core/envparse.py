"""Strict environment-knob parsing shared by every layer.

``autotune.env_bytes`` established the contract for byte-sized budgets:
empty/unset means the default, anything else must parse or the process
refuses to start — a typo'd knob must never silently fall back and turn
into an invisible perf bug (the r14 ``RING_MIN_BYTES`` fix).  This module
holds the integer counterpart at the bottom of the import graph (no
heat_tpu imports) so ``mesh``/``utils.fault`` — modules below or beside
``autotune`` — can share the parser without a cycle.
``autotune.env_int`` re-exports it as the public name.
"""

import os
from typing import Optional


def env_int(
    name: str, default: int, minimum: int = 1, env: Optional[dict] = None
) -> int:
    """THE integer env knob parser (``HEAT_TPU_INJECT_SEED``, launcher
    size sniffs): empty/unset
    returns ``default``; a malformed value or one below ``minimum``
    raises ``ValueError`` naming the variable."""
    raw = (os.environ if env is None else env).get(name, "").strip()
    if not raw:
        return int(default)
    try:
        val = int(raw)
        if val < minimum:
            raise ValueError
    except ValueError:
        raise ValueError(
            f"{name} must be an integer >= {minimum}, got {raw!r}"
        ) from None
    return val
