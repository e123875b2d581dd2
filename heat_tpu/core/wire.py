"""Quantized collectives: absmax-scaled wire formats for the movement engines.

Heat's value is moving shards (PAPER.md: every op is local compute + MPI
collectives), and the roofline plane names the collective-heavy rows of
the memory-bound tail as the top unreclaimed cost.  Round 16 proved int8
blocks can ride the ring for one consumer (``spatial.cdist_quantized``);
this module generalizes it into a property of the transport/overlap layer:
every split-crossing byte becomes a tuning decision.

The format: immediately before the ``all_to_all``/``ppermute``, each tile
is snapped to int8 (or ``float8_e4m3fn``) on an absmax grid with ONE f32
scale per tile-row (:func:`absmax_encode` — the same grid math as
``core/quantize.py``'s weight quantizer, which now delegates here); the
quantized payload and its scales ride the collective side by side, and
:func:`absmax_decode` lands them back in the payload dtype inside the
same shard_map program.  Accumulation stays f32.  All-zero rows carry
scale 1 so zeros round-trip exactly — in particular, the engines' masked
pad lanes stay exact zeros on the far side.

Dispatch rides the tuning plane as a ``("wire_f32", "wire_int8",
"wire_fp8")`` arm tuple per (site, geometry, device kind) —
:data:`WIRE_ARMS`:

- **wire_f32** — today's full-precision collective, byte-for-byte.  This
  is the *reference* arm: explore calls return its result bitwise, and
  ``HEAT_TPU_WIRE=off`` (or ``HEAT_TPU_AUTOTUNE=off``) restores it with
  zero table decisions.
- **wire_int8 / wire_fp8** — 1-byte elements on the wire (~4x less ICI
  traffic for f32 payloads), f32 scales beside them (one per tile-row),
  dequantize-on-landing, measured against the f32 arm by the same
  explore/exploit machinery as ring-vs-GSPMD.  Winners persist through
  ``HEAT_TPU_AUTOTUNE_CACHE`` and ``autotune.merge``.

Exactness-sensitive paths decline STATICALLY — no table entry, no
decision, the f32 wire bit-for-bit: bool/integer payloads
(:func:`eligible`), index gathers whose payload IS the data
(``transport.tiled_take`` — its ``psum_scatter`` also sums across
sources, which per-source scales cannot survive), guard-folded
finiteness chains (``overlap._Spec.fold`` — the guard's verdict must
describe the caller's numbers, not the quantized ones), the traveling
``rs`` accumulator (re-quantizing partial sums every hop compounds the
error), and any caller passing ``exact=True``.

Knobs (both HT001-clean): ``HEAT_TPU_WIRE`` = ``on`` (default: arm per
site via autotune) | ``off`` | ``int8`` | ``fp8`` (force one arm, zero
table decisions — benchmarks/law tests); ``HEAT_TPU_WIRE_MIN_BYTES``
(``autotune.env_bytes``, default 64 KiB) — below it the wire stays f32:
tiny transfers are latency-bound and the quant/dequant pass only adds
work.
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Tuple

import jax.numpy as jnp

from . import autotune, telemetry

__all__ = [
    "QMAX",
    "WIRE_ARMS",
    "absmax_decode",
    "absmax_encode",
    "account",
    "choose",
    "consume",
    "decline",
    "eligible",
    "explore",
    "fp8_available",
    "min_bytes",
    "mode",
    "payload_nbytes",
    "qdtype",
    "set_mode",
    "stats",
]

# absmax maps onto the quantized grid's largest representable magnitude
QMAX = {"int8": 127.0, "fp8": 448.0}

# round 17: the WIRE format of the data-movement engines.  "wire_f32" is
# the reference arm (today's full-precision collective, byte-for-byte);
# "wire_int8"/"wire_fp8" ship absmax-scaled low-precision tiles over the
# all_to_all/ppermute and dequantize on landing.  Distinct from
# quantize.QUANT_ARMS: those pick what the GEMM *computes on*, these pick
# what the COLLECTIVE *ships* — a site can hold both kinds of entries at
# once.
WIRE_ARMS = ("wire_f32", "wire_int8", "wire_fp8")

_VALID_MODES = ("on", "off", "int8", "fp8")
_MODE_OVERRIDE: "list[Optional[str]]" = [None]

_WIRE_MIN_BYTES_DEFAULT = 64 << 10  # below this the hop is latency-bound


def qdtype(mode_str: str):
    """The jnp dtype of one wire/quant mode (``int8`` | ``fp8``)."""
    if mode_str == "int8":
        return jnp.dtype(jnp.int8)
    if mode_str == "fp8":
        f8 = getattr(jnp, "float8_e4m3fn", None)
        if f8 is None:
            raise ValueError(
                "fp8 quantization needs a jax with float8_e4m3fn support"
            )
        return jnp.dtype(f8)
    raise ValueError(
        f"quantize dtype must be 'int8' or 'fp8', got {mode_str!r}"
    )


def fp8_available() -> bool:
    return getattr(jnp, "float8_e4m3fn", None) is not None


def mode(env: Optional[dict] = None) -> str:
    """The ``HEAT_TPU_WIRE`` mode: ``on`` (tuned arm per site, default),
    ``off`` (f32 wire bit-for-bit, zero table decisions), or a forced
    ``int8``/``fp8`` arm.  Malformed values raise naming the variable —
    an operator's typo'd mode must not silently become a different one."""
    if _MODE_OVERRIDE[0] is not None:
        return _MODE_OVERRIDE[0]
    raw = (os.environ if env is None else env).get("HEAT_TPU_WIRE", "on")
    raw = raw.strip().lower() or "on"
    if raw not in _VALID_MODES:
        raise ValueError(
            f"HEAT_TPU_WIRE must be one of {_VALID_MODES}, got {raw!r}"
        )
    return raw


def set_mode(mode_str: Optional[str]) -> Optional[str]:
    """Process-wide override of ``HEAT_TPU_WIRE`` (``None`` restores the
    environment variable).  Returns the previous override."""
    if mode_str is not None and mode_str not in _VALID_MODES:
        raise ValueError(
            f"mode must be one of {_VALID_MODES}, got {mode_str!r}"
        )
    prev = _MODE_OVERRIDE[0]
    _MODE_OVERRIDE[0] = mode_str
    return prev


def min_bytes(env: Optional[dict] = None) -> int:
    # one parser with HEAT_TPU_TILE_BYTES (autotune.env_bytes): a
    # malformed threshold raises instead of silently running the default
    return autotune.env_bytes(
        "HEAT_TPU_WIRE_MIN_BYTES", _WIRE_MIN_BYTES_DEFAULT, env
    )


# Registered as the "wire" telemetry group → Prometheus heat_tpu_wire_*
_STATS = telemetry.register_group(
    "wire",
    {
        # dispatches that actually shipped a quantized wire format
        "quantized_dispatches": 0,
        # static declines while the wire plane was live (bool/int dtype,
        # exact=True, index gathers, folded guards, below min-bytes)
        "declined_static": 0,
        # explore rounds (all arms measured, f32 result returned)
        "explores": 0,
        # modeled bytes the f32 wire would have moved for quantized
        # dispatches, and what the quantized wire moved instead — the
        # on-wire delta the cb rows and dashboards prove the win from
        "bytes_logical": 0,
        "bytes_wire": 0,
        "by_arm": {"wire_f32": 0, "wire_int8": 0, "wire_fp8": 0},
    },
)


def stats() -> dict:
    """Snapshot of the ``wire`` counter group (Prometheus:
    ``heat_tpu_wire_*``)."""
    return telemetry.snapshot_group("wire")


# ---------------------------------------------------------------- grid math


def absmax_encode(x, mode_str: str, axes: tuple):
    """Absmax quantization: reduce ``|x|`` over every non-kept axis, snap
    to the int8/fp8 grid.  ``axes`` is the tuple of KEPT (scale-carrying)
    axes — ``(0,)`` gives one f32 scale per tile-row, ``()`` one scalar
    scale for the whole block.  Scales stay f32; all-zero rows get scale
    1 so the dequant is exact zeros, never 0/0.  Pure traced-safe jnp —
    usable inside shard_map bodies (the wire sites) and under the weight
    quantizer's jitted wrappers (``core/quantize.py`` delegates here)."""
    qdt = qdtype(mode_str)
    qmax = QMAX[mode_str]
    xf = x.astype(jnp.float32)
    reduce_axes = tuple(d for d in range(x.ndim) if d not in axes)
    absmax = jnp.max(jnp.abs(xf), axis=reduce_axes)
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0).astype(jnp.float32)
    sb = jnp.expand_dims(scale, reduce_axes)
    grid = xf / sb
    if mode_str == "int8":
        q = jnp.clip(jnp.round(grid), -qmax, qmax).astype(qdt)
    else:
        q = jnp.clip(grid, -qmax, qmax).astype(qdt)
    return q, scale


def absmax_decode(q, scale, axes: tuple, dtype):
    """Land a quantized tile back in ``dtype``: ``q * scale`` with the
    scale broadcast over the reduced axes, f32 multiply."""
    reduce_axes = tuple(d for d in range(q.ndim) if d not in axes)
    sb = jnp.expand_dims(scale, reduce_axes)
    return (q.astype(jnp.float32) * sb).astype(dtype)


# ----------------------------------------------------------------- dispatch


def eligible(dtype, nbytes: int, *, exact: bool = False) -> bool:
    """Static wire eligibility for one transfer: a floating payload (bool
    and integer payloads must arrive bit-exact; complex has no absmax
    grid) of at least ``HEAT_TPU_WIRE_MIN_BYTES``, from a caller that did
    not request ``exact=True``, with the wire plane on.  Ineligible
    transfers take today's f32 path with ZERO wire-arm table decisions."""
    if exact:
        return _note_declined()
    if mode() == "off":
        return False
    if not jnp.issubdtype(jnp.dtype(dtype), jnp.floating):
        return _note_declined()
    if jnp.dtype(dtype).itemsize <= 1:
        return _note_declined()  # already wire-minimal
    if int(nbytes) < min_bytes():
        return _note_declined()
    return True


def _note_declined() -> bool:
    _STATS["declined_static"] += 1
    return False


def decline(site: str) -> None:
    """Count one always-ineligible site consult (``tiled_take``: the
    gathered payload IS the data, and its ``psum_scatter`` sums across
    sources — per-source scales cannot survive the reduction)."""
    if mode() != "off":
        _STATS["declined_static"] += 1


def choose(site: str, geometry: tuple, desc: str = ""):
    """THE wire-arm consult for one ELIGIBLE dispatch: returns
    ``(arm, decision_or_None)``.  A forced mode (``HEAT_TPU_WIRE=int8|
    fp8``) returns its arm with no table decision; ``HEAT_TPU_AUTOTUNE=
    off`` means wire_f32 (the acceptance bit-for-bit restore); otherwise
    the autotune plane decides per (site, geometry, device kind) — the
    caller runs :func:`explore` when ``decision.explore`` is set."""
    m = mode()
    if m in ("int8", "fp8"):
        if m == "fp8" and not fp8_available():
            return "wire_f32", None
        return "wire_" + m, None
    if not autotune.enabled():
        return "wire_f32", None
    key = autotune.key("wire", site, *geometry)
    d = autotune.decide(
        key, "wire_f32", desc=desc or f"wire {site} {geometry}",
        arms=WIRE_ARMS,
    )
    return d.arm, d


def consume(site: str, geometry: tuple) -> str:
    """Consume-only consult for ELIGIBLE dispatches at sites that must
    not double-execute their program (the fused resplit tail, the lazy
    matmul chain): a forced mode applies directly; otherwise only an
    already-RESOLVED winner for the shared (site, geometry) key is
    served — the eager engine's explores of the same geometry warm it —
    and an unresolved key records the f32 prior.  Returns the wire mode
    string (``""`` | ``"int8"`` | ``"fp8"``)."""
    m = mode()
    if m in ("int8", "fp8"):
        if m == "fp8" and not fp8_available():
            return ""
        return m
    if not autotune.enabled():
        return ""
    key = autotune.key("wire", site, *geometry)
    w = autotune.winner(key)
    if w in ("wire_int8", "wire_fp8"):
        return w[len("wire_"):]
    if w is None:
        autotune.note_prior(key, "wire_f32", site=f"wire_{site}")
    return ""


def explore(decision, run_for) -> object:
    """One explore round at a wire site: run every arm under measurement
    — ``run_for(wire_mode)`` with ``""`` (f32), ``"int8"``, ``"fp8"`` —
    and return the f32 result, so numerics never depend on tuning state.
    An arm that cannot run (no fp8 dtype: :func:`qdtype` raises; a
    backend refusing the wire format) loses by forfeit."""
    out = autotune.explore(
        decision,
        {
            "wire_f32": functools.partial(run_for, ""),
            "wire_int8": functools.partial(run_for, "int8"),
            "wire_fp8": functools.partial(
                run_for if fp8_available() else qdtype, "fp8"
            ),
        },
        site="wire", forfeit=WIRE_ARMS[1:],
    )
    _STATS["explores"] += 1
    _STATS["by_arm"]["wire_f32"] += 1
    return out


def payload_nbytes(n_elems: int, n_scales: int, mode_str: str) -> int:
    """Exact on-wire byte model of one quantized transfer: 1-byte grid
    elements plus the f32 scales riding beside them."""
    return int(n_elems) * 1 + int(n_scales) * 4


def account(site: str, arm: str, logical_bytes: int, wire_bytes: int) -> None:
    """Ledger one quantized dispatch: the f32 bytes the wire WOULD have
    moved vs what the quantized format moved (``heat_tpu_wire_*``)."""
    _STATS["quantized_dispatches"] += 1
    _STATS["by_arm"][arm] += 1
    _STATS["bytes_logical"] += int(logical_bytes)
    _STATS["bytes_wire"] += int(wire_bytes)
    telemetry.record_event(
        "wire_dispatch", site=site, arm=arm,
        logical_bytes=int(logical_bytes), wire_bytes=int(wire_bytes),
    )
