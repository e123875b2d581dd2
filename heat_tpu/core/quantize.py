"""Weight quantization for the inference path (ROADMAP item 5).

The sharded GEMM is the universal hot path — every estimator,
``nn.functional.linear``, and the MoE FFN route through it — and the ring
matmul's fused :class:`~heat_tpu.parallel.overlap.Epilogue` was built one
step away from low-precision weights: per-channel scales are exactly
"a runtime extra sliced per out-split block", and the ring already
accumulates half-precision inputs in f32.  This module supplies that step:

* :func:`quantize_weights` → :class:`QuantizedDNDarray`: an int8 (or
  fp8 ``e4m3``) buffer with absmax-per-output-channel f32 scales stored
  beside it, both ledgered in memtrack so the residency win is
  attributed in ``live_buffers()`` / ``census()`` / ``bytes_by_dtype``.
  ``donate=True`` consumes the master through a ``donate_argnums``
  dispatch and poisons it for the use-after-donate sanitizer (on CPU the
  donation is a no-op, which is exactly why the poison matters — see
  ``analysis/sanitize.py``).

* :func:`matmul_quantized` / :func:`linear`: the quantized GEMM behind
  ``nn.functional.linear`` and ``linalg.basics.matmul``.  Dispatch rides
  the tuning plane as a ``("bf16", "int8")`` arm pair per (site,
  geometry, device kind) — :data:`QUANT_ARMS`:

  - **bf16** — dequantize, then the ordinary (itself ring-vs-GSPMD
    tuned) matmul.  This is the *reference* arm: explore calls return
    its result bitwise, and ``HEAT_TPU_AUTOTUNE=off`` restores it
    bit-for-bit with zero table decisions.
  - **int8** — the low-precision buffer rides the GEMM (the ring
    program's per-block ``astype`` is the only upcast; HBM and the ICI
    wire carry 1-byte elements), accumulation stays f32, and the
    per-channel scale + output cast fold into the ring epilogue as
    runtime extras — new checkpoints never retrace.

  Safe decline: traced operands (a grad/training path), unsupported
  layouts, and a failing int8 arm all fall back to bf16.  Winners
  persist through ``HEAT_TPU_AUTOTUNE_CACHE`` like every other arm.

* :func:`quantize_tensor` / :func:`quantize_params`: the raw-array tier
  for the MoE FFN (``parallel/expert.py``) — :class:`QuantizedTensor` is
  a registered pytree so quantized expert weights pass through
  ``shard_map`` / jit boundaries unchanged.

Exactness at shard boundaries is inherited, not re-proven: the ring
masks both operands' k-pads to exact zeros and re-zeros out-split pad
rows after the epilogue, so a mesh-4 quantized product equals the
mesh-1 one to accumulation-order tolerance (pinned by the law tests).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from . import autotune, memtrack, telemetry, types, wire as _wire
from .dndarray import DNDarray, _ensure_split
from ..analysis import sanitize

__all__ = [
    "QUANT_ARMS",
    "QuantizedDNDarray",
    "QuantizedTensor",
    "dequantize_tensor",
    "linear",
    "matmul_quantized",
    "quantize_params",
    "quantize_tensor",
    "quantize_weights",
    "stats",
    "tuned_arm",
]

# absmax-per-channel maps onto the quantized grid's largest magnitude.
# The grid math lives in core/wire.py now (round 17 made it the shared
# tile-quant helper of the quantized-collective wire formats); these
# aliases keep this module's surface stable.
_QMAX = _wire.QMAX
_qdtype = _wire.qdtype

# round 16: quantized inference epilogues — "bf16" is the
# dequantize-then-dispatch reference (bitwise the unquantized flow over
# the same dequantized values), "int8" keeps the low-precision buffer
# through the GEMM with the per-channel scale folded into the ring
# epilogue.  The reference arm name stays "bf16" for fp8 entries too:
# the arm names the REFERENCE precision class, not the storage.
QUANT_ARMS = ("bf16", "int8")


_STATS = telemetry.register_group(
    "quantize",
    {
        "quantized": 0,       # quantize_weights / quantize_tensor calls
        "donated": 0,         # masters consumed via donate=True
        "dequantized": 0,     # full-weight dequants (the bf16 arm's cost)
        "matmuls": 0,         # matmul_quantized entries
        "by_arm": {"bf16": 0, "int8": 0},
        "declines": 0,        # safe declines straight to bf16 (tracer, off)
        "int8_fallbacks": 0,  # int8 arm failed at run time -> bf16 rescue
    },
)


def stats() -> dict:
    """Snapshot of the ``quantize`` counter group (Prometheus:
    ``heat_tpu_quantize_*``)."""
    return telemetry.snapshot_group("quantize")


# ------------------------------------------------------------ raw-array tier


@functools.partial(jax.jit, static_argnames=("qdt", "axes"))
def _quantize_arr(w, *, qdt, axes):
    return _quantize_body(w, qdt, axes)


@functools.partial(
    jax.jit, static_argnames=("qdt", "axes"), donate_argnums=(0,)
)
def _quantize_arr_donating(w, *, qdt, axes):
    return _quantize_body(w, qdt, axes)


def _quantize_body(w, qdt, axes):
    """absmax-per-channel quantization: reduce |w| over every non-kept
    axis, snap to the grid.  ``axes`` is the tuple of KEPT (channel)
    axes — ``(1,)`` for a 2-D weight's columns, ``(0, 2)`` for
    per-(expert, channel) scales on a 3-D MoE weight.  Scales stay f32;
    all-zero channels get scale 1 so the dequant is exact zeros, never
    0/0.  One grid, one implementation: this is the same
    ``wire.absmax_encode`` the quantized collectives ship tiles through,
    so a weight quantized here and a tile quantized on the wire agree
    bit-for-bit on the same values."""
    mode = "int8" if qdt == jnp.dtype(jnp.int8) else "fp8"
    return _wire.absmax_encode(w, mode, axes)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class QuantizedTensor:
    """Raw-array quantized weight: ``q`` (int8/fp8), f32 ``scale`` with
    one entry per channel over the kept ``axes``, and the master's dtype
    for the round trip.  A registered pytree — passes through jit /
    shard_map boundaries, so the MoE FFN's expert weights can be
    quantized once and served."""

    q: Any
    scale: Any
    axes: Tuple[int, ...]
    orig_dtype: str

    @property
    def shape(self) -> tuple:
        return tuple(self.q.shape)

    @property
    def ndim(self) -> int:
        return self.q.ndim

    @property
    def nbytes(self) -> int:
        return int(self.q.nbytes) + int(self.scale.nbytes)

    def tree_flatten(self):
        return (self.q, self.scale), (self.axes, self.orig_dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0], aux[1])

    def scale_broadcast(self):
        """The scale shaped to broadcast against ``q``."""
        reduce_axes = tuple(
            d for d in range(self.q.ndim) if d not in self.axes
        )
        return jnp.expand_dims(self.scale, reduce_axes)


def _norm_axes(axis, ndim: int) -> Tuple[int, ...]:
    axes = (axis,) if isinstance(axis, int) else tuple(axis)
    return tuple(sorted(a % ndim for a in axes))


def quantize_tensor(w, dtype: str = "int8", *, axis=-1) -> QuantizedTensor:
    """Quantize one raw jax array with absmax scales per channel along
    ``axis`` — an int, or a tuple of kept axes (the MoE expert weights
    ``(E, d, h)``/``(E, h, d)`` use ``axis=(0, 2)`` for per-(expert,
    out-channel) scales)."""
    qdt = _qdtype(dtype)
    w = jnp.asarray(w)
    axes = _norm_axes(axis, w.ndim)
    q, scale = _quantize_arr(w, qdt=qdt, axes=axes)
    if not _is_traced(q):  # call-time quantize inside a jit trace
        memtrack.register_buffer(q, tag="leaf")
        memtrack.register_buffer(scale, tag="leaf")
    _STATS["quantized"] += 1
    return QuantizedTensor(q, scale, axes, str(w.dtype))


def dequantize_tensor(qt: QuantizedTensor):
    """Round-trip a :class:`QuantizedTensor` back to its master dtype."""
    _STATS["dequantized"] += 1
    out = qt.q.astype(jnp.float32) * qt.scale_broadcast()
    return out.astype(jnp.dtype(qt.orig_dtype))


def quantize_params(
    params,
    dtype: str = "int8",
    *,
    targets: Tuple[str, ...] = ("w_in", "w_out"),
    axis=(0, 2),
):
    """Walk a (flax-style) nested param dict and replace every leaf whose
    key is in ``targets`` with a :class:`QuantizedTensor`.  Returns a new
    tree; untouched leaves are shared, not copied.  The quantized tree
    feeds :func:`~heat_tpu.parallel.expert.moe_ffn` directly — flax's
    ``apply`` param-shape check predates pytree-valued params, so serve
    through the functional entry, not ``Module.apply``."""
    if not isinstance(params, dict):
        return params
    out = {}
    for key, val in params.items():
        if isinstance(val, dict):
            out[key] = quantize_params(
                val, dtype, targets=targets, axis=axis
            )
        elif key in targets and hasattr(val, "ndim"):
            out[key] = quantize_tensor(val, dtype, axis=axis)
        else:
            out[key] = val
    return out


# ----------------------------------------------------------- DNDarray tier


class QuantizedDNDarray:
    """Per-output-channel-scaled low-precision weight with DNDarray-style
    metadata (gshape / split / device / comm), deliberately NOT a
    :class:`~heat_tpu.core.dndarray.DNDarray` subclass: the quantized
    buffer must never wander into the generic op surface — only the
    GEMM consumers (``matmul_quantized``, the ring cdist) and
    :meth:`dequantize` understand it."""

    __slots__ = ("q", "scale", "axis", "orig_dtype", "gshape", "split",
                 "device", "comm")

    def __init__(self, q, scale, axis, orig_dtype, gshape, split, device,
                 comm):
        self.q = q                    # logical low-precision buffer
        self.scale = scale            # f32, (gshape[axis],)
        self.axis = int(axis)         # the per-channel axis
        self.orig_dtype = orig_dtype  # heat type of the master
        self.gshape = tuple(gshape)
        self.split = split
        self.device = device
        self.comm = comm

    # -- DNDarray-flavored metadata ------------------------------------
    @property
    def shape(self) -> tuple:
        return self.gshape

    @property
    def ndim(self) -> int:
        return len(self.gshape)

    @property
    def dtype(self):
        """The MASTER's heat type — what consumers compute in/return."""
        return self.orig_dtype

    @property
    def qdtype(self) -> str:
        return str(self.q.dtype)

    @property
    def nbytes(self) -> int:
        return int(self.q.nbytes) + int(self.scale.nbytes)

    def __repr__(self) -> str:
        return (
            f"QuantizedDNDarray(shape={self.gshape}, q={self.qdtype}, "
            f"channel_axis={self.axis}, split={self.split}, "
            f"master={self.orig_dtype.__name__})"
        )

    # -- ops ------------------------------------------------------------
    def dequantize(self) -> DNDarray:
        """Back to a master-dtype DNDarray (the bf16 arm's operand)."""
        _STATS["dequantized"] += 1
        reduce_axes = tuple(
            d for d in range(self.ndim) if d != self.axis
        )
        sb = jnp.expand_dims(self.scale, reduce_axes)
        w = (self.q.astype(jnp.float32) * sb).astype(
            self.orig_dtype.jax_type()
        )
        out = DNDarray(
            w, self.gshape, self.orig_dtype, self.split, self.device,
            self.comm,
        )
        return _ensure_split(out, self.split)

    def transpose(self) -> "QuantizedDNDarray":
        """2-D transpose: the channel axis and split follow the permute
        (the ``F.linear`` ``(out, in)`` → ``(in, out)`` hop)."""
        if self.ndim != 2:
            raise ValueError("QuantizedDNDarray.transpose is 2-D only")
        split = None if self.split is None else 1 - self.split
        return QuantizedDNDarray(
            self.q.T, self.scale, 1 - self.axis, self.orig_dtype,
            (self.gshape[1], self.gshape[0]), split, self.device, self.comm,
        )

    @property
    def T(self) -> "QuantizedDNDarray":
        return self.transpose()


def quantize_weights(
    w: DNDarray,
    dtype: str = "int8",
    *,
    axis: int = 0,
    donate: bool = False,
) -> QuantizedDNDarray:
    """Quantize a weight DNDarray to int8/fp8 with absmax scales per
    ``axis`` channel (default 0 — torch's ``(out_features, in_features)``
    linear convention).  The quantized buffer and its scales are
    memtrack-ledgered, so the residency win shows up in
    ``live_buffers()`` / ``census()["bytes_by_dtype"]``.

    ``donate=True`` hands the master to XLA via ``donate_argnums`` and
    poisons it for the use-after-donate sanitizer: reading ``w`` (or its
    buffer) afterwards raises under ``HEAT_TPU_SANITIZE=1`` and is
    flagged by lint HT005 — on TPU that read is silent corruption."""
    from . import sanitation

    sanitation.sanitize_in(w)
    qdt = _qdtype(dtype)
    axis = axis % w.ndim
    master = w.larray
    phys = w.parray
    fn = _quantize_arr_donating if donate else _quantize_arr
    q, scale = fn(master, qdt=qdt, axes=(axis,))
    memtrack.register_buffer(q, tag="leaf")
    memtrack.register_buffer(scale, tag="leaf")
    _STATS["quantized"] += 1
    if donate:
        _STATS["donated"] += 1
        site = "quantize.quantize_weights(donate=True)"
        memtrack.tag_buffer(master, "donated")
        sanitize.poison(master, donated_site=site)
        if phys is not master:
            memtrack.tag_buffer(phys, "donated")
            sanitize.poison(phys, donated_site=site)
    telemetry.record_event(
        "quantize",
        dtype=str(qdt),
        shape=tuple(w.shape),
        axis=axis,
        donate=bool(donate),
        master_nbytes=int(master.nbytes),  # ht: HT002 ok — .nbytes is shape metadata, no device readback
        quant_nbytes=int(q.nbytes) + int(scale.nbytes),  # ht: HT002 ok — .nbytes is shape metadata, no device readback
    )
    return QuantizedDNDarray(
        q, scale, axis, w.dtype, tuple(w.shape), w.split, w.device, w.comm,
    )


# ------------------------------------------------------------ arm dispatch


def _is_traced(value) -> bool:
    return isinstance(value, jax.core.Tracer)


def tuned_arm(
    site: str,
    geometry: tuple,
    bf16_fn: Callable[[], Any],
    int8_fn: Callable[[], Any],
    *,
    desc: str = "",
    arm: Optional[str] = None,
):
    """THE quantized-arm dispatch: per (site, geometry, device kind),
    explore runs BOTH arms under measurement and returns the bf16
    (reference) result bitwise; a resolved winner runs alone; the tuning
    plane off means bf16, bit-for-bit, zero table decisions.  ``arm``
    forces one arm (law tests / benchmarks).  An int8 arm that raises
    falls back to bf16 — quantization must never turn a working call
    into an error."""
    if arm is not None:
        if arm not in QUANT_ARMS:
            raise ValueError(f"arm must be one of {QUANT_ARMS}")
        _STATS["by_arm"][arm] += 1
        return int8_fn() if arm == "int8" else bf16_fn()
    if not autotune.enabled():
        _STATS["declines"] += 1
        _STATS["by_arm"]["bf16"] += 1
        return bf16_fn()
    key = autotune.key("quant", site, *geometry)
    decision = autotune.decide(
        key, "bf16", desc=desc or f"{site} {geometry}", arms=QUANT_ARMS,
    )
    if decision.explore:
        # an int8 arm that cannot run loses by forfeit (bounded explore)
        out = autotune.explore(
            decision, {"bf16": bf16_fn, "int8": int8_fn},
            site="quantize", forfeit=("int8",),
        )
        _STATS["by_arm"]["bf16"] += 1
        return out
    if decision.arm == "int8":
        try:
            result = int8_fn()
        except Exception:
            _STATS["int8_fallbacks"] += 1
            telemetry.record_event(
                "fallback", site="quantize." + site, reason="int8-arm-error",
            )
            _STATS["by_arm"]["bf16"] += 1
            return bf16_fn()
        _STATS["by_arm"]["int8"] += 1
        return result
    _STATS["by_arm"]["bf16"] += 1
    return bf16_fn()


# ------------------------------------------------------------- matmul tier


@functools.partial(jax.jit, static_argnames=("comp", "out_dt"))
def _gspmd_quant_mm(x, q, scale, *, comp, out_dt):
    """The int8 arm's GSPMD form (the ring's decline target): one einsum
    over the low-precision buffer with f32+ accumulation, scale and cast
    fused in the same program."""
    out = jnp.matmul(x.astype(comp), q.astype(comp))
    return (out * scale).astype(out_dt)


def matmul_quantized(
    x: DNDarray,
    qw: QuantizedDNDarray,
    out_split="auto",
    *,
    arm: Optional[str] = None,
) -> DNDarray:
    """``x @ qw`` for a 2-D quantized right operand whose channel axis is
    the output (column) axis.  Arm dispatch per the module docstring;
    the int8 arm goes ring-first (`overlap.matmul_raw` with the scale +
    cast folded into the :class:`~heat_tpu.parallel.overlap.Epilogue`)
    and declines to the fused GSPMD einsum."""
    from ..parallel import overlap as _overlap

    if qw.ndim != 2 or x.ndim != 2:
        raise ValueError(
            f"matmul_quantized is 2-D only, got {x.shape} @ {qw.shape}"
        )
    if qw.axis != 1:
        raise ValueError(
            "matmul_quantized needs the channel axis on the output "
            "(column) axis of the right operand — transpose the "
            f"QuantizedDNDarray first (channel axis is {qw.axis})"
        )
    m, k = x.shape
    k2, n = qw.shape
    if k != k2:
        raise ValueError(
            f"matmul_quantized: inner dimensions do not match: "
            f"{x.shape} @ {qw.shape}"
        )
    _STATS["matmuls"] += 1
    if out_split == "auto":
        out_split = 0 if x.split == 0 else (1 if qw.split == 1 else None)
    out_ht = types.promote_types(x.dtype, qw.orig_dtype)
    out_dt = jnp.dtype(out_ht.jax_type())
    comp = jnp.promote_types(x.larray.dtype, jnp.float32)

    def _bf16() -> DNDarray:
        from .linalg import basics

        return basics.matmul(x, qw.dequantize())

    def _int8() -> DNDarray:
        ep = _overlap.Epilogue(scale=qw.scale, dtype=out_dt)
        out = _overlap.matmul_raw(
            x.comm, x.parray, qw.q, (m, k), (k, n), x.split, qw.split,
            out_split, comp_dtype=comp, epilogue=ep,
        )
        if out is None:
            out = _gspmd_quant_mm(
                x.larray, qw.q, qw.scale, comp=comp, out_dt=out_dt,
            )
        wrapped = DNDarray(
            out, (m, n), out_ht, out_split, x.device, x.comm,
        )
        return _ensure_split(wrapped, out_split)

    if arm is None and (_is_traced(x.larray) or _is_traced(qw.q)):
        # a grad/training trace must not explore, time, or mutate tables
        _STATS["declines"] += 1
        return _bf16()
    geometry = (m, k, n, x.comm.size, str(comp), x.split, qw.split,
                out_split, qw.qdtype)
    return tuned_arm(
        "linear", geometry, _bf16, _int8,
        desc=f"linear {m}x{k}x{n} {qw.qdtype} S={x.comm.size}",
        arm=arm,
    )


def linear(x: DNDarray, qw: QuantizedDNDarray, bias=None) -> DNDarray:
    """Quantized ``F.linear``: ``x @ qw.T + bias`` with ``qw`` in torch's
    ``(out_features, in_features)`` layout (channel axis 0)."""
    if qw.ndim != 2 or qw.axis != 0:
        raise ValueError(
            "linear expects a (out_features, in_features) quantized "
            f"weight with channel axis 0, got shape {qw.shape} axis "
            f"{qw.axis}"
        )
    out = matmul_quantized(x, qw.transpose())
    if bias is not None:
        out = out + bias
    return out
