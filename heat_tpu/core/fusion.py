"""Fused op-chain execution: lazy expressions + a sharding-aware compile cache.

The reference executes one torch call (plus one optional MPI collective) per
operator; the eager port kept that shape, so a chain like ``(x - mu) / sd``
dispatches N separate XLA programs with no cross-op fusion, and re-traces
whenever the same chain recurs through a new Python call path.  This module
makes the `_operations.py` workhorses *lazy*: elementwise ops, dtype casts,
``where=`` masks and trailing reductions accumulate into a small op-DAG (an
:class:`Expr` per node), and the whole DAG lowers as ONE jitted XLA
computation at a materialization boundary — ``.larray`` access, a
split-changing op, I/O, or a comparison used in Python control flow (all of
which read the mangled ``_DNDarray__array`` slot and therefore funnel through
:class:`LazyDNDarray.__getattr__`).

Compiled executables are cached under
``(op-graph fingerprint, leaf avals + NamedShardings, target layout)`` with
hit/miss counters exposed via :func:`cache_stats`, so steady-state serving
traffic pays zero retrace.  Scalars enter the graph as 0-d array *inputs*
(never baked constants): the fingerprint is value-independent and a chain
re-run with a different scalar is a cache hit.

Donation-awareness: inside a fused program the intermediates of the chain
never materialize (XLA reuses their buffers), the pad-to-physical +
``with_sharding_constraint`` finalization happens in-program instead of as a
separate dispatch, and the compile layer honors ``donate`` indices
(``jax.jit(donate_argnums=...)``) for callers that hand over a dead input
buffer.  The engine also cooperates with the PR-1 transport engine's
donating ``resplit_``: leaf buffers captured by still-pending expressions
are *pinned* (:func:`safe_to_donate`) so a donating in-place resplit cannot
invalidate a lazy chain built before it.

``HEAT_TPU_FUSE=off`` (or ``0``/``false``) restores fully eager execution
for debugging; :func:`fuse` is the scoped equivalent.

Guardrails (round 8, ISSUE 3): fused execution degrades instead of dying.
A compile or execution failure of the fused program (an XLA error, a
lowering bug) no longer propagates — :func:`_run` falls back to per-op
eager evaluation of the same linearized DAG, and :func:`cache_stats`
breaks the ``fallbacks`` total down by reason (``unfusable``,
``compile_error``, ``exec_error``, ``guard_replay``).  With the
non-finite guard on (``HEAT_TPU_GUARD``, :mod:`heat_tpu.core.guard`),
every op node records the user source line that built it, and a
materialized chain whose finite inputs produced NaN/Inf is replayed
eagerly op-by-op to raise :class:`~heat_tpu.core.guard.NonFiniteError`
naming the first offending op and its originating line.  Provenance is
excluded from the compile-cache key, so guarding adds zero retraces.
"""

from __future__ import annotations

import os
import time
import warnings
import weakref
from collections import OrderedDict
from contextlib import contextmanager
from typing import Callable, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from . import guard, memtrack, telemetry, types
from ..analysis import program_audit, sanitize
from .dndarray import DNDarray, _physical_dim
from .guard import NonFiniteError

__all__ = [
    "LazyDNDarray",
    "NonFiniteError",
    "Unfusable",
    "cache_stats",
    "defer",
    "describe",
    "enabled",
    "fuse",
    "last_hlo",
    "leaf",
    "leaf_from",
    "materialize",
    "materialize_all",
    "materialize_resplit",
    "node",
    "op_name",
    "register_op",
    "register_split_terminator",
    "register_terminator",
    "reset_cache",
    "safe_to_donate",
    "set_enabled",
]


# --------------------------------------------------------------- env switch

def _env_enabled() -> bool:
    return os.environ.get("HEAT_TPU_FUSE", "on").strip().lower() not in (
        "off", "0", "false", "no",
    )


_ENABLED = _env_enabled()


def enabled() -> bool:
    """Whether the lazy fusion engine is active (``HEAT_TPU_FUSE``)."""
    return _ENABLED


def set_enabled(flag: bool) -> bool:
    """Switch the engine on/off; returns the previous state."""
    global _ENABLED
    prev = _ENABLED
    _ENABLED = bool(flag)
    return prev


@contextmanager
def fuse(flag: bool = True):
    """Scoped :func:`set_enabled` (``with fusion.fuse(False): ...``)."""
    prev = set_enabled(flag)
    try:
        yield
    finally:
        set_enabled(prev)


class Unfusable(Exception):
    """Raised while building a lazy node when the op cannot enter the DAG
    (unhashable static kwargs, shape inference failure, mixed meshes).
    Callers fall back to the eager path — which either succeeds or raises
    the proper user-facing error."""


# ----------------------------------------------------------------- op table
# Registered metadata for the fns that flow through the engine: a stable
# display name (fingerprints key on the function OBJECT — qualnames are
# unsafe, closures with different static state share them) and a kind tag.
# Registration is optional: unregistered callables fuse too, they just
# print as their __name__ in describe()/debug output.

_OP_TABLE: "dict[Callable, Tuple[str, str]]" = {}


def register_op(fn: Callable, name: str, kind: str = "elementwise") -> Callable:
    """Record display metadata for ``fn`` (see arithmetics/relational/logical
    module bottoms for the standard tables)."""
    _OP_TABLE[fn] = (name, kind)
    return fn


def op_name(fn: Callable) -> str:
    meta = _OP_TABLE.get(fn)
    if meta is not None:
        return meta[0]
    return getattr(fn, "__name__", repr(fn))


# -------------------------------------------------------------- buffer pins
# id(array) -> live-pin count.  A pin means: some still-pending Expr leaf
# holds this exact jax.Array strongly (so the id cannot be recycled while
# the entry exists).  resplit_ consults safe_to_donate() before handing the
# buffer to the transport engine's donating all-to-all.

_PINNED: "dict[int, int]" = {}
# buf_id -> weakrefs to the pinning Exprs; diagnostic shadow of _PINNED
# that lets memtrack's leak detector tell "pin whose owner is gone but the
# finalize never fired" from a legitimately live pin
_PIN_OWNERS: "dict[int, list]" = {}


def _unpin(buf_id: int) -> None:
    n = _PINNED.get(buf_id, 0) - 1
    owners = _PIN_OWNERS.get(buf_id)
    if owners:
        # drop a dead owner ref if one exists (this finalize just killed
        # its Expr), else the newest — the count is what's authoritative
        for i, r in enumerate(owners):
            if r() is None:
                del owners[i]
                break
        else:
            owners.pop()
        if not owners:
            _PIN_OWNERS.pop(buf_id, None)
    if n > 0:
        _PINNED[buf_id] = n
    else:
        _PINNED.pop(buf_id, None)


def _pin(expr: "Expr", value) -> None:
    buf_id = id(value)
    _PINNED[buf_id] = _PINNED.get(buf_id, 0) + 1
    _PIN_OWNERS.setdefault(buf_id, []).append(weakref.ref(expr))
    weakref.finalize(expr, _unpin, buf_id)
    memtrack.tag_buffer(value, "pinned")


def pin_leaks() -> "list[dict]":
    """Pins whose owning Exprs are (partly) gone: for each pinned buffer,
    compare the live-owner count against the pin count — a shortfall means
    an Expr died without its finalize releasing the pin (the leak class
    ``telemetry.leaks()`` exists to catch).  Empty in a healthy process."""
    out = []
    for buf_id, count in _PINNED.items():
        live = sum(1 for r in _PIN_OWNERS.get(buf_id, ()) if r() is not None)
        if live < count:
            out.append({"buf_id": buf_id, "pins": count, "live_owners": live})
    return out


def safe_to_donate(value) -> bool:
    """False iff a pending lazy expression still references ``value`` as a
    leaf — donating it would turn later materialization into a
    use-after-free (``Array has been deleted``)."""
    return id(value) not in _PINNED


# ------------------------------------------------------------------ op-DAG

class Expr:
    """One node of the lazy DAG.

    Leaf: ``value`` is a concrete jax.Array (physical — possibly padded — or
    logical) and ``lshape`` its logical shape.  Op node: ``fn`` applied to
    ``args`` with static ``kwargs``; ``aval`` is the eval_shape-predicted
    result.  Materialization *leafifies* the node in place (sets ``value``,
    drops ``fn``/``args``) so diamond DAGs never recompute a subchain.

    ``site`` is the user source line that built the node (guard.py
    provenance, ``None`` with the guard off or for internal builders).  It
    is diagnostic-only: never part of the compile-cache key."""

    __slots__ = ("fn", "args", "kwargs", "aval", "value", "lshape", "site", "__weakref__")

    def __init__(self, fn, args, kwargs, aval, value=None, lshape=None, site=None):
        self.fn = fn
        self.args = args
        self.kwargs = kwargs
        self.aval = aval
        self.value = value
        self.lshape = lshape
        self.site = site

    def leafify(self, value, lshape) -> None:
        self.value = value
        self.lshape = tuple(lshape)
        self.fn = None
        self.args = ()
        self.kwargs = None


def leaf(value, lshape=None, pin: bool = False) -> Expr:
    """Wrap a concrete jax array as a DAG leaf.  ``lshape`` is the logical
    shape when ``value`` carries even-chunk physical padding."""
    lshape = tuple(value.shape) if lshape is None else tuple(lshape)
    aval = jax.ShapeDtypeStruct(lshape, value.dtype)
    e = Expr(None, (), None, aval, value=value, lshape=lshape)
    if pin:
        _pin(e, value)
    return e


def leaf_from(x: DNDarray) -> Expr:
    """Leaf (or pending sub-DAG) for a DNDarray operand.  Lazy handles
    contribute their expression — consumer chains extend the producer's DAG
    instead of forcing it.  Concrete handles contribute their *physical*
    array (the program slices the pad off), pinned against donation."""
    if isinstance(x, LazyDNDarray) and "_DNDarray__array" not in x.__dict__:
        e = x._expr
        if e is not None:
            return e
    return leaf(x.parray, x.gshape, pin=True)


def _kwargs_key(kwargs) -> tuple:
    if not kwargs:
        return ()
    try:
        items = tuple(sorted(kwargs.items(), key=lambda kv: kv[0]))
        hash(items)
    except TypeError as err:
        raise Unfusable(f"unhashable static kwargs: {kwargs!r}") from err
    return items


# eval_shape is O(1) per op but not free; memoize on (fn, child avals,
# static kwargs).  LRU-capped: stray per-call closures must not grow it
# unboundedly over a long serving process.
_AVAL_MEMO: "OrderedDict[tuple, jax.ShapeDtypeStruct]" = OrderedDict()
_AVAL_MEMO_MAX = 4096


def _infer_aval(fn, child_avals, kw_key):
    key = (fn, tuple((a.shape, str(a.dtype)) for a in child_avals), kw_key)
    try:
        out = _AVAL_MEMO[key]
        _AVAL_MEMO.move_to_end(key)
        return out
    except KeyError:
        pass
    except TypeError as err:  # unhashable fn
        raise Unfusable(f"unhashable op {fn!r}") from err
    kwargs = dict(kw_key)
    try:
        out = jax.eval_shape(lambda *xs: fn(*xs, **kwargs), *child_avals)
    except Unfusable:
        raise
    except Exception as err:
        raise Unfusable(f"shape inference failed for {op_name(fn)}: {err}") from err
    if not isinstance(out, jax.ShapeDtypeStruct):
        raise Unfusable(f"{op_name(fn)} does not return a single array")
    _AVAL_MEMO[key] = out
    if len(_AVAL_MEMO) > _AVAL_MEMO_MAX:
        _AVAL_MEMO.popitem(last=False)
    return out


def node(fn: Callable, args: Tuple[Expr, ...], **kwargs) -> Expr:
    """Apply ``fn`` lazily to child nodes with static ``kwargs``.  Metadata
    (shape/dtype) is predicted via ``jax.eval_shape`` — no execution.  With
    the guard on, the user source line that built the op rides along for
    non-finite provenance."""
    kw_key = _kwargs_key(kwargs)
    aval = _infer_aval(fn, tuple(a.aval for a in args), kw_key)
    site = guard.capture_site(2) if guard.enabled() else None
    return Expr(fn, tuple(args), kw_key, aval, site=site)


def _astype(t, dtype):
    return t.astype(dtype)


register_op(_astype, "astype", kind="cast")


def cast_node(child: Expr, dtype) -> Expr:
    """Lazy dtype cast (fuses into the chain; no array-sized copy)."""
    if str(child.aval.dtype) == str(jnp.dtype(dtype)):
        return child
    return node(_astype, (child,), dtype=jnp.dtype(dtype))


def _render_instrs(instrs, leaves, out_slots, upto=None, mark=None) -> str:
    """Shared renderer behind :func:`describe` and the guard's offending-
    subtree report.  ``upto`` truncates after that slot; ``mark`` annotates
    one slot (the first non-finite producer).

    A slot consumed more than once — by several op nodes, several program
    outputs, or both — is a shared subexpression: it renders ONCE, tagged
    ``<<shared xN>>`` with its consumer count, instead of being re-printed
    per consumer (the instruction list is already in deduplicated form, so
    re-printing would misreport the program as executing it N times)."""
    if isinstance(out_slots, int):
        out_slots = (out_slots,)
    refs: "dict[int, int]" = {}
    for ins in instrs:
        if ins[0] == "O":
            for c in ins[3]:
                refs[c] = refs.get(c, 0) + 1
    for s in out_slots:
        refs[s] = refs.get(s, 0) + 1
    last = len(instrs) - 1 if upto is None else int(upto)
    lines = []
    for i, ins in enumerate(instrs[: last + 1]):
        if ins[0] == "L":
            lf = leaves[ins[1]]
            line = f"%{i} = leaf{tuple(lf.lshape)}:{lf.value.dtype}"
        else:
            _, fn, kw, ch = ins
            kws = f" {dict(kw)}" if kw else ""
            line = f"%{i} = {op_name(fn)}({', '.join('%%%d' % c for c in ch)}){kws}"
        if refs.get(i, 0) > 1:
            line += f"   <<shared x{refs[i]}>>"
        if mark is not None and i == mark:
            line += "   <-- first non-finite"
        lines.append(line)
    if upto is None:
        lines.append("return " + ", ".join(f"%{s}" for s in out_slots))
    else:
        lines.append(f"return %{last}")
    return "\n".join(lines)


def describe(*exprs) -> str:
    """Human-readable postorder rendering of one or more DAG roots
    (debugging aid).  Accepts :class:`Expr` roots or (lazy) DNDarrays;
    several roots render as ONE deduplicated instruction list with a
    multi-value ``return`` — exactly the program :func:`materialize_all`
    would compile — and subtrees consumed more than once carry a
    ``<<shared xN>>`` ref-mark instead of being printed per consumer."""
    roots = []
    for e in exprs:
        if isinstance(e, Expr):
            roots.append(e)
        elif isinstance(e, DNDarray):
            roots.append(leaf_from(e))
        else:
            raise TypeError(f"describe() takes Expr or DNDarray, got {type(e)}")
    instrs, _, leaves, out_slots = _linearize(*roots)
    return _render_instrs(instrs, leaves, out_slots)


# -------------------------------------------------- fingerprint + lowering

def _linearize(*roots: Expr):
    """Postorder-linearize one or more DAG roots into
    ``(instrs, sites, leaves, out_slots)``.

    ``instrs`` is the canonical serialization the compile cache keys on:
    leaves become ``("L", leaf_index)`` numbered by first encounter, op
    nodes ``("O", fn, kwargs_key, child_slots)``.  All roots share ONE
    instruction list — ``out_slots`` names each root's result slot — so a
    subtree reachable from several roots is scheduled exactly once.

    Deduplication is two-level.  Node identity: a diamond (the same
    ``Expr`` object reached twice) serializes once.  Structural CSE: two
    *distinct* op nodes with the same fingerprint — op object, kwargs key,
    child slots, the same scheme the cache key uses — collapse to one
    slot, so independently built copies of a subexpression (``mean`` and
    ``var`` each re-deriving ``(x - mu)``) execute once inside the fused
    program.  Every op-node reuse from either level counts as a
    ``cse_hits`` event in :func:`cache_stats`.

    ``sites`` is the parallel per-slot provenance (guard.py user lines) —
    kept OUT of ``instrs`` so the same chain built from two source
    locations shares one cache entry; a structurally merged node keeps the
    site of its first builder."""
    instrs = []
    sites = []
    leaves = []
    slot: "dict[int, int]" = {}
    leaf_slot: "dict[tuple, int]" = {}
    struct_slot: "dict[tuple, int]" = {}
    keepalive = []  # id()-keyed dict needs the nodes alive for the walk

    def visit(n: Expr) -> int:
        nid = id(n)
        hit = slot.get(nid)
        if hit is not None:
            if instrs[hit][0] == "O":
                _STATS["cse_hits"] += 1
            return hit
        keepalive.append(n)
        if n.value is not None:
            # two leaf nodes wrapping the same buffer collapse to one
            # program input (x appearing twice in a chain is one arg)
            lk = (id(n.value), tuple(n.lshape))
            if lk in leaf_slot:
                slot[nid] = leaf_slot[lk]
                return slot[nid]
            leaves.append(n)
            instrs.append(("L", len(leaves) - 1))
            sites.append(n.site)
            leaf_slot[lk] = len(instrs) - 1
        else:
            ch = tuple(visit(c) for c in n.args)
            sk = (n.fn, n.kwargs, ch)
            hit = struct_slot.get(sk)
            if hit is not None:
                _STATS["cse_hits"] += 1
                slot[nid] = hit
                return hit
            instrs.append(("O", n.fn, n.kwargs, ch))
            sites.append(n.site)
            struct_slot[sk] = len(instrs) - 1
        slot[nid] = len(instrs) - 1
        return slot[nid]

    out_slots = tuple(visit(r) for r in roots)
    return tuple(instrs), tuple(sites), leaves, out_slots


def _build_program(
    instrs, out_slots, lshapes, gshapes, splits, nshards, targets, with_guard=False
):
    """The single fused computation for one cache entry: slice leaf pads to
    logical, evaluate the DAG once, and — for EVERY output slot — pad the
    result to its physical shape and pin its canonical NamedSharding; the
    whole `_ensure_split` finalization happens *inside* the program instead
    of as a separate dispatch.  Returns a flat tuple, one array per root.
    A subtree feeding several roots executes once (the instruction list is
    already in deduplicated form).

    ``with_guard=True`` folds the non-finite guard's reduction into the
    SAME executable: the program appends one joint ``allfinite`` scalar
    (AND over all outputs) to the tuple, so the guard costs zero extra
    dispatches on the hot path (a separate jitted isfinite program
    measured ~10x the acceptable tax on the CPU CI mesh).  Guard-off
    programs are byte-identical to the unguarded build."""

    def ht_fused(*vals):  # names the XLA module: jit_ht_fused
        env = []
        for ins in instrs:
            if ins[0] == "L":
                v = vals[ins[1]]
                ls = lshapes[ins[1]]
                if tuple(v.shape) != ls:
                    v = v[tuple(slice(0, n) for n in ls)]
                env.append(v)
            else:
                _, fn, kw, ch = ins
                # one device scope per op node (trace-time only), so the
                # profiler's device events carry the library's op names
                with jax.named_scope("ht.fused/" + op_name(fn)):
                    env.append(fn(*[env[c] for c in ch], **dict(kw or ())))
        outs = []
        flag = jnp.asarray(True) if with_guard else None
        for out_slot, gshape, split, target in zip(out_slots, gshapes, splits, targets):
            out = env[out_slot]
            if with_guard and jnp.issubdtype(jnp.result_type(out), jnp.inexact):
                # on the logical (pre-pad) output: pad zeros are always finite
                with jax.named_scope("ht.fused/guard.isfinite"):
                    flag = jnp.logical_and(flag, jnp.all(jnp.isfinite(out)))
            if split is not None and gshape:
                n = gshape[split]
                pn = _physical_dim(n, nshards)
                if pn != n:
                    pad = [(0, 0)] * len(gshape)
                    pad[split] = (0, pn - n)
                    out = jnp.pad(out, pad)
            out = jax.lax.with_sharding_constraint(out, target)
            outs.append(out)
        return tuple(outs) + ((flag,) if with_guard else ())

    return ht_fused


# --------------------------------------------------------- chain terminators
# Schedule-controlled engines (parallel/overlap.py's collective matmul)
# register a *lowerer* consulted at compile-cache misses, before the generic
# GSPMD program is built.  A lowerer that recognizes the chain returns a
# replacement program with the same contract as _build_program
# (``program(*leaf_vals) -> out`` or ``(out, allfinite)`` under the folded
# guard); returning None declines.  The replacement enters the SAME cache
# entry — hits/misses/retrace accounting in cache_stats() cover terminated
# chains identically.  ``salt`` contributes the engine's dispatch state
# (mode/threshold) to the cache key, so flipping HEAT_TPU_MATMUL builds a
# distinct entry instead of reusing the other mode's executable.
# Correctness never depends on a lowerer: a declined or failing lowering
# falls back to the generic fused program (and a replacement program that
# fails to compile falls back eager like any other entry).

_TERMINATORS: "list[Tuple[Callable, Optional[Callable]]]" = []


def register_terminator(lowerer: Callable, salt: Optional[Callable] = None) -> Callable:
    """Register ``lowerer(instrs, leaves, out_slot, lshapes, gshape, split,
    comm, target, with_guard) -> program | None`` (see block comment)."""
    _TERMINATORS.append((lowerer, salt))
    return lowerer


def _terminator_salt() -> tuple:
    return tuple(s() for _, s in _TERMINATORS if s is not None)


# zero-arg callables whose results join EVERY compile-cache key (a
# terminator salt rides only alongside its lowerer's registration).
# Process-wide dispatch state that changes which program a chain should
# build — the autotune plane's (enabled, generation) — registers here,
# so a tuned-winner flip builds a distinct cache entry instead of
# reusing the executable lowered under the old decision.
_CACHE_SALTS: "list[Callable]" = []


def register_cache_salt(fn: Callable) -> Callable:
    """Register a zero-arg callable contributing to every compile-cache
    key (idempotent per callable)."""
    if fn not in _CACHE_SALTS:
        _CACHE_SALTS.append(fn)
    return fn


def _cache_salt() -> tuple:
    return tuple(s() for s in _CACHE_SALTS)


def _lower_terminated(instrs, leaves, out_slot, lshapes, gshape, split, comm,
                      target, with_guard):
    for lowerer, _ in _TERMINATORS:
        try:
            program = lowerer(
                instrs, leaves, out_slot, lshapes, gshape, split, comm,
                target, with_guard,
            )
        except Exception:
            program = None  # a broken matcher must not break the chain
        if program is not None:
            return program
    return None


# ------------------------------------------------------------ compile cache

class _Entry:
    __slots__ = ("jitted", "avals", "hits", "fp")

    def __init__(self, jitted, avals, fp=None):
        self.jitted = jitted
        self.avals = avals
        self.hits = 0
        self.fp = fp  # telemetry ledger fingerprint (None below counters)


_CACHE: "OrderedDict[tuple, _Entry]" = OrderedDict()
_CACHE_MAX = 4096  # live executables; LRU drops beyond it
# All counters live in ONE telemetry group; the registry owns the reset
# contract (a counter added to the defaults below resets/exports/snapshots
# with no second bookkeeping site).  Notable members:
#   roots_per_program — output-arity histogram of compiled programs
#                       ({n_roots: misses at that arity}).  A serving
#                       steady state shows this frozen; a growing
#                       multi-root bucket on repeated materialize_all()
#                       calls is a retrace regression.
#   fallback_reasons  — per-reason breakdown of the `fallbacks` total:
#     unfusable     — op declined to enter the DAG (built eagerly instead)
#     compile_error — fused program failed to trace/compile/first-run;
#                     re-executed per-op eagerly with identical semantics
#     exec_error    — cached executable failed at run time; same recovery
#     guard_replay  — non-finite guard replayed the chain op-by-op to
#                     attribute the first NaN/Inf producer
_STATS = telemetry.register_group(
    "fusion",
    {
        "hits": 0, "misses": 0, "evictions": 0, "fallbacks": 0,
        "cse_hits": 0,
        "fallback_reasons": {
            "unfusable": 0, "compile_error": 0, "exec_error": 0,
            "guard_replay": 0,
        },
        "roots_per_program": {},
    },
    extra=lambda: {"size": len(_CACHE)},
)
# hot-path aliases into the group (reset_group restores nested dicts in
# place, so these never dangle)
_FALLBACK_REASONS = _STATS["fallback_reasons"]
_ROOTS_PER_PROGRAM = _STATS["roots_per_program"]


def cache_stats() -> dict:
    """Counters for the executable cache: ``hits``/``misses`` (lookups),
    ``size`` (live entries), ``evictions`` (LRU drops past
    ``_CACHE_MAX``), ``fallbacks`` (total degraded-to-eager
    events) with a per-reason breakdown under ``fallback_reasons``
    (``unfusable`` / ``compile_error`` / ``exec_error`` /
    ``guard_replay``).  A serving steady state shows misses flat and hits
    climbing — a miss on a repeated chain is a retrace regression; a
    climbing ``compile_error``/``exec_error`` bucket means fused programs
    are failing and silently running degraded.

    DAG-scheduler counters: ``cse_hits`` counts op-subtree reuse events
    during linearization — every time a root (or another consumer) resolves
    to an already-scheduled op slot instead of re-emitting its subtree,
    whether by node identity (a diamond / several roots over one producer)
    or by structural fingerprint (independently built copies of the same
    subexpression).  ``roots_per_program`` is the output-arity histogram of
    compiled programs (``{1: single-root misses, 2: two-output misses,
    ...}``): `materialize_all` traffic shows up as multi-root buckets, and
    a bucket that keeps growing on repeated same-shape calls is a
    multi-output retrace regression.

    Thin shim over ``telemetry.snapshot_group("fusion")`` — the same
    counters appear in ``ht.telemetry.snapshot()`` and the Prometheus
    export."""
    return telemetry.snapshot_group("fusion")


def reset_cache() -> None:
    """Drop all executables and zero the counters (tests/benchmarks).
    Counter reset is registry-managed (``telemetry.reset_group``)."""
    _CACHE.clear()
    telemetry.reset_group("fusion")


def count_fallback(reason: str = "unfusable", error: Optional[BaseException] = None) -> None:
    _STATS["fallbacks"] += 1
    _FALLBACK_REASONS[reason] = _FALLBACK_REASONS.get(reason, 0) + 1
    if error is None:
        telemetry.record_event("fallback", reason=reason)
    else:
        # the degraded path swallows the exception: keep what it said
        telemetry.record_event(
            "fallback", reason=reason,
            error=f"{type(error).__name__}: {error}"[:500],
        )
    if reason == "exec_error":
        # a cached executable dying at run time is the flight recorder's
        # flagship postmortem case: dump the trail before degrading
        telemetry.postmortem("exec_error_fallback")


def last_hlo() -> Optional[str]:
    """Compiled HLO text of the most recently used cache entry (census
    tests count modules/ops in it).  None when the cache is empty."""
    if not _CACHE:
        return None
    entry = next(reversed(_CACHE.values()))
    return entry.jitted.lower(*entry.avals).compile().as_text()


def _sliced_leaf(vals, lshapes, idx):
    v = vals[idx]
    ls = lshapes[idx]
    if tuple(v.shape) != ls:
        v = v[tuple(slice(0, n) for n in ls)]
    return v


def _eager_eval(instrs, vals, lshapes):
    """Per-op eager evaluation of the linearized DAG: the degraded-mode
    twin of :func:`_build_program`'s in-jit loop.  Each op dispatches as
    its own XLA program (exactly the pre-fusion execution shape), so a
    chain that breaks the fused compiler still computes — slower, never
    wrong."""
    env = []
    for ins in instrs:
        if ins[0] == "L":
            env.append(_sliced_leaf(vals, lshapes, ins[1]))
        else:
            _, fn, kw, ch = ins
            env.append(fn(*[env[c] for c in ch], **dict(kw or ())))
    return env


def _finalize_eager(out, gshape, split, nshards, target):
    """The `_build_program` finalization (pad to physical + canonical
    sharding) for eagerly-computed results."""
    if split is not None and gshape:
        n = gshape[split]
        pn = _physical_dim(n, nshards)
        if pn != n:
            pad = [(0, 0)] * len(gshape)
            pad[split] = (0, pn - n)
            out = jnp.pad(out, pad)
    return jax.device_put(out, target)


def _eager_fallback(instrs, vals, lshapes, out_slots, gshapes, splits, comm, targets):
    env = _eager_eval(instrs, vals, lshapes)
    return tuple(
        _finalize_eager(env[s], tuple(g), sp, comm.size, tg)
        for s, g, sp, tg in zip(out_slots, gshapes, splits, targets)
    )


@jax.jit
def _allfinite(a):
    return jnp.all(jnp.isfinite(a))


def _finite(v) -> bool:
    """Host-synced finiteness of one array (True for non-float dtypes)."""
    if not jnp.issubdtype(v.dtype, jnp.inexact):
        return True
    return bool(_allfinite(v))


# Outputs at or below this many elements are guard-checked on the host (one
# small device_get + a numpy pass); above it the allfinite reduction is
# folded into the fused executable instead, so no output-sized host
# transfer ever happens.  64K elements = 256 KiB of f32 — well under a
# tile, and the host pass is cheaper than an extra XLA dispatch there.
_GUARD_FOLD_MIN_ELEMS = 1 << 16


def _host_finite(out) -> bool:
    arr = np.asarray(out)
    if not np.issubdtype(arr.dtype, np.inexact):
        return True
    return bool(np.isfinite(arr).all())


def _reaches(instrs, root_slot, target_slot) -> bool:
    """Whether ``target_slot`` is in the subtree of ``root_slot`` (used to
    attribute a shared offending node to every consuming output)."""
    memo: "dict[int, bool]" = {}

    def walk(s):
        if s == target_slot:
            return True
        got = memo.get(s)
        if got is not None:
            return got
        ins = instrs[s]
        memo[s] = r = ins[0] == "O" and any(walk(c) for c in ins[3])
        return r

    return walk(root_slot)


def _guard_check(outs, instrs, sites, leaves, lshapes, out_slots, fast_flag=None):
    """Raise :class:`NonFiniteError` when the chain *introduced* NaN/Inf.

    ``outs``/``out_slots`` cover every root of the (possibly multi-output)
    program.  Fast path: the joint ``allfinite`` scalar the fused program
    already computed (``fast_flag``, large outputs), or a host-side numpy
    pass over the fetched outputs (small outputs / eager-fallback
    results).  Only when that trips: if any input leaf already carried
    non-finite values the chain merely propagated them (nansum-style
    workflows are legal) and nothing is raised; otherwise the linearized
    DAG replays eagerly op-by-op — ONCE, over the deduplicated instruction
    list, so a shared node is evaluated and blamed once — to name the
    first op whose finite inputs went non-finite, plus every program
    output its subtree feeds."""
    if fast_flag is not None:
        with telemetry.sync("guard.flag"):  # the folded allfinite scalar
            finite = bool(fast_flag)
    else:
        with telemetry.sync("guard.host_finite"):  # small outputs, fetched
            finite = all(_host_finite(o) for o in outs)
    if finite:
        return
    vals = [lf.value for lf in leaves]
    if not all(_finite(v) for v in vals):
        return  # propagation, not production
    count_fallback("guard_replay")
    err = None
    env = []
    for i, ins in enumerate(instrs):
        if ins[0] == "L":
            env.append(_sliced_leaf(vals, lshapes, ins[1]))
            continue
        _, fn, kw, ch = ins
        val = fn(*[env[c] for c in ch], **dict(kw or ()))
        env.append(val)
        if not _finite(val):
            name = op_name(fn)
            site = sites[i]
            subtree = _render_instrs(instrs, leaves, out_slots, upto=i, mark=i)
            consumers = ""
            if len(out_slots) > 1:
                fed = [
                    k for k, s in enumerate(out_slots) if _reaches(instrs, s, i)
                ]
                consumers = (
                    f"; feeds output(s) {', '.join('%%%d' % out_slots[k] for k in fed)}"
                    f" (root index {', '.join(str(k) for k in fed)})"
                    f" of the {len(out_slots)}-output program"
                )
            err = NonFiniteError(
                f"non-finite values first produced by op '{name}' "
                f"(built at {guard.format_site(site)}){consumers}; "
                f"offending subtree:\n{subtree}",
                op=name, site=site, subtree=subtree,
            )
            break
    if err is None:
        # the eager replay stayed finite: the non-finites exist only in
        # the fused program's output (an XLA numeric divergence — or an
        # injected corruption).  Still a guard trip: degraded numerics
        # must not pass silently just because they resist op-level
        # attribution.
        subtree = _render_instrs(instrs, leaves, out_slots)
        err = NonFiniteError(
            "non-finite values in the fused output, but an eager op-by-op "
            "replay of the same chain is finite — fused-program numeric "
            "divergence (rerun with HEAT_TPU_FUSE=off to confirm); chain:\n"
            f"{subtree}",
            op=None, site=None, subtree=subtree,
        )
    eid = telemetry.record_event(
        "guard_blame",
        op=err.op,
        site=guard.format_site(err.site) if err.site else None,
        n_roots=len(out_slots),
        strict=guard.strict(),
    )
    err.event_id = eid
    if guard.strict():
        telemetry.postmortem("guard_raise")
        raise err
    # default warn mode: NumPy's own contract for sqrt(-1)/log(0)-class
    # results is a RuntimeWarning, not an exception — keep parity, but
    # with chain-aware attribution attached.  Warning is constructed as an
    # INSTANCE so the blame event id survives onto it (warning → event
    # correlation for tests and postmortems).
    w = guard.NonFiniteWarning(str(err))
    w.event_id = eid
    warnings.warn(w, stacklevel=3)


def _tuplize(program, with_guard):
    """Adapt a single-root terminator program (contract: returns ``out`` or
    ``(out, allfinite)``) to the scheduler's flat-tuple convention
    (``(out,)`` or ``(out, allfinite)`` flattened)."""

    def wrapped(*vals):
        out = program(*vals)
        if with_guard:
            out, flag = out
            return (out, flag)
        return (out,)

    return wrapped


def _program_fingerprint(instrs, out_slots) -> str:
    """Stable short digest of the program TOPOLOGY for the telemetry
    ledger: registered display names (not function reprs, which carry
    object addresses), static kwargs, child slots, and the root set.
    Distinct from the compile-cache key on purpose — the ledger
    identifies a program shape across meshes and dtypes."""
    parts = []
    for ins in instrs:
        if ins[0] == "L":
            parts.append(f"L{ins[1]}")
        else:
            parts.append(f"{op_name(ins[1])}{ins[2] or ()}>{ins[3]}")
    parts.append(f"->{out_slots}")
    return telemetry.fingerprint(parts)


def _estimate_cost(instrs, leaves, lshapes, out_slots):
    """Walk the linearized DAG once and estimate ``(ops, flops,
    hbm_bytes)`` for the telemetry cost ledger.

    FLOPs per op by registered kind: elementwise/cast/comparison/
    predicate count one per OUTPUT element; reduction/composite/scan one
    per INPUT element; matmul counts ``2·m·k·n`` from its 2-D operand
    avals — the same operand accounting the overlap dispatcher's
    bytes-per-step cost model keys on.  HBM bytes are the mandatory
    traffic floor: each unique leaf read once plus each root written once
    (fused intermediates never round-trip — that is the point of the
    engine).  Avals re-derive through the memoized :func:`_infer_aval`,
    so a repeat walk of a known topology is dict lookups."""

    def _nelems(shape):
        n = 1
        for d in shape:
            n *= int(d)
        return n

    avals = []
    n_ops = 0
    flops = 0.0
    for ins in instrs:
        if ins[0] == "L":
            lf = leaves[ins[1]]
            avals.append(
                jax.ShapeDtypeStruct(tuple(lshapes[ins[1]]), lf.value.dtype)
            )
            continue
        _, fn, kw, ch = ins
        child = tuple(avals[c] for c in ch)
        out = _infer_aval(fn, child, kw)
        avals.append(out)
        n_ops += 1
        kind = _OP_TABLE.get(fn, (None, "elementwise"))[1]
        if (
            kind == "matmul"
            and len(child) >= 2
            and len(child[0].shape) == 2
            and len(child[1].shape) == 2
        ):
            (m, k), n = child[0].shape, child[1].shape[-1]
            flops += 2.0 * int(m) * int(k) * int(n)
        elif kind in ("reduction", "composite", "scan"):
            flops += float(sum(_nelems(a.shape) for a in child))
        else:  # elementwise / cast / comparison / predicate / unregistered
            flops += float(_nelems(out.shape))
    hbm = sum(
        _nelems(lshapes[i]) * np.dtype(lf.value.dtype).itemsize
        for i, lf in enumerate(leaves)
    )
    hbm += sum(
        _nelems(avals[s].shape) * np.dtype(avals[s].dtype).itemsize
        for s in out_slots
    )
    return n_ops, flops, float(hbm)


def _run_many(exprs, gshapes, splits, comm, donate: Tuple[int, ...] = ()):
    """Telemetry-spanned wrapper: every multi-root lowering runs inside a
    ``fusion.materialize`` span (nested under any caller span; at trace
    level it lands in Perfetto via ``jax.profiler.TraceAnnotation``)."""
    with telemetry.span("fusion.materialize", roots=len(exprs)):
        return _run_many_impl(exprs, gshapes, splits, comm, donate)


def _run_many_impl(exprs, gshapes, splits, comm, donate: Tuple[int, ...] = ()):
    """Lower several DAG roots as ONE multi-output program (or fetch the
    cached executable) and run it, returning one physical array per root.

    The roots linearize into a single deduplicated instruction list
    (:func:`_linearize`), so subtrees shared between roots — by node
    identity or by structural fingerprint — compile and execute exactly
    once.  The cache key carries the full ``out_slots`` tuple: output
    arity and the root-set fingerprint are part of the entry, so a
    two-output program never aliases its single-output prefix.

    Failure containment: a fused program that fails to compile or execute
    falls back to per-op eager evaluation of the same DAG (counted under
    ``compile_error``/``exec_error`` in :func:`cache_stats`); with the
    guard on, a materialized chain whose finite inputs produced NaN/Inf
    raises :class:`NonFiniteError` via an attributing eager replay — the
    folded fast-finite flag joins the program's output tuple instead of
    forcing a second dispatch."""
    instrs, sites, leaves, out_slots = _linearize(*exprs)
    vals = [lf.value for lf in leaves]
    if sanitize.enabled():
        # every DAG leaf funnels through here — the use-after-donate
        # choke point for fused programs
        for v in vals:
            sanitize.check_use(v, "fusion.materialize")
    lshapes = tuple(tuple(lf.lshape) for lf in leaves)
    gshapes = tuple(tuple(g) for g in gshapes)
    splits = tuple(splits)
    targets = tuple(comm.sharding(s, len(g)) for s, g in zip(splits, gshapes))
    sig = tuple(
        (tuple(v.shape), str(v.dtype), getattr(v, "sharding", None))
        for v in vals
    )
    # For large outputs the guard folds its allfinite reduction into the
    # executable (no output-sized host transfer, no extra dispatch), so
    # the guard state is part of the program — guard-off entries stay
    # byte-identical to the unguarded build.  Small outputs keep the
    # unmodified program and are checked host-side after the fetch.
    guard_on = guard.enabled()
    fold = False
    if guard_on:
        n_max = 0
        for g in gshapes:
            n = 1
            for d in g:
                n *= int(d)
            n_max = max(n_max, n)
        fold = n_max > _GUARD_FOLD_MIN_ELEMS
    key = (
        instrs, out_slots, lshapes, sig, gshapes, splits, targets, donate,
        guard_on, _terminator_salt(), _cache_salt(),
    )
    flag = None
    donated_ran = False
    entry = _CACHE.get(key)
    if entry is None:
        _STATS["misses"] += 1
        n_roots = len(out_slots)
        _ROOTS_PER_PROGRAM[n_roots] = _ROOTS_PER_PROGRAM.get(n_roots, 0) + 1
        # ledger + flight-recorder bookkeeping happens only on the miss
        # path — by definition not the steady state, so the DAG cost walk
        # and fingerprint hash add nothing to cached traffic
        fp = None
        ops = 0
        flops = hbm = 0.0
        mesh_info = {"devices": comm.size}
        if telemetry.ledger_enabled():
            try:
                fp = _program_fingerprint(instrs, out_slots)
                ops, flops, hbm = _estimate_cost(
                    instrs, leaves, lshapes, out_slots
                )
            except Exception:  # an estimator bug must never block lowering
                pass
        telemetry.record_event(
            "cache_miss", fingerprint=fp, n_roots=n_roots,
        )
        telemetry.record_event(
            "compile_begin", fingerprint=fp, n_roots=n_roots, ops=ops,
            mesh=mesh_info, flops=flops, hbm_bytes=hbm,
        )
        t0 = time.monotonic()
        try:
            guard.fire("fusion.compile")
            program = None
            if n_roots == 1:
                # schedule-controlled engines (overlap.py's ring matmul)
                # keep their single-root contract; multi-root programs
                # always take the generic GSPMD build
                single = _lower_terminated(
                    instrs, leaves, out_slots[0], lshapes, gshapes[0],
                    splits[0], comm, targets[0], fold,
                )
                if single is not None:
                    program = _tuplize(single, fold)
            if program is None:
                program = _build_program(
                    instrs, out_slots, lshapes, gshapes, splits, comm.size,
                    targets, with_guard=fold,
                )
            jitted = jax.jit(program, donate_argnums=donate or ())
            if program_audit.enabled():
                fp_a = fp
                if fp_a is None:
                    try:
                        fp_a = _program_fingerprint(instrs, out_slots)
                    except Exception:
                        fp_a = None
                program_audit.audit_program(
                    "fused", fp_a, jitted, vals,
                    donate=tuple(donate or ()), expect="reduce",
                )
            # only mesh shardings are recorded for AOT re-lowering (last_hlo):
            # a SingleDeviceSharding on an uncommitted scalar leaf would pin it
            # to device 0 and clash with the mesh-committed array leaves
            avals = tuple(
                jax.ShapeDtypeStruct(
                    v.shape, v.dtype,
                    sharding=s if isinstance(s, jax.sharding.NamedSharding) else None,
                )
                for v in vals
                for s in (getattr(v, "sharding", None),)
            )
            entry = _Entry(jitted, avals)
            outs = entry.jitted(*vals)
            donated_ran = True
            if fold:
                outs, flag = outs[:-1], outs[-1]
        except Exception as exc:
            # trace/lowering/compile/first-run failure: the executable is
            # unusable — do NOT cache it; recompute per-op eagerly
            telemetry.record_event(
                "compile_end", fingerprint=fp, ok=False,
                dur_s=round(time.monotonic() - t0, 6),
            )
            count_fallback("compile_error", exc)
            flag = None
            outs = _eager_fallback(
                instrs, vals, lshapes, out_slots, gshapes, splits, comm, targets
            )
        else:
            telemetry.record_event(
                "compile_end", fingerprint=fp, ok=True,
                dur_s=round(time.monotonic() - t0, 6),
                n_roots=n_roots, ops=ops, flops=flops, hbm_bytes=hbm,
                mesh=mesh_info,
            )
            if fp is not None:
                telemetry.record_program(
                    fp, kind="fused", n_roots=n_roots, ops=ops,
                    flops=flops, hbm_bytes=hbm, mesh=mesh_info,
                )
            entry.fp = fp
            _CACHE[key] = entry
            while len(_CACHE) > _CACHE_MAX:
                _, evicted = _CACHE.popitem(last=False)
                _STATS["evictions"] += 1
                telemetry.record_event("cache_evict", fingerprint=evicted.fp)
    else:
        _STATS["hits"] += 1
        entry.hits += 1
        _CACHE.move_to_end(key)
        telemetry.program_hit(entry.fp)
        telemetry.record_event("cache_hit", fingerprint=entry.fp)
        try:
            guard.fire("fusion.exec")
            # steady-state executions get the (sampled) measured wall
            # clock; the miss path's first run is excluded — its wall is
            # trace+compile time, already on the compile_end event
            outs = telemetry.timed_call(entry.fp, entry.jitted, *vals)
            donated_ran = True
            if fold:
                outs, flag = outs[:-1], outs[-1]
        except Exception as exc:
            count_fallback("exec_error", exc)
            flag = None
            outs = _eager_fallback(
                instrs, vals, lshapes, out_slots, gshapes, splits, comm, targets
            )
    if donate and donated_ran:
        # the executed program consumed these leaves via donate_argnums —
        # poison the stale handles (the eager fallback never donates)
        for i in donate:
            if i < len(vals):
                sanitize.poison(
                    vals[i], donated_site="fusion._run_many(donate_argnums)"
                )
    outs = tuple(outs)
    fused_outs = outs
    outs = guard.corrupt("fusion.exec", outs)
    if guard_on:
        # an injected corruption replaced the output object: the folded
        # flag describes the pre-corruption values, so re-check explicitly
        _guard_check(
            outs, instrs, sites, leaves, lshapes, out_slots,
            fast_flag=flag if outs is fused_outs else None,
        )
    return outs


def _run(expr: Expr, gshape, split, comm, donate: Tuple[int, ...] = ()):
    """Single-root :func:`_run_many` (the ``.larray`` boundary)."""
    return _run_many((expr,), (gshape,), (split,), comm, donate)[0]


# ----------------------------------------------------------- lazy DNDarray

class LazyDNDarray(DNDarray):
    """A DNDarray whose payload is a pending :class:`Expr`.

    All metadata (shape, dtype, split, device, comm) is exact and available
    immediately — only the array value is deferred.  Every base-class code
    path that reads the mangled ``_DNDarray__array`` slot (``.larray``,
    ``.parray``, ``__bool__``, ``resplit_``, printing, ``numpy()``, ...)
    triggers ``__getattr__`` on the missing slot, which materializes the
    DAG through the compile cache and caches the physical result — the
    materialization boundaries of the ISSUE fall out of attribute access,
    with zero changes to the call sites."""

    def __init__(self, expr, gshape, dtype, split, device, comm):
        super().__init__(None, gshape, dtype, split, device, comm)
        object.__setattr__(self, "_expr", expr)
        del self._DNDarray__array

    def __getattr__(self, name):
        if name == "_DNDarray__array":
            expr = self._expr
            value = _run(expr, self.gshape, self.split, self.comm)
            # leafify in place: later chains referencing this node reuse
            # the computed buffer instead of recompiling the subchain.
            # The buffer is pinned for the node's remaining lifetime (it
            # may now be a leaf of other pending DAGs) and the handle
            # drops its expression reference, so the pin dies with the
            # last consumer rather than with this handle.
            expr.leafify(value, self.gshape)
            memtrack.register_buffer(value, tag="output", split=self.split)
            _pin(expr, value)
            object.__setattr__(self, "_DNDarray__array", value)
            object.__setattr__(self, "_expr", None)
            return value
        raise AttributeError(
            f"{type(self).__name__!r} object has no attribute {name!r}"
        )

    def astype(self, dtype, copy: bool = True) -> "DNDarray":
        # still pending + copy=True: the cast joins the DAG (a fused
        # convert_element_type, never an array-sized dispatch)
        if copy and "_DNDarray__array" not in self.__dict__:
            ht_dtype = types.canonical_heat_type(dtype)
            try:
                casted = cast_node(self._expr, ht_dtype.jax_type())
            except Unfusable:
                return super().astype(dtype, copy)
            return LazyDNDarray(
                casted, self.gshape, ht_dtype, self.split, self.device, self.comm
            )
        return super().astype(dtype, copy)


def defer(expr: Expr, gshape, dtype, split, device, comm) -> LazyDNDarray:
    """Wrap a DAG root as a lazy DNDarray with the given result metadata."""
    return LazyDNDarray(
        expr, tuple(gshape), dtype, split, device, comm
    )


def materialize_all(*arrays):
    """Materialize several (possibly lazy) DNDarrays as ONE fused program.

    All still-pending roots that share a mesh lower together through
    :func:`_run_many`: subtrees shared between the roots (by node identity
    or structural fingerprint) compile and execute exactly once, and the
    whole batch is a single compile-cache entry / single XLA dispatch.
    Already-materialized (or eager) arrays pass through untouched; roots
    on different meshes are grouped per mesh.  Returns ``arrays`` as a
    tuple, every element now physical.
    """
    # DNDarray.__eq__ is elementwise — membership tests must use id()
    pending = []
    seen = set()
    for x in arrays:
        if (
            isinstance(x, LazyDNDarray)
            and "_DNDarray__array" not in x.__dict__
            and id(x) not in seen
        ):
            seen.add(id(x))
            pending.append(x)
    while pending:
        head = pending[0]
        group = [
            x for x in pending
            if x.comm is head.comm or x.comm.mesh == head.comm.mesh
        ]
        gids = {id(x) for x in group}
        pending = [x for x in pending if id(x) not in gids]
        if len(group) == 1:
            group[0].parray  # single root: the ordinary __getattr__ path
            continue
        exprs = tuple(x._expr for x in group)
        outs = _run_many(
            exprs,
            tuple(x.gshape for x in group),
            tuple(x.split for x in group),
            head.comm,
        )
        for x, value in zip(group, outs):
            expr = x._expr
            expr.leafify(value, x.gshape)
            memtrack.register_buffer(value, tag="output", split=x.split)
            _pin(expr, value)
            object.__setattr__(x, "_DNDarray__array", value)
            object.__setattr__(x, "_expr", None)
    for x in arrays:
        x.parray  # eager handles are no-ops; duplicates already leafified
    return tuple(arrays)


def materialize(*arrays):
    """Force one or more (possibly lazy) DNDarrays to physical payloads.

    ``materialize(x)`` keeps the original single-array contract and
    returns ``x`` itself.  ``materialize(a, b, ...)`` batches all pending
    roots into ONE multi-output fused executable (shared subtrees
    deduplicated — see :func:`materialize_all`) and returns the arrays as
    a tuple.  Exported as ``heat_tpu.materialize``.
    """
    if not arrays:
        raise TypeError("materialize() requires at least one array")
    if len(arrays) == 1:
        arrays[0].parray  # property read funnels through __getattr__
        return arrays[0]
    return materialize_all(*arrays)


# ------------------------------------------- split-boundary terminators

# Lowerers consulted when a lazy chain terminates at a split CHANGE (a
# resplit / split-crossing reshape boundary) rather than at a plain read.
# Contract: lowerer(instrs, leaves, out_slot, lshapes, gshape, old_split,
# new_split, comm, tile_bytes) -> physical array in the NEW split, or
# None to decline.  Registered lazily by parallel/transport.py so core
# keeps zero imports from parallel at module load.
_SPLIT_TERMINATORS: "list[Callable]" = []


def register_split_terminator(lowerer: Callable) -> Callable:
    """Register a split-boundary lowerer (see ``_SPLIT_TERMINATORS``)."""
    _SPLIT_TERMINATORS.append(lowerer)
    return lowerer


_SPLIT_LOWERERS_READY = False


def _ensure_split_lowerers() -> None:
    global _SPLIT_LOWERERS_READY
    if _SPLIT_LOWERERS_READY:
        return
    from ..parallel import transport

    transport.ensure_fused_tail_registered()
    _SPLIT_LOWERERS_READY = True


def materialize_resplit(x, new_split, tile_bytes=None):
    """Lower a pending chain DIRECTLY into the new split's transport loop.

    When ``x`` is a still-pending :class:`LazyDNDarray` whose elementwise
    tail a registered split terminator can fuse into the per-tile
    all-to-all (compute on tile *k* overlapping the collective for tile
    *k+1*), returns the physical array already in ``new_split`` — no
    separate pre-pass materialization.  Returns None when the chain is
    not pending, the boundary is not a real split change, or every
    lowerer declines; callers then fall back to materialize-then-resplit.

    ``x`` itself stays pending: the fused output is in the NEW layout,
    while other consumers of the chain still need the old-split value.
    """
    if not _ENABLED:
        return None
    if not (
        isinstance(x, LazyDNDarray) and "_DNDarray__array" not in x.__dict__
    ):
        return None
    if new_split is None or x.split is None or new_split == x.split:
        return None
    _ensure_split_lowerers()
    expr = x._expr
    if expr is None:
        return None
    instrs, sites, leaves, out_slots = _linearize(expr)
    lshapes = tuple(tuple(lf.lshape) for lf in leaves)
    for lowerer in _SPLIT_TERMINATORS:
        try:
            out = lowerer(
                instrs, leaves, out_slots[0], lshapes, tuple(x.gshape),
                x.split, int(new_split), x.comm, tile_bytes,
            )
        except Exception:
            out = None
        if out is not None:
            if guard.enabled():
                _guard_check(
                    (out,), instrs, sites, leaves, lshapes, out_slots,
                    fast_flag=None,
                )
            return out
    return None
