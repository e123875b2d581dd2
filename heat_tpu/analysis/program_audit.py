"""Compiled-program audit tier of the SPMD hazard analyzer
(``HEAT_TPU_AUDIT=1``; ``HEAT_TPU_AUDIT=hlo`` adds the compiled-module
scan).

Hooked into the three compile sites — fusion's ``_run_many`` miss path,
transport's tiled programs, overlap's ring programs — each program is
audited ONCE per (kind, fingerprint), off the steady state:

* **use_after_donate** — an input buffer the sanitizer's poison ledger
  says was already donated to XLA (the auditor registers interest, so
  donation sites poison even when the raising sanitizer is off).
* **donation_unaliasable** — a ``donate_argnums`` input whose byte size
  matches no program output: XLA cannot alias it, so the donation buys
  nothing and the caller gave up a buffer for free (jax warns once,
  deep in the log; here it lands in the flight recorder with the
  cost-ledger fingerprint).
* **host_transfer** — callback primitives (``pure_callback`` /
  ``io_callback`` / debug prints) inside an engine program: a
  device-to-host round trip per dispatch that the roofline would
  mis-attribute.
* **unexpected_collective / unexpected_reshard** — collective
  primitives in a program the cost ledger modeled as local
  (``expect="none"``), or — under ``hlo`` mode — GSPMD-inserted
  resharding collectives (all-gather / all-to-all / collective-permute)
  in a fused program modeled as local-plus-reduce (``expect="reduce"``:
  the estimator prices trailing cross-shard reductions, so
  all-reduce-class ops are expected there and only data *rearrangement*
  flags).

Findings are recorded as ``analysis_finding`` flight-recorder events
carrying the cost-ledger fingerprint, so :func:`telemetry.roofline_report`
can mark audited-dirty rows — a row whose measured time includes an
unmodeled collective or host sync is not trustworthy attribution.
"""

import os
from typing import Dict, List, Optional, Sequence, Tuple

from ..core import telemetry
from . import sanitize

# ------------------------------------------------------------------- gating

_MODE_OVERRIDE: "List[Optional[str]]" = [None]

_VALID_MODES = ("off", "jaxpr", "hlo")


def mode() -> str:
    """``off`` | ``jaxpr`` | ``hlo`` (``HEAT_TPU_AUDIT``: unset/0 = off,
    1/on/jaxpr = jaxpr walk, hlo = jaxpr walk + compiled-module scan)."""
    if _MODE_OVERRIDE[0] is not None:
        return _MODE_OVERRIDE[0]
    raw = os.environ.get("HEAT_TPU_AUDIT", "").strip().lower()
    if raw in ("", "0", "off", "false", "no"):
        return "off"
    if raw == "hlo":
        return "hlo"
    return "jaxpr"


def enabled() -> bool:
    return mode() != "off"


def set_mode(m: Optional[str]) -> Optional[str]:
    """Override the env toggle (``None`` restores env control).  Returns
    the previous override."""
    if m is not None and m not in _VALID_MODES:
        raise ValueError(f"audit mode must be one of {_VALID_MODES}, got {m!r}")
    prev = _MODE_OVERRIDE[0]
    _MODE_OVERRIDE[0] = m
    return prev


# donation sites poison for us even when the raising sanitizer is off
sanitize.register_interest(enabled)

# ----------------------------------------------------------------- findings

_FINDINGS: List[dict] = []
_BY_FP: Dict[str, List[dict]] = {}
_SEEN: set = set()

# named "audit", not "program_audit": heat_tpu_program_* is the reserved
# prometheus namespace for per-program labeled roofline gauges
_STATS = telemetry.register_group(
    "audit",
    {
        "audits": 0,      # programs walked (once per kind+fingerprint)
        "findings": 0,    # hazards recorded
        "audit_errors": 0,  # programs the walker could not trace
    },
)

_COLLECTIVE_PRIMS = frozenset({
    "psum", "all_gather", "all_gather_invariant", "all_to_all", "ppermute",
    "pmin", "pmax", "reduce_scatter", "psum_scatter", "pgather",
})
# all-reduce-class compiled ops are "modeled" for expect="reduce"
# programs (the fused-chain cost estimator prices trailing cross-shard
# reductions); data-rearrangement ops are never modeled there
_RESHARD_HLO = (
    "all-gather(", "all-gather-start(", "all-to-all(", "all-to-all-start(",
    "collective-permute(", "collective-permute-start(",
)
_ALL_HLO = _RESHARD_HLO + ("all-reduce(", "all-reduce-start(",
                           "reduce-scatter(")
# host-callback primitives as jax 0.9 names them in a jaxpr
# (jax.debug.print traces to its own ``debug_print`` primitive)
_CALLBACK_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
})


def findings(fp: Optional[str] = None) -> List[dict]:
    """All recorded findings, or just those for one fingerprint."""
    if fp is not None:
        return list(_BY_FP.get(fp, ()))
    return list(_FINDINGS)


def dirty_fingerprints() -> set:
    """Fingerprints with at least one finding — the roofline marks these
    rows audited-dirty."""
    return set(_BY_FP)


def reset() -> None:
    del _FINDINGS[:]
    _BY_FP.clear()
    _SEEN.clear()


def _record(kind: str, fp: Optional[str], rule: str, detail: str) -> dict:
    finding = {"kind": kind, "fingerprint": fp, "rule": rule,
               "detail": detail}
    _FINDINGS.append(finding)
    if fp is not None:
        _BY_FP.setdefault(fp, []).append(finding)
    _STATS["findings"] += 1
    telemetry.record_event(
        "analysis_finding", kind=kind, fingerprint=fp, rule=rule,
        detail=detail,
    )
    return finding


# -------------------------------------------------------------- jaxpr walk


def _walk_jaxpr(jaxpr, prims: set) -> None:
    for eqn in getattr(jaxpr, "eqns", ()):
        prims.add(eqn.primitive.name)
        for val in eqn.params.values():
            _walk_params(val, prims)


def _walk_params(val, prims: set) -> None:
    inner = getattr(val, "jaxpr", None)
    if inner is not None:  # ClosedJaxpr
        _walk_jaxpr(inner, prims)
        return
    if hasattr(val, "eqns"):  # raw Jaxpr
        _walk_jaxpr(val, prims)
        return
    if isinstance(val, (tuple, list)):
        for v in val:
            _walk_params(v, prims)


def _nbytes(shape, dtype) -> int:
    n = int(getattr(dtype, "itemsize", 0) or 0)
    for d in shape:
        n *= int(d)
    return n


# -------------------------------------------------------------------- audit


def audit_program(
    kind: str,
    fp: Optional[str],
    fn,
    args: Sequence,
    donate: Tuple[int, ...] = (),
    expect: str = "any",
) -> List[dict]:
    """Audit one compiled program; returns the findings it produced.

    ``fn`` is the (jitted or plain) callable about to run on ``args``;
    ``donate`` the positional donate_argnums; ``expect`` declares the
    collective contract the caller's cost model assumed: ``"any"``
    (transport/overlap — collectives are the point), ``"reduce"``
    (fused programs — trailing cross-shard reductions are modeled,
    resharding is not), ``"none"`` (modeled fully local)."""
    if not enabled():
        return []
    import jax

    got: List[dict] = []

    # (1) inputs already donated elsewhere — the poison ledger knows.
    # This check runs on EVERY call (dict lookups, cheap): the same
    # program fingerprint can be fed clean buffers on one call and a
    # donated one on the next, so it must not dedup with the walk below.
    for i, a in enumerate(args):
        entry = sanitize.poison_entry(a)
        if entry is not None:
            got.append(_record(
                kind, fp, "use_after_donate",
                f"input {i} was donated at {entry['donated']} "
                f"(buffer created at {entry['created']}) and is fed back "
                "into this program",
            ))

    # the program-structure walk is once per (kind, fingerprint) — off
    # the steady state
    key = (kind, fp) if fp is not None else (
        kind,
        tuple(
            (tuple(getattr(a, "shape", ())), str(getattr(a, "dtype", "?")))
            for a in args
        ),
        tuple(donate), expect,
    )
    if key in _SEEN:
        return got
    _SEEN.add(key)
    _STATS["audits"] += 1

    try:
        closed = jax.make_jaxpr(fn)(*args)
    except Exception as err:  # an unauditable program must not block it
        _STATS["audit_errors"] += 1
        telemetry.record_event(
            "analysis_finding", kind=kind, fingerprint=fp,
            rule="audit_error", detail=str(err)[:200],
        )
        return got

    prims: set = set()
    _walk_jaxpr(closed.jaxpr, prims)

    # (2) host round trips inside the program
    callbacks = sorted(prims & _CALLBACK_PRIMS)
    if callbacks:
        got.append(_record(
            kind, fp, "host_transfer",
            f"callback primitive(s) {callbacks} force a device-to-host "
            "round trip per dispatch",
        ))

    # (3) trace-level collectives in a modeled-local program
    colls = sorted(prims & _COLLECTIVE_PRIMS)
    if expect == "none" and colls:
        got.append(_record(
            kind, fp, "unexpected_collective",
            f"collective primitive(s) {colls} in a program the cost "
            "ledger modeled as local",
        ))

    # (4) donation aliasing: a donated input must byte-match some output
    out_sizes = [
        _nbytes(getattr(av, "shape", ()), getattr(av, "dtype", None))
        for av in closed.out_avals
    ]
    for i in donate:
        if i >= len(args):
            continue
        a = args[i]
        nb = _nbytes(getattr(a, "shape", ()), getattr(a, "dtype", None))
        if nb not in out_sizes:
            got.append(_record(
                kind, fp, "donation_unaliasable",
                f"donated input {i} ({nb} bytes) matches no output "
                f"(outputs: {out_sizes}) — XLA cannot alias it; the "
                "buffer is given up for nothing",
            ))

    # (5) hlo mode: GSPMD-inserted collectives in the compiled module
    if mode() == "hlo" and expect in ("none", "reduce"):
        try:
            lowered = fn.lower(*args) if hasattr(fn, "lower") else (
                jax.jit(fn).lower(*args)
            )
            text = lowered.compile().as_text()
        except Exception as err:
            _STATS["audit_errors"] += 1
            telemetry.record_event(
                "analysis_finding", kind=kind, fingerprint=fp,
                rule="audit_error", detail=f"hlo: {str(err)[:200]}",
            )
            return got
        markers = _ALL_HLO if expect == "none" else _RESHARD_HLO
        seen_ops = sorted(
            {m.rstrip("(") for m in markers if m in text}
        )
        if seen_ops:
            got.append(_record(
                kind, fp, "unexpected_reshard",
                f"GSPMD inserted {seen_ops} into a program modeled as "
                f"{'local' if expect == 'none' else 'local+reduce'} — "
                "the roofline row's measured time includes unmodeled "
                "wire traffic",
            ))
    return got
