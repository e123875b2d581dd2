"""Self-tuning runtime: measured explore/exploit dispatch, HBM-seeded
budgets, and a persisted warm-start cache (ROADMAP item 2 — close the
measure→decide loop).

Rounds 11–13 built a measurement plane (per-fingerprint wall clock,
roofline placement, real HBM watermarks); every performance decision
still read a static env-var knob.  This module spends those
measurements at the three engine sites:

1. **Explore/exploit dispatch between lowerings of one operation.**
   Per (program fingerprint, device kind), the first K calls
   (:func:`explore_k`, 3 per arm) run EVERY arm under timed
   measurement; the winner by steady-state ``min_s`` sticks in a
   per-process tuning table.  A site's static verdict is demoted to a
   *prior*: it still decides sites that cannot explore (lazy chains)
   and breaks ties, but a measured winner overrides it.  Safety
   margin: a sticky winner whose sampled wall clock degrades >2x vs
   its recorded best is sent back to explore.  The protocol is written
   once, here: :func:`run` (decide, explore or run the winner under the
   degradation watch) and :func:`explore` (one measured round, the
   reference arm's result returned).  A family of arms is declared by
   the module that dispatches it, which hands its arm names and a
   :func:`key` to these functions; this module names no client.

2. **HBM-seeded budgets up front.**  ``memtrack.suggest_budget()`` (the
   one formula behind transport's informed OOM retry) now also seeds
   transport's tile budget and the ring matmul's staging admission at
   plan time, instead of only shrinking after a ``RESOURCE_EXHAUSTED``.
   Statsless backends (CPU) keep today's static defaults.

3. **Persisted warm start.**  :func:`save` / :func:`load` persist the
   tuning table as versioned JSON keyed by (fingerprint, device kind,
   library version); ``HEAT_TPU_AUTOTUNE_CACHE`` loads it at import and
   enables JAX's persistent compilation cache next to it, so a
   restarted serving process replays winners with zero explore calls
   and warm lowering.

Every decision lands in the flight recorder as an ``autotune_decision``
event (arm, times, source: explored|cached|prior) and in the
``autotune`` counter group (Prometheus: ``heat_tpu_autotune_*``);
:func:`report` (also ``telemetry.autotune_report()``) renders the
table.  ``HEAT_TPU_AUTOTUNE=off`` restores the static dispatch
bit-for-bit.  This module deliberately imports only telemetry/memtrack
(never parallel/fusion): the engines register its :func:`salt` into the
fusion compile-cache key via ``fusion.register_cache_salt`` so tuned
flips build distinct entries without an import cycle.
"""

import functools
import json
import os
import time
from typing import Any, Callable, Dict, Mapping, NamedTuple, Optional, Tuple

from . import memtrack, telemetry
from .envparse import env_int  # the strict env-int twin of env_bytes (lint HT001)
from .version import __version__

__all__ = [
    "CACHE_VERSION",
    "Decision",
    "decide",
    "device_kind",
    "enabled",
    "env_bytes",
    "env_int",
    "explore",
    "explore_k",
    "key",
    "load",
    "merge",
    "note_budget_seed",
    "note_prior",
    "observe",
    "report",
    "reset",
    "run",
    "salt",
    "save",
    "set_enabled",
    "stats",
    "table",
    "winner",
]

CACHE_VERSION = 1

# samples kept per arm (min_s over a bounded window; enough for the
# explore phase plus degradation evidence, bounded so a long-lived
# serving process never grows the table entries)
_MAX_SAMPLES = 16

# a sticky winner this many times slower than its recorded best, on
# this many CONSECUTIVE sampled calls, goes back to explore (two
# strikes: one slow sample is GC / scheduler noise, two is a regime
# change — input residency, a neighbor hogging ICI, thermal throttle)
_DEGRADE_FACTOR = 2.0
_DEGRADE_STRIKES = 2


# --------------------------------------------------------------- env parsing


def env_bytes(name: str, default: int, env: Optional[dict] = None) -> int:
    """THE byte-sized env knob parser (``HEAT_TPU_TILE_BYTES``,
    ``HEAT_TPU_MATMUL_RING_MIN_BYTES``): empty/unset returns
    ``default``; a malformed or non-positive value raises ``ValueError``
    naming the variable — silently falling back to a default turns an
    operator's typo'd budget into an invisible perf bug."""
    raw = (os.environ if env is None else env).get(name, "").strip()
    if not raw:
        return int(default)
    try:
        val = int(raw)
        if val <= 0:
            raise ValueError
    except ValueError:
        raise ValueError(
            f"{name} must be a positive integer (bytes), got {raw!r}"
        ) from None
    return val


def explore_k() -> int:
    """Explore budget: measured samples per arm before a winner is
    declared."""
    return 3


# ------------------------------------------------------------------ enabling

# None → follow the env var; a bool → API override (tests, notebooks)
_ENABLED_OVERRIDE: "list[Optional[bool]]" = [None]


def enabled() -> bool:
    """Whether the tuning plane is live (``HEAT_TPU_AUTOTUNE``, default
    **on**).  Off restores the static env-knob dispatch exactly: no
    exploration, no table lookups, no plan-time budget seeding."""
    if _ENABLED_OVERRIDE[0] is not None:
        return _ENABLED_OVERRIDE[0]
    return os.environ.get("HEAT_TPU_AUTOTUNE", "on").strip().lower() not in (
        "off", "0", "false", "no",
    )


def set_enabled(on: Optional[bool]) -> Optional[bool]:
    """Override the env toggle (``None`` restores env control).  Returns
    the previous override.  Bumps the generation so fused programs built
    under the other mode don't serve stale dispatch decisions."""
    prev = _ENABLED_OVERRIDE[0]
    _ENABLED_OVERRIDE[0] = None if on is None else bool(on)
    if prev is not _ENABLED_OVERRIDE[0]:
        _GENERATION[0] += 1
    return prev


# ------------------------------------------------------------- tuning table

# (fingerprint, device_kind) → entry dict:
#   {"arms": {"ring": [durs], "gspmd": [durs]}, "winner": None|arm,
#    "best_s": float|None, "strikes": int, "loaded": bool, "desc": str}
_TABLE: Dict[Tuple[str, str], dict] = {}

# bumped whenever a decision could flip (winner resolved, re-explore,
# cache load, enable toggle, reset); joins the fusion compile-cache key
# via fusion.register_cache_salt so tuned flips build distinct entries
_GENERATION = [0]

_STATS = telemetry.register_group(
    "autotune",
    {
        "decisions": 0,      # every consult that returned an arm
        "explores": 0,       # calls that ran BOTH arms under measurement
        "cache_hits": 0,     # decisions served by a resolved winner
        "cache_loads": 0,    # entries restored by load()
        "priors": 0,         # decisions that fell back to the static prior
        "budget_seeds": 0,   # plan-time budgets shrunk from measured HBM
        "staging_declines": 0,  # ring staging refused by the HBM budget
        "re_explores": 0,    # winners sent back to explore on degradation
        "fallbacks": 0,      # corrupt/stale cache files ignored
        "saves": 0,
    },
    extra=lambda: {
        "enabled": enabled(),
        "table_size": len(_TABLE),
        "resolved": sum(1 for e in _TABLE.values() if e["winner"]),
        "generation": _GENERATION[0],
    },
)


def stats() -> Dict[str, Any]:
    """Snapshot of the ``autotune`` counter group (exported to
    Prometheus as ``heat_tpu_autotune_*`` gauges)."""
    return telemetry.snapshot_group("autotune")


def table() -> Dict[Tuple[str, str], dict]:
    """Deep-ish copy of the live tuning table (for tests/debugging)."""
    return {
        k: {**e, "arms": {a: list(d) for a, d in e["arms"].items()}}
        for k, e in _TABLE.items()
    }


def reset() -> None:
    """Drop every tuning entry and bump the generation.  Counters are
    telemetry-owned (``telemetry.reset_all()``); the table itself is NOT
    cleared by a counter reset — measured winners outlive metric
    scrapes."""
    _TABLE.clear()
    _GENERATION[0] += 1


def salt() -> tuple:
    """Dispatch-relevant state for the fusion compile-cache key: a
    program lowered while ``(enabled, generation)`` was X must not be
    reused once a tuned winner flips the ring/GSPMD choice."""
    return ("autotune", enabled(), _GENERATION[0])


def _entry(key: Tuple[str, str], desc: str, arms: Tuple[str, ...]) -> dict:
    e = _TABLE.get(key)
    if e is None:
        e = _TABLE[key] = {
            "arms": {a: [] for a in arms},
            "winner": None,
            "best_s": None,
            "strikes": 0,
            "loaded": False,
            "desc": desc,
        }
    elif desc and not e["desc"]:
        e["desc"] = desc
    return e


def table_size() -> int:
    return len(_TABLE)


def winner(key: Tuple[str, str]) -> Optional[str]:
    """Resolved winner for ``key`` or ``None`` (still exploring /
    unseen).  A hit counts as a served decision — this is the lazy-chain
    consult path."""
    e = _TABLE.get(key)
    if e is None or e["winner"] is None:
        return None
    _STATS["decisions"] += 1
    _STATS["cache_hits"] += 1
    telemetry.record_event(
        "autotune_decision",
        fingerprint=key[0], device_kind=key[1], arm=e["winner"],
        source="cached", site="chain", times=_arm_times(e),
    )
    return e["winner"]


def _arm_times(e: dict) -> Dict[str, Optional[float]]:
    out: Dict[str, Optional[float]] = {}
    for a, d in e["arms"].items():
        out[a + "_min_s"] = round(min(d), 6) if d else None
    return out


# ------------------------------------------------------------------ devices

_DEVICE_KIND: "list[Optional[str]]" = [None]


def device_kind() -> str:
    """``platform:kind`` of device 0 (e.g. ``tpu:TPU v4``,
    ``cpu:TFRT_CPU``) — tuning tables must never cross accelerator
    generations.  Cached; falls back to ``unknown`` before a backend
    initializes (never raises)."""
    if _DEVICE_KIND[0] is None:
        try:
            import jax

            d = jax.devices()[0]
            _DEVICE_KIND[0] = f"{d.platform}:{getattr(d, 'device_kind', '?')}"
        except Exception:
            return "unknown"
    return _DEVICE_KIND[0]


def key(family: str, site: str, *geometry) -> Tuple[str, str]:
    """Tuning-table key for one dispatch site of one arm family at one
    geometry.  The family's owner declares its arm names and hands them
    to :func:`decide` / :func:`run`; the family string keeps two owners'
    sites apart where site and geometry coincide."""
    fp = telemetry.fingerprint((family, site) + tuple(geometry))
    return fp, device_kind()


# ---------------------------------------------------------------- decisions


class Decision(NamedTuple):
    arm: str          # what to run (explore: run every arm, return the
    #                   reference arm's result)
    source: str       # "explored" | "cached" | "prior"
    explore: bool     # run EVERY arm under measurement this call
    key: Tuple[str, str]


@telemetry.span("autotune.decide")
def decide(
    key: Tuple[str, str],
    prior_arm: str,
    desc: str = "",
    *,
    arms: Tuple[str, ...],
) -> Decision:
    """One dispatch consult at the eager engine entry.  While any arm
    has fewer than :func:`explore_k` samples the call explores (runs
    every arm); a resolved entry serves its winner; the caller's static
    verdict rides along as the prior.  ``arms`` is the arm set the site
    dispatches.  A stored entry that names any other set came from
    outside the program (a cache file written for another site or
    build): it is dropped here, before it can be served, with a
    ``fallback`` event, and the site explores afresh."""
    e = _TABLE.get(key)
    if e is not None and e["arms"].keys() != set(arms):
        _STATS["fallbacks"] += 1
        telemetry.record_event(
            "fallback", site="autotune.decide", fingerprint=key[0],
            device_kind=key[1],
            error=f"entry arms {sorted(e['arms'])}, site dispatches {sorted(arms)}",
        )
        del _TABLE[key]
        _GENERATION[0] += 1
    e = _entry(key, desc, arms)
    if e["winner"] is not None:
        _STATS["decisions"] += 1
        _STATS["cache_hits"] += 1
        telemetry.record_event(
            "autotune_decision",
            fingerprint=key[0], device_kind=key[1], arm=e["winner"],
            source="cached", loaded=e["loaded"], times=_arm_times(e),
        )
        return Decision(e["winner"], "cached", False, key)
    _STATS["decisions"] += 1
    _STATS["explores"] += 1
    telemetry.record_event(
        "autotune_decision",
        fingerprint=key[0], device_kind=key[1], arm=prior_arm,
        source="explored", explore=True,
        **{a + "_samples": len(d) for a, d in e["arms"].items()},
    )
    return Decision(prior_arm, "explored", True, key)


def note_prior(key: Tuple[str, str], arm: str, site: str = "chain") -> None:
    """Record that a site fell back to the static threshold (no winner
    yet and the site cannot explore — e.g. inside a fused chain)."""
    _STATS["decisions"] += 1
    _STATS["priors"] += 1
    telemetry.record_event(
        "autotune_decision",
        fingerprint=key[0], device_kind=key[1], arm=arm,
        source="prior", site=site,
    )


def observe(key: Tuple[str, str], arm: str, dur_s: float) -> None:
    """Fold one measured wall clock into ``key``'s arm.  Resolves the
    winner once every arm carries :func:`explore_k` samples (argmin over
    per-arm ``min_s`` — min, not mean: the steady state, compile and
    cache-warm outliers washed out).  On a resolved entry this is the
    degradation watch: ``_DEGRADE_STRIKES`` consecutive samples slower
    than ``_DEGRADE_FACTOR``× the recorded best send it back to
    explore."""
    e = _TABLE.get(key)
    if e is None:  # never decided (or reset since): no arm set to fill
        return
    if e["winner"] is not None:
        if arm != e["winner"] or not e["best_s"]:
            return
        if dur_s > _DEGRADE_FACTOR * e["best_s"]:
            e["strikes"] += 1
            if e["strikes"] >= _DEGRADE_STRIKES:
                _STATS["re_explores"] += 1
                telemetry.record_event(
                    "autotune_reexplore",
                    fingerprint=key[0], device_kind=key[1],
                    arm=arm, observed_s=round(dur_s, 6),
                    best_s=round(e["best_s"], 6),
                )
                e["arms"] = {a: [] for a in e["arms"]}
                e["winner"] = None
                e["best_s"] = None
                e["strikes"] = 0
                e["loaded"] = False
                _GENERATION[0] += 1
        else:
            e["strikes"] = 0
        return
    durs = e["arms"].setdefault(arm, [])
    durs.append(float(dur_s))
    del durs[:-_MAX_SAMPLES]
    k = explore_k()
    if all(len(d) >= k for d in e["arms"].values()):
        mins = {a: min(d) for a, d in e["arms"].items()}
        e["winner"] = min(mins, key=mins.get)
        e["best_s"] = mins[e["winner"]]
        e["strikes"] = 0
        _GENERATION[0] += 1
        telemetry.record_event(
            "autotune_decision",
            fingerprint=key[0], device_kind=key[1], arm=e["winner"],
            source="explored", resolved=True,
            times={a + "_min_s": round(v, 6) for a, v in mins.items()},
        )


def timed(fn: Callable, *args) -> Tuple[Any, float]:
    """Run ``fn(*args)`` and return ``(out, wall_s)`` with a
    ``block_until_ready`` fence — the explore-phase measurement (always
    fenced; the steady-state path keeps telemetry's *sampled* fence)."""
    import jax

    t0 = time.perf_counter()
    out = fn(*args)
    # an asynchronous device error (OOM, runtime fault) surfaces here and
    # must propagate: a poisoned result is not a timing
    with telemetry.sync("autotune.timed"):  # the measured arm's fence
        jax.block_until_ready(out)
    return out, time.perf_counter() - t0


def explore(
    decision: Decision,
    arms: Mapping[str, Callable[[], Any]],
    *,
    site: str,
    forfeit: Tuple[str, ...] = (),
    programs: Optional[Mapping[str, Optional[str]]] = None,
) -> Any:
    """One explore round: run every arm of ``arms`` (arm name → thunk,
    the first being the reference) under :func:`timed`, fold each wall
    clock into ``decision.key`` and return the REFERENCE arm's output, so
    numerics never depend on tuning state.  An arm named in ``forfeit``
    that raises loses by an infinite time (which keeps the explore phase
    bounded); any other arm's exception propagates, and then nothing is
    observed.  ``programs`` maps an arm to the cost-ledger fingerprint its
    wall clock is also recorded under."""
    programs = programs or {}
    times: Dict[str, float] = {}
    with telemetry.span("autotune.explore", site=site):
        ref, *others = arms
        out, times[ref] = timed(arms[ref])
        for arm in others:
            try:
                times[arm] = timed(arms[arm])[1]
            except Exception:
                if arm not in forfeit:
                    raise
                times[arm] = float("inf")
    for arm, dur_s in times.items():
        observe(decision.key, arm, dur_s)
        telemetry.record_timing(programs.get(arm), dur_s)  # no program: no-op
    return out


def run(
    key: Tuple[str, str],
    arms: Mapping[str, Callable[[], Any]],
    *,
    prior: str,
    desc: str,
    site: str,
    cost: Optional[Mapping[str, dict]] = None,
    forfeit: Tuple[str, ...] = (),
) -> Any:
    """THE explore/exploit dispatch between lowerings of one operation:
    :func:`decide`, then :func:`explore` while the entry is unresolved,
    the winner alone afterwards.  ``cost`` gives an arm its cost-ledger
    program: ``sig`` (the tuple its fingerprint is taken of) beside what
    ``telemetry.ensure_program`` takes.  Such an arm's explore times land
    in the ledger, and as the winner it runs under telemetry's sampled
    fence with :func:`observe` watching it for degradation."""
    programs = {}
    for arm, model in (cost or {}).items():
        model = dict(model)
        programs[arm] = telemetry.fingerprint(model.pop("sig"))
        telemetry.ensure_program(programs[arm], **model)
    d = decide(key, prior, desc=desc, arms=tuple(arms))
    if d.explore:
        return explore(d, arms, site=site, forfeit=forfeit, programs=programs)
    if d.arm in programs:
        return telemetry.timed_call(
            programs[d.arm], arms[d.arm],
            observer=functools.partial(observe, key, d.arm),
        )
    return arms[d.arm]()


# ------------------------------------------------------------- HBM seeding


def note_budget_seed(site: str, granted: int, default: int) -> None:
    """Ledger one plan-time budget shrunk from measured free HBM."""
    _STATS["budget_seeds"] += 1
    telemetry.record_event(
        "autotune_budget", site=site, budget=int(granted),
        default=int(default), free_bytes=memtrack.min_free_bytes(),
    )


def note_staging_decline(key: Tuple[str, str], need: int, granted: int) -> None:
    """Ledger a ring dispatch refused because staging would not fit the
    measured free HBM (the caller falls back to GSPMD, whose
    tile/rechunk machinery degrades gracefully under pressure)."""
    _STATS["staging_declines"] += 1
    telemetry.record_event(
        "autotune_budget", site="ring_staging", fingerprint=key[0],
        device_kind=key[1], need=int(need), budget=int(granted),
        declined=True,
    )


# ---------------------------------------------------------------- warm start


def save(path) -> int:
    """Persist the tuning table as versioned JSON (atomic: tmp +
    ``os.replace``).  Keyed by (fingerprint, device kind) and stamped
    with the library version — :func:`load` refuses anything else.
    Returns the number of entries written."""
    entries = []
    for (fp, dk), e in _TABLE.items():
        entries.append({
            "fingerprint": fp,
            "device_kind": dk,
            "winner": e["winner"],
            "best_s": _finite(e["best_s"]),
            "desc": e["desc"],
            "arms": {a: [_finite(t) for t in d] for a, d in e["arms"].items()},
        })
    path = _write_doc(path, entries)
    _STATS["saves"] += 1
    telemetry.record_event(
        "autotune_cache", action="save", path=path, entries=len(entries),
    )
    return len(entries)


def _write_doc(path, entries) -> str:
    """One cache document, written atomically (tmp + ``os.replace``)."""
    doc = {
        "version": CACHE_VERSION,
        "library": __version__,
        "entries": entries,
    }
    path = os.fspath(path)
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
    os.replace(tmp, path)
    return path


def _finite(t):
    if t is None:
        return None
    t = float(t)
    return t if t < 1e9 else 1e9


def _parse_cache_doc(doc):
    """Validate + parse one cache document (the shared back half of
    :func:`load` and :func:`merge`).  Raises on anything :func:`load`
    would refuse — a merge must never launder a row load() rejects."""
    if not isinstance(doc, dict):
        raise ValueError("not a JSON object")
    if doc.get("version") != CACHE_VERSION:
        raise ValueError(f"cache version {doc.get('version')!r}, "
                         f"want {CACHE_VERSION}")
    if doc.get("library") != __version__:
        raise ValueError(f"library {doc.get('library')!r}, "
                         f"want {__version__!r}")
    entries = doc["entries"]
    parsed = []
    for ent in entries:
        w = ent.get("winner")
        # the entry's own arm set round-trips (every family shares one
        # cache file).  What this module can know alone is checked here;
        # that the names are the ones the site dispatches is checked by
        # decide(), where the site says what they are
        arm_names = tuple(ent.get("arms", {}))
        if not arm_names:
            raise ValueError("entry names no arms")
        if w is not None and w not in arm_names:
            raise ValueError(f"winner {w!r} outside entry arms")
        parsed.append((
            (str(ent["fingerprint"]), str(ent["device_kind"])),
            w,
            ent.get("best_s"),
            str(ent.get("desc") or ""),
            {a: [float(t) for t in ent.get("arms", {}).get(a, [])]
             for a in arm_names},
        ))
    return parsed


def _read_doc(path: str, site: str):
    """Read and parse one cache file; a file :func:`load` refuses is
    ignored whole, with a recorded ``fallback`` event, and gives ``None``."""
    try:
        with open(path) as f:
            return _parse_cache_doc(json.load(f))
    except Exception as exc:
        _STATS["fallbacks"] += 1
        telemetry.record_event("fallback", site=site, path=path, error=str(exc))
        return None


def load(path) -> int:
    """Restore a saved tuning table.  A corrupt, stale-version, or
    different-library file is IGNORED with a recorded ``fallback`` event
    (a warm start must never be able to break a cold one); entries for
    another device kind load fine — they simply never match a key here.
    Returns the number of entries restored (0 on fallback)."""
    path = os.fspath(path)
    parsed = _read_doc(path, "autotune.load")
    if parsed is None:
        return 0
    for key, w, best, desc, arms in parsed:
        e = _entry(key, desc, tuple(arms))
        e["winner"] = w
        e["best_s"] = float(best) if best is not None else None
        e["arms"] = arms
        e["strikes"] = 0
        e["loaded"] = True
    _STATS["cache_loads"] += len(parsed)
    _GENERATION[0] += 1
    telemetry.record_event(
        "autotune_cache", action="load", path=path, entries=len(parsed),
    )
    return len(parsed)


def _merge_prefers(new: dict, old: dict) -> bool:
    """Newest-best selection: a resolved winner beats an unresolved
    entry; between resolved entries the lower ``best_s`` wins; every
    tie goes to ``new`` — the later file in the merge argument list."""
    nw, ow = new["winner"], old["winner"]
    if (nw is None) != (ow is None):
        return nw is not None
    nb, ob = new["best_s"], old["best_s"]
    if nw is not None and nb is not None and ob is not None and nb != ob:
        return nb < ob
    return True


def merge(paths, out) -> str:
    """Merge several per-process tuning caches into ONE warm-start file.

    The serving-fleet story (ROADMAP item 2): every serving process
    :func:`save`\\ s its own table; deployment ships the union so the
    next generation warm-starts with zero explores.  Selection is
    **newest-best** per (fingerprint, device kind, arm set) — see
    :func:`_merge_prefers`.  A file :func:`load` would refuse (corrupt,
    stale cache version, different library version) is skipped whole
    with a recorded ``fallback`` event; its rows never reach the output.
    The merged file is written atomically in :func:`save`'s format and
    the path returned, also reachable as
    ``python -m heat_tpu.core.autotune --merge IN... --out OUT``."""
    chosen: Dict[tuple, dict] = {}
    sources = 0
    for path in paths:
        parsed = _read_doc(os.fspath(path), "autotune.merge")
        if parsed is None:
            continue
        sources += 1
        for key, w, best, desc, arms in parsed:
            entry = {
                "fingerprint": key[0],
                "device_kind": key[1],
                "winner": w,
                "best_s": _finite(float(best)) if best is not None else None,
                "desc": desc,
                "arms": {a: [_finite(t) for t in d] for a, d in arms.items()},
            }
            mkey = key + (tuple(sorted(arms)),)
            old = chosen.get(mkey)
            if old is None or _merge_prefers(entry, old):
                chosen[mkey] = entry
    out = _write_doc(out, sorted(
        chosen.values(), key=lambda e: (e["fingerprint"], e["device_kind"])
    ))
    telemetry.record_event(
        "autotune_cache", action="merge", path=out,
        entries=len(chosen), sources=sources,
    )
    return out


def _init_from_env() -> None:
    """Import-time warm start: ``HEAT_TPU_AUTOTUNE_CACHE=<path>`` loads
    the tuning table (a missing file is a fresh start, not a fallback).
    The XLA compilation cache is placed separately, by the entry point
    (:func:`heat_tpu.utils.compile_cache.enable`)."""
    path = os.environ.get("HEAT_TPU_AUTOTUNE_CACHE", "").strip()
    if not path or not enabled():
        return
    if os.path.exists(path):
        load(path)


# ------------------------------------------------------------------- report


def report(top: Optional[int] = None) -> dict:
    """The tuning table as a dashboard-ready dict: header (device kind,
    enabled, counters) + one row per entry, resolved winners first,
    then by fingerprint."""
    rows = []
    for (fp, dk), e in _TABLE.items():
        row = {
            "fingerprint": fp,
            "device_kind": dk,
            "desc": e["desc"],
            "winner": e["winner"],
            "source": ("cached" if e["loaded"] else
                       "explored" if e["winner"] else "prior"),
            "best_s": _finite(e["best_s"]),
            "arms": tuple(e["arms"]),
        }
        # per-arm columns keyed by the entry's own arm set:
        # ring_min_s/gspmd_min_s for matmul rows, classic_min_s/
        # kernel_min_s for the Pallas kernel sites
        row.update(_arm_times(e))
        for a, d in e["arms"].items():
            row[a + "_samples"] = len(d)
        rows.append(row)
    rows.sort(key=lambda r: (r["winner"] is None, r["fingerprint"]))
    if top is not None:
        rows = rows[:int(top)]
    return {
        "device_kind": device_kind(),
        "enabled": enabled(),
        "generation": _GENERATION[0],
        "stats": stats(),
        "rows": rows,
    }


_init_from_env()


# ---------------------------------------------------------------------- CLI


def _main(argv=None) -> int:
    """``python -m heat_tpu.core.autotune --merge IN [IN ...] --out OUT``
    — fleet-cache merge without writing a line of Python."""
    import argparse

    parser = argparse.ArgumentParser(
        prog="python -m heat_tpu.core.autotune",
        description="Merge per-process tuning caches into one warm-start file.",
    )
    parser.add_argument(
        "--merge", nargs="+", metavar="IN", required=True,
        help="input cache files (later files win ties: newest last)",
    )
    parser.add_argument(
        "--out", metavar="OUT", required=True, help="merged output path",
    )
    opts = parser.parse_args(argv)
    out = merge(opts.merge, opts.out)
    with open(out) as f:
        entries = len(json.load(f)["entries"])
    print(f"merged {len(opts.merge)} cache(s) -> {out} ({entries} entries)")
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
