"""Self-tuning runtime (ISSUE 11): explore/exploit matmul dispatch,
HBM-seeded budgets, and the persisted warm-start cache.

The suite runs with ``HEAT_TPU_AUTOTUNE=off`` (conftest default — counter
laws elsewhere need today's static dispatch bit-for-bit); each test here
opts back in through the API (``autotune.set_enabled(True)``) and
restores env control on the way out.  Doctrine stays "no mocks": the
explore tests run the real ring and GSPMD programs under measurement on
the real mesh, the seeding tests drive the real ``memory_stats()``
consumer through ``FaultInjector.low_hbm`` / ``memtrack.stats_override``,
and the persistence tests round-trip real JSON files."""

import json
import os
import tempfile
import unittest

import numpy as np
import pytest

import jax

import heat_tpu as ht
from heat_tpu.core import autotune, fusion, memtrack, telemetry
from heat_tpu.parallel import overlap, transport
from heat_tpu.utils import fault

from .base import TestCase, scripted_clock

_MULTI = len(jax.local_devices()) > 1

# clears the ring threshold at S>=2: ag bps = ceil(512/S)/S... for S=8,
# kb=64 → 64*1024*4 B/step × 7 steps ≈ 1.8 MiB ≥ 1 MiB
_BIG = ((256, 512), (512, 1024))
# stays under it: bps = 32*384*4 × 7 ≈ 336 KiB
_SMALL = ((512, 256), (256, 384))


class _Tuned:
    """Scoped tuning plane: enabled via API, events level, clean
    table/counters/recorder on both sides.  The round-19 per-link wire
    arms are forced OFF here: this file pins the MATMUL site's counter
    arithmetic (explores == k, table_size == 1, ...), and a winning ring
    arm would otherwise open its own wire entries per transfer geometry
    — whose laws test_wire.py pins separately."""

    def __init__(self, level="events"):
        self.level = level

    def __enter__(self):
        from heat_tpu.core import wire

        self.prev_level = telemetry.set_level(self.level)
        self.prev_on = autotune.set_enabled(True)
        self.prev_wire = wire.set_mode("off")
        telemetry.reset_all()
        telemetry.clear_events()
        autotune.reset()
        return self

    def __exit__(self, *exc):
        from heat_tpu.core import wire

        wire.set_mode(self.prev_wire)
        autotune.set_enabled(self.prev_on)
        autotune.reset()
        telemetry.reset_all()
        telemetry.clear_events()
        telemetry.set_level(self.prev_level)
        return False


def _mm_pair(shape_a=_SMALL[0], shape_b=_SMALL[1], split=0):
    rng = np.random.default_rng(7)
    a = ht.array(rng.random(shape_a).astype(np.float32), split=split)
    b = ht.array(rng.random(shape_b).astype(np.float32), split=split)
    return a, b


def _decision_events():
    return [e for e in telemetry.events() if e["kind"] == "autotune_decision"]


class TestEnvBytes(TestCase):
    """Satellite: ONE parser for byte-sized env knobs; malformed values
    raise (transport's behavior) instead of silently defaulting
    (overlap's old bug)."""

    def test_default_and_valid(self):
        self.assertEqual(autotune.env_bytes("X_B", 123, {}), 123)
        self.assertEqual(autotune.env_bytes("X_B", 123, {"X_B": ""}), 123)
        self.assertEqual(autotune.env_bytes("X_B", 123, {"X_B": " 456 "}), 456)

    def test_malformed_raises_with_name(self):
        for bad in ("lots", "-4", "0", "1.5"):
            with self.assertRaises(ValueError) as ctx:
                autotune.env_bytes("X_B", 123, {"X_B": bad})
            self.assertIn("X_B must be a positive integer (bytes)", str(ctx.exception))

    def test_transport_knob_unchanged(self):
        # the pre-existing contract (test_guard.py) now served by the
        # shared parser
        self.assertEqual(
            transport._env_tile_bytes({"HEAT_TPU_TILE_BYTES": "1048576"}),
            1 << 20,
        )
        self.assertEqual(transport._env_tile_bytes({}), 8 << 20)

    def test_ring_min_bytes_now_raises(self):
        # the satellite fix: a typo'd threshold must surface, not silently
        # run the 1 MiB default
        os.environ["HEAT_TPU_MATMUL_RING_MIN_BYTES"] = "garbage"
        try:
            with self.assertRaises(ValueError) as ctx:
                overlap._ring_min_bytes()
            self.assertIn(
                "HEAT_TPU_MATMUL_RING_MIN_BYTES must be a positive integer "
                "(bytes)", str(ctx.exception),
            )
        finally:
            del os.environ["HEAT_TPU_MATMUL_RING_MIN_BYTES"]
        self.assertEqual(overlap._ring_min_bytes(), 1 << 20)


class TestSuggestBudget(TestCase):
    """Satellite: the one free-HBM budget formula behind transport retry,
    kmeans packing, and plan-time seeding."""

    def test_formula(self):
        free = 8 << 20
        # clamp to request / fraction of free / floor
        self.assertEqual(
            memtrack.suggest_budget(1 << 20, fraction=0.25, free=free), 1 << 20
        )
        self.assertEqual(
            memtrack.suggest_budget(4 << 20, fraction=0.25, free=free), 2 << 20
        )
        self.assertEqual(
            memtrack.suggest_budget(4 << 20, fraction=0.25, floor=3 << 20, free=free),
            3 << 20,
        )
        # headroom reserved before the fraction
        self.assertEqual(
            memtrack.suggest_budget(
                4 << 20, fraction=1.0, headroom=6 << 20, free=free
            ),
            2 << 20,
        )

    def test_matches_informed_retry_formula(self):
        # exactly transport's informed first-retry sizing (ISSUE 10)
        free, halved = 2 << 20, transport.TILE_BYTES >> 1
        want = max(
            transport.TILE_FLOOR_BYTES,
            min(halved, int(free * transport._FREE_TILE_FRACTION)),
        )
        self.assertEqual(
            memtrack.suggest_budget(
                halved, fraction=transport._FREE_TILE_FRACTION,
                floor=transport.TILE_FLOOR_BYTES, free=free,
            ),
            want,
        )

    def test_statsless_is_none(self):
        # CPU reports no memory_stats: no fake budget, callers keep their
        # static defaults
        if memtrack.min_free_bytes() is None:
            self.assertIsNone(memtrack.suggest_budget(1 << 20))

    def test_override_supplies_free(self):
        with memtrack.stats_override([
            {"device": "fake0", "bytes_limit": 100, "bytes_in_use": 60}
        ]):
            self.assertEqual(
                memtrack.suggest_budget(1000, fraction=0.5), 20
            )

    def test_kmeans_pack_budget_routes_through_helper(self):
        import jax.numpy as jnp

        from heat_tpu.cluster import kmeans as km

        arr = jnp.asarray(
            np.random.default_rng(0).random((256, 64)), dtype=jnp.bfloat16
        )
        # tight free HBM (< 1 GiB headroom): the lane-pack must decline
        with memtrack.stats_override([
            {"device": "fake0", "bytes_limit": 1 << 30, "bytes_in_use": (1 << 30) - (64 << 20)}
        ]):
            self.assertIsNone(km._pack_lanes(arr))
        # plentiful: it packs
        with memtrack.stats_override([
            {"device": "fake0", "bytes_limit": 8 << 30, "bytes_in_use": 1 << 20}
        ]):
            packed = km._pack_lanes(arr)
        self.assertIsNotNone(packed)
        self.assertEqual(packed[3:], (64, 2))


class TestExploreExploit(TestCase):
    """Tentpole site 1: both arms measured for the first K calls, winner
    sticky by steady-state min_s, lazy chains consume (never explore)."""

    @unittest.skipUnless(_MULTI, "needs a multi-device mesh")
    def test_explore_then_sticky(self):
        with _Tuned(), scripted_clock(ring=0.001, gspmd=0.002):
            a, b = _mm_pair()
            k = autotune.explore_k()
            with fusion.fuse(False):
                for _ in range(k + 2):
                    out = ht.matmul(a, b)
                    _ = out.larray
            st = autotune.stats()
            self.assertEqual(st["explores"], k)
            self.assertEqual(st["cache_hits"], 2)
            self.assertEqual(st["decisions"], k + 2)
            self.assertEqual(st["table_size"], 1)
            self.assertEqual(st["resolved"], 1)
            # both arms really measured
            (key, entry), = autotune.table().items()
            self.assertGreaterEqual(len(entry["arms"]["ring"]), k)
            self.assertGreaterEqual(len(entry["arms"]["gspmd"]), k)
            self.assertEqual(entry["winner"], "ring")
            self.assertEqual(entry["best_s"], min(entry["arms"][entry["winner"]]))
            # the flight recorder saw the explores and the sticky phase
            sources = [e["source"] for e in _decision_events()]
            self.assertEqual(sources.count("explored"), k + 1)  # +1 resolution
            self.assertEqual(sources.count("cached"), 2)
            # numerics: explore returns the ring arm's result
            self.assert_array_equal(
                out, np.asarray(a.larray) @ np.asarray(b.larray), rtol=1e-4
            )

    @unittest.skipUnless(_MULTI, "needs a multi-device mesh")
    def test_chain_consumes_winner_never_explores(self):
        with _Tuned():
            a, b = _mm_pair()
            # lazy chains before any winner: static prior stands, recorded
            out = ht.matmul(a, b) + 1.0
            _ = out.larray
            st = autotune.stats()
            self.assertEqual(st["explores"], 0)
            self.assertEqual(st["priors"], 1)
            # resolve a winner eagerly on the same GEMM geometry
            with fusion.fuse(False):
                for _ in range(autotune.explore_k()):
                    _ = ht.matmul(a, b).larray
            self.assertEqual(autotune.stats()["resolved"], 1)
            # the chain now lowers with the cached winner — and because the
            # autotune generation salts the fusion cache key, it REBUILDS
            # rather than reusing the prior-mode executable
            out2 = ht.matmul(a, b) + 1.0
            _ = out2.larray
            last = overlap.stats()["last"]
            self.assertEqual(last["reason"], "autotune:cached")
            chain_evs = [
                e for e in _decision_events() if e.get("site") == "chain"
            ]
            self.assertEqual(chain_evs[-1]["source"], "cached")
            self.assert_array_equal(
                out2, np.asarray(a.larray) @ np.asarray(b.larray) + 1.0,
                rtol=1e-4,
            )

    @unittest.skipUnless(_MULTI, "needs a multi-device mesh")
    def test_off_restores_static_dispatch(self):
        # HEAT_TPU_AUTOTUNE=off (the conftest suite default): dispatch is
        # exactly the byte-threshold census law — no explores, no table,
        # no autotune events
        prev = telemetry.set_level("events")
        telemetry.reset_all()
        telemetry.clear_events()
        autotune.reset()
        try:
            self.assertFalse(autotune.enabled())
            big = _mm_pair(*_BIG)
            small = _mm_pair(*_SMALL)
            with fusion.fuse(False):
                for _ in range(2):
                    _ = ht.matmul(*big).larray
                    _ = ht.matmul(*small).larray
            sched = overlap.stats()["by_schedule"]
            self.assertEqual(sched["ring_ag"], 2)   # big: above threshold
            self.assertEqual(sched["gspmd"], 2)     # small: below threshold
            self.assertEqual(overlap.stats()["last"]["reason"], "below-threshold")
            st = autotune.stats()
            for c in ("decisions", "explores", "cache_hits", "priors"):
                self.assertEqual(st[c], 0, c)
            self.assertEqual(st["table_size"], 0)
            self.assertEqual(_decision_events(), [])
        finally:
            autotune.reset()
            telemetry.reset_all()
            telemetry.clear_events()
            telemetry.set_level(prev)

    def test_degradation_reexplores(self):
        # synthetic clock: a sticky winner that turns 2x slower on two
        # consecutive sampled calls goes back to explore
        with _Tuned():
            key = ("fp_degrade", "test:kind")
            for _ in range(autotune.explore_k()):
                d = autotune.decide(key, "ring", arms=overlap.ARMS)
                self.assertTrue(d.explore)
                autotune.observe(key, "ring", 0.001)
                autotune.observe(key, "gspmd", 0.002)
            self.assertEqual(autotune.winner(key), "ring")
            gen = autotune.salt()[2]
            autotune.observe(key, "ring", 0.0011)   # fine: strikes stay 0
            autotune.observe(key, "ring", 0.0030)   # strike 1
            autotune.observe(key, "ring", 0.0012)   # recovery clears it
            autotune.observe(key, "ring", 0.0030)   # strike 1
            self.assertIsNotNone(autotune.winner(key))
            autotune.observe(key, "ring", 0.0031)   # strike 2 → re-explore
            self.assertIsNone(autotune.winner(key))
            self.assertEqual(autotune.stats()["re_explores"], 1)
            self.assertGreater(autotune.salt()[2], gen)
            self.assertTrue(
                any(e["kind"] == "autotune_reexplore" for e in telemetry.events())
            )


class TestPersistence(TestCase):
    """Tentpole site 3: versioned atomic save/load; corrupt or stale
    files fall back to a cold start with a recorded event.  Table-level
    laws run at EVERY mesh size (ci.sh replays this file at 8/4/1)."""

    def _resolve(self, key, winner="ring"):
        slow = {"ring": 0.002, "gspmd": 0.001}
        slow[winner] = 0.0005
        for _ in range(autotune.explore_k()):
            autotune.decide(key, "ring", arms=overlap.ARMS)
            for arm in overlap.ARMS:
                autotune.observe(key, arm, slow[arm])

    def test_save_load_roundtrip(self):
        with _Tuned(), tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "tune.json")
            k1 = ("fp_one", autotune.device_kind())
            k2 = ("fp_two", autotune.device_kind())
            self._resolve(k1, "ring")
            self._resolve(k2, "gspmd")
            n = autotune.save(path)
            self.assertEqual(n, 2)
            doc = json.load(open(path))
            self.assertEqual(doc["version"], autotune.CACHE_VERSION)
            self.assertEqual(doc["library"], ht.__version__)
            autotune.reset()
            self.assertEqual(autotune.stats()["table_size"], 0)
            self.assertEqual(autotune.load(path), 2)
            st = autotune.stats()
            self.assertEqual(st["cache_loads"], 2)
            self.assertEqual(st["fallbacks"], 0)
            self.assertEqual(autotune.winner(k1), "ring")
            self.assertEqual(autotune.winner(k2), "gspmd")
            # loaded entries serve decisions without exploring
            d = autotune.decide(k1, "gspmd", arms=overlap.ARMS)
            self.assertEqual((d.arm, d.source, d.explore), ("ring", "cached", False))
            row = [r for r in autotune.report()["rows"] if r["fingerprint"] == "fp_one"][0]
            self.assertEqual(row["source"], "cached")

    def test_corrupt_and_stale_ignored_with_fallback_event(self):
        with _Tuned(), tempfile.TemporaryDirectory() as td:
            cases = {
                "not_json.json": "{nope",
                "not_object.json": json.dumps([1, 2]),
                "stale_version.json": json.dumps(
                    {"version": 999, "library": ht.__version__, "entries": []}
                ),
                "other_library.json": json.dumps(
                    {"version": autotune.CACHE_VERSION, "library": "9.9.9",
                     "entries": []}
                ),
                "bad_arm.json": json.dumps(
                    {"version": autotune.CACHE_VERSION,
                     "library": ht.__version__,
                     "entries": [{"fingerprint": "f", "device_kind": "d",
                                  "winner": "quantum"}]}
                ),
            }
            for i, (name, content) in enumerate(cases.items(), 1):
                path = os.path.join(td, name)
                with open(path, "w") as f:
                    f.write(content)
                self.assertEqual(autotune.load(path), 0, name)
                self.assertEqual(autotune.stats()["fallbacks"], i, name)
                self.assertEqual(autotune.stats()["table_size"], 0, name)
            evs = [e for e in telemetry.events() if e["kind"] == "fallback"
                   and e.get("site") == "autotune.load"]
            self.assertEqual(len(evs), len(cases))
            self.assertTrue(all(e["error"] for e in evs))

    def test_save_is_atomic(self):
        with _Tuned(), tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "tune.json")
            self._resolve(("fp_a", "dk"))
            autotune.save(path)
            self.assertEqual(os.listdir(td), ["tune.json"])  # no tmp litter

    @unittest.skipUnless(_MULTI, "needs a multi-device mesh")
    def test_warm_start_zero_explores(self):
        # the acceptance law, in-process: a table resolved by process 1
        # lets the same workload replay with ZERO explore calls (the
        # two-OS-process version runs in ci.sh stage 15)
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "tune.json")
            a, b = _mm_pair()
            with _Tuned(), scripted_clock(ring=0.001, gspmd=0.002):
                with fusion.fuse(False):
                    for _ in range(autotune.explore_k() + 1):
                        _ = ht.matmul(a, b).larray
                self.assertGreater(autotune.stats()["explores"], 0)
                autotune.save(path)
            with _Tuned(), scripted_clock(ring=0.001, gspmd=0.002):
                autotune.load(path)
                with fusion.fuse(False):
                    for _ in range(3):
                        _ = ht.matmul(a, b).larray
                st = autotune.stats()
                self.assertEqual(st["explores"], 0)
                self.assertEqual(st["cache_hits"], 3)
                self.assertTrue(
                    all(e["source"] == "cached" for e in _decision_events())
                )


class TestHBMSeeding(TestCase):
    """Tentpole site 2: budgets seeded from measured free HBM at plan
    time — before the first RESOURCE_EXHAUSTED, not after it."""

    @unittest.skipUnless(_MULTI, "needs a multi-device mesh")
    def test_low_hbm_seeds_transport_tile_budget(self):
        with _Tuned():
            free = 2 << 20
            inj = fault.FaultInjector(seed=0).low_hbm(free)
            with fault.injected(inj):
                x = ht.arange(16 * 64, dtype=ht.float32, split=0).reshape((16, 64))
                x.resplit_(1)
            st = transport.stats()
            want = max(
                transport.TILE_FLOOR_BYTES,
                min(transport.TILE_BYTES,
                    int(free * transport._FREE_TILE_FRACTION)),
            )
            self.assertEqual(st["last_tile_bytes"], want)
            self.assertEqual(st["oom_retries"], 0)  # seeded, not recovered
            self.assertGreaterEqual(autotune.stats()["budget_seeds"], 1)
            evs = [e for e in telemetry.events() if e["kind"] == "autotune_budget"]
            self.assertTrue(evs)
            self.assertEqual(evs[0]["budget"], want)

    @unittest.skipUnless(_MULTI, "needs a multi-device mesh")
    def test_off_keeps_static_tile_budget(self):
        # same injected pressure, tuning plane off: today's static budget
        inj = fault.FaultInjector(seed=0).low_hbm(2 << 20)
        transport.reset_stats()
        try:
            with fault.injected(inj):
                x = ht.arange(16 * 64, dtype=ht.float32, split=0).reshape((16, 64))
                x.resplit_(1)
            self.assertEqual(
                transport.stats()["last_tile_bytes"], transport.TILE_BYTES
            )
        finally:
            transport.reset_stats()

    @unittest.skipUnless(_MULTI, "needs a multi-device mesh")
    def test_ring_staging_declined_under_pressure(self):
        with _Tuned():
            a, b = _mm_pair(*_BIG)
            inj = fault.FaultInjector(seed=0).low_hbm(64 << 10)
            with fault.injected(inj):
                with fusion.fuse(False):
                    out = ht.matmul(a, b)
            # ring refused up front; the GSPMD fallback still computes
            self.assertEqual(overlap.stats()["last"]["reason"], "hbm-budget")
            self.assertGreaterEqual(autotune.stats()["staging_declines"], 1)
            self.assertEqual(autotune.stats()["explores"], 0)
            self.assert_array_equal(
                out, np.asarray(a.larray) @ np.asarray(b.larray), rtol=1e-4
            )


class TestOpsSurface(TestCase):
    """Satellite: Prometheus gauges + the report table."""

    def test_prometheus_gauges(self):
        with _Tuned():
            self._seed_one()
            text = telemetry.export_prometheus()
            for fam in (
                "heat_tpu_autotune_table_size",
                "heat_tpu_autotune_explores",
                "heat_tpu_autotune_cache_hits",
                "heat_tpu_autotune_cache_loads",
            ):
                self.assertIn(fam, text)
            line = [l for l in text.splitlines()
                    if l.startswith("heat_tpu_autotune_table_size")][0]
            self.assertEqual(line.split()[-1], "1")

    def _seed_one(self):
        key = ("fp_prom", "test:kind")
        for _ in range(autotune.explore_k()):
            autotune.decide(key, "ring", arms=overlap.ARMS)
            autotune.observe(key, "ring", 0.001)
            autotune.observe(key, "gspmd", 0.002)

    def test_report_shape(self):
        with _Tuned():
            self._seed_one()
            rep = telemetry.autotune_report()
            self.assertTrue(rep["enabled"])
            self.assertEqual(len(rep["rows"]), 1)
            row = rep["rows"][0]
            self.assertEqual(row["winner"], "ring")
            self.assertEqual(row["source"], "explored")
            self.assertEqual(row["ring_min_s"], 0.001)
            self.assertEqual(row["gspmd_min_s"], 0.002)
            self.assertEqual(rep["stats"]["resolved"], 1)

class TestMerge(TestCase):
    """`autotune.merge` (ISSUE 14 satellite): fleet caches fold into one
    warm-start file, newest-best per (fingerprint, device kind, arms),
    refusing whole files that `load` would refuse."""

    @staticmethod
    def _doc(entries, library=None):
        return {
            "version": autotune.CACHE_VERSION,
            "library": ht.__version__ if library is None else library,
            "entries": entries,
        }

    @staticmethod
    def _entry(fp, winner, best, arms=None):
        arms = arms or {"ring": [best or 0.01], "gspmd": [0.05]}
        return {"fingerprint": fp, "device_kind": "cpu", "winner": winner,
                "best_s": best, "desc": "d", "arms": arms}

    def test_newest_best_selection(self):
        with _Tuned(), tempfile.TemporaryDirectory() as td:
            p1, p2, out = (os.path.join(td, n) for n in ("a.json", "b.json", "m.json"))
            # p1: slower resolved winner for fp_x + an unresolved fp_y
            json.dump(self._doc([
                self._entry("fp_x", "ring", 0.02),
                self._entry("fp_y", None, None, {"classic": [0.5], "kernel": []}),
            ]), open(p1, "w"))
            # p2 (newer): faster winner for fp_x, resolved fp_y
            json.dump(self._doc([
                self._entry("fp_x", "gspmd", 0.01,
                            {"ring": [0.03], "gspmd": [0.01]}),
                self._entry("fp_y", "kernel", 0.1,
                            {"classic": [0.5], "kernel": [0.1]}),
            ]), open(p2, "w"))
            self.assertEqual(autotune.merge([p1, p2], out), out)
            doc = json.load(open(out))
            self.assertEqual(doc["version"], autotune.CACHE_VERSION)
            self.assertEqual(doc["library"], ht.__version__)
            got = {e["fingerprint"]: e for e in doc["entries"]}
            self.assertEqual(len(got), 2)
            # lower best_s wins regardless of order...
            self.assertEqual(got["fp_x"]["winner"], "gspmd")
            self.assertEqual(got["fp_x"]["best_s"], 0.01)
            # ...and resolved beats unresolved
            self.assertEqual(got["fp_y"]["winner"], "kernel")
            # the merged file round-trips through load
            autotune.reset()
            self.assertEqual(autotune.load(out), 2)
            self.assertEqual(autotune.winner(("fp_x", "cpu")), "gspmd")

    def test_ties_go_to_the_later_path(self):
        with _Tuned(), tempfile.TemporaryDirectory() as td:
            p1, p2, out = (os.path.join(td, n) for n in ("a.json", "b.json", "m.json"))
            json.dump(self._doc([self._entry("fp", "ring", 0.01)]), open(p1, "w"))
            newer = self._entry("fp", "ring", 0.01)
            newer["desc"] = "newest"
            json.dump(self._doc([newer]), open(p2, "w"))
            autotune.merge([p1, p2], out)
            (entry,) = json.load(open(out))["entries"]
            self.assertEqual(entry["desc"], "newest")

    def test_cross_library_rows_refused_whole_file(self):
        with _Tuned(), tempfile.TemporaryDirectory() as td:
            good = os.path.join(td, "good.json")
            alien = os.path.join(td, "alien.json")
            broken = os.path.join(td, "broken.json")
            out = os.path.join(td, "m.json")
            json.dump(self._doc([self._entry("fp_ok", "ring", 0.01)]), open(good, "w"))
            json.dump(self._doc([self._entry("fp_alien", "ring", 0.001)],
                                library="9.9.9"), open(alien, "w"))
            with open(broken, "w") as f:
                f.write("{nope")
            autotune.merge([alien, good, broken], out)
            doc = json.load(open(out))
            self.assertEqual([e["fingerprint"] for e in doc["entries"]], ["fp_ok"])
            self.assertEqual(autotune.stats()["fallbacks"], 2)
            evs = [e for e in telemetry.events()
                   if e["kind"] == "fallback" and e.get("site") == "autotune.merge"]
            self.assertEqual(len(evs), 2)

    def test_cli_entry_point(self):
        with _Tuned(), tempfile.TemporaryDirectory() as td:
            p1 = os.path.join(td, "a.json")
            out = os.path.join(td, "m.json")
            json.dump(self._doc([self._entry("fp", "ring", 0.01)]), open(p1, "w"))
            rc = autotune._main(["--merge", p1, p1, "--out", out])
            self.assertEqual(rc, 0)
            self.assertEqual(len(json.load(open(out))["entries"]), 1)

    def test_wire_arm_entries_merge_and_round_trip(self):
        # ISSUE 16: the wire arms are first-class merge citizens — fleet
        # caches carrying ("wire_f32","wire_int8","wire_fp8") rows fold
        # newest-best and serve back through the --merge CLI + load
        def _wire_entry(fp, winner, best, f32=0.02):
            return self._entry(
                fp, winner, best,
                {"wire_f32": [f32], "wire_int8": [best or 0.01],
                 "wire_fp8": []},
            )

        with _Tuned(), tempfile.TemporaryDirectory() as td:
            p1, p2, out = (
                os.path.join(td, n) for n in ("a.json", "b.json", "m.json")
            )
            json.dump(self._doc([
                _wire_entry("fp_w", "wire_int8", 0.02),
                self._entry("fp_mm", "ring", 0.03),
            ]), open(p1, "w"))
            # newer + faster: the int8 wire win survives the fold
            json.dump(self._doc([
                _wire_entry("fp_w", "wire_int8", 0.005),
            ]), open(p2, "w"))
            rc = autotune._main(["--merge", p1, p2, "--out", out])
            self.assertEqual(rc, 0)
            doc = json.load(open(out))
            got = {e["fingerprint"]: e for e in doc["entries"]}
            self.assertEqual(set(got), {"fp_w", "fp_mm"})
            self.assertEqual(got["fp_w"]["winner"], "wire_int8")
            self.assertEqual(got["fp_w"]["best_s"], 0.005)
            self.assertEqual(
                set(got["fp_w"]["arms"]),
                {"wire_f32", "wire_int8", "wire_fp8"},
            )
            # the merged file round-trips: the wire winner is served
            autotune.reset()
            self.assertEqual(autotune.load(out), 2)
            self.assertEqual(
                autotune.winner(("fp_w", "cpu")), "wire_int8"
            )


# ------------------------------------------------------------------ the seam
# autotune.key / autotune.run / autotune.explore: the one place the
# explore/exploit protocol is written.  Plain pytest functions, so the
# cases are parametrised and each counts.


@pytest.mark.parametrize(
    "family, site, geometry, want",
    [
        # fingerprints printed by the parent commit's kernel_key / quant_key
        # / spmv_key / wire_key / stream_key / matmul_key for these arguments:
        # a HEAT_TPU_AUTOTUNE_CACHE file written before the seam stays valid
        ("kernel", "qr_panel", (4096, 256, "float32", True, 1), "bece932f73e2"),
        ("quant", "linear", (64, 128, 256, "int8", 8), "29091f48d567"),
        ("spmv", "spmv_csr", (1000, 1000, 4, 13, 128, "float32", 8), "0ca49b9d7329"),
        ("wire", "resplit", ((512, 64), 0, 1, "float32", 8), "eca15d039228"),
        ("stream", "kmeans_fit", (100000, 64, "float32", 8, 27), "595c6a08d65b"),
        # the parent's matmul_key(case, out_split, m, k, n, size, comp)
        ("matmul", "ag", (0, 512, 256, 128, 8, "float32"), "cbcb26037aa9"),
    ],
)
def test_key_is_the_parents_fingerprint(family, site, geometry, want):
    assert autotune.key(family, site, *geometry) == (want, autotune.device_kind())


class _Arm:
    """One lowering under a scripted clock: counts its runs, returns its
    own array, may raise."""

    def __init__(self, value, raises=None):
        self.out = jax.numpy.full(4, value)
        self.raises = raises
        self.runs = 0

    def __call__(self):
        self.runs += 1
        if self.raises is not None:
            raise self.raises
        return self.out


_BOOM = RuntimeError("arm cannot run")

# name: (arm -> (scripted seconds, exception or None), forfeit, cost arm,
#        expected winner or the exception that must propagate)
_SEAM_CASES = {
    "reference_wins": ({"ref": (1.0, None), "alt": (2.0, None)}, (), None, "ref"),
    "other_arm_wins": ({"ref": (2.0, None), "alt": (1.0, None)}, (), None, "alt"),
    "three_arms_last_wins": (
        {"ref": (3.0, None), "alt": (2.0, None), "alt2": (1.0, None)},
        (), None, "alt2",
    ),
    "forfeiting_arm_gets_inf_and_loses": (
        {"ref": (2.0, None), "alt": (1.0, _BOOM)}, ("alt",), None, "ref",
    ),
    "forfeit_named_arm_that_runs_can_win": (
        {"ref": (2.0, None), "alt": (1.0, None)}, ("alt",), None, "alt",
    ),
    "other_exception_propagates": (
        {"ref": (2.0, None), "alt": (1.0, _BOOM)}, (), None, _BOOM,
    ),
    "winner_with_a_cost_is_ledgered_and_watched": (
        {"ref": (2.0, None), "alt": (1.0, None)}, (), "alt", "alt",
    ),
}


@pytest.mark.parametrize("case", sorted(_SEAM_CASES))
def test_seam_protocol(case, monkeypatch):
    script, forfeit, cost_arm, want = _SEAM_CASES[case]
    arms = {a: _Arm(i, exc) for i, (a, (_, exc)) in enumerate(script.items())}
    by_thunk = {id(fn): a for a, fn in arms.items()}
    timed_calls = []

    def timed(fn, *args):  # the scripted clock: the one explore-phase timer
        timed_calls.append(by_thunk[id(fn)])
        return fn(*args), script[by_thunk[id(fn)]][0]

    monkeypatch.setattr(autotune, "timed", timed)
    cost = None
    if cost_arm:
        cost = {cost_arm: dict(sig=("seam_probe", case), kind="seam_probe",
                               ops=1, flops=8.0, hbm_bytes=16.0)}
    key = autotune.key("probe", case, 4)
    k = autotune.explore_k()

    def call():
        return autotune.run(
            key, arms, prior="ref", desc=case, site="probe",
            cost=cost, forfeit=forfeit,
        )

    with _Tuned():
        if isinstance(want, Exception):
            with pytest.raises(RuntimeError, match="arm cannot run"):
                call()
            # a poisoned round is no measurement: nothing was observed
            assert autotune.table()[key]["arms"] == {"ref": [], "alt": []}
            assert autotune.stats()["explores"] == 1
            return
        for i in range(1, k + 1):
            out = call()
            # explore: every arm timed once, the REFERENCE result returned
            assert out is arms["ref"].out
            assert timed_calls == list(arms) * i
            assert {a: len(d) for a, d in autotune.table()[key]["arms"].items()} \
                == dict.fromkeys(arms, i)
        entry = autotune.table()[key]
        assert entry["winner"] == want
        for a, (secs, exc) in script.items():
            assert entry["arms"][a] == [float("inf") if exc else secs] * k
        spans = [e for e in telemetry.events("span_begin")
                 if e["name"] == "autotune.explore"]
        assert [e["site"] for e in spans] == ["probe"] * k
        # exploit: the winner alone, no timer of the explore phase
        runs = {a: fn.runs for a, fn in arms.items()}
        for _ in range(2):
            assert call() is arms[want].out
        assert timed_calls == list(arms) * k
        runs[want] += 2
        assert {a: fn.runs for a, fn in arms.items()} == runs
        st = autotune.stats()
        assert (st["explores"], st["cache_hits"], st["decisions"]) == (k, 2, k + 2)
        if cost_arm:
            (prog,) = [p for p in telemetry.programs() if p["kind"] == "seam_probe"]
            # k explore times and two watched runs (events level: all sampled)
            assert prog["calls"] == k + 2
            # the watch is alive: two slow samples send the winner back
            with scripted_clock(**{want: 10.0}):
                call(), call()
            assert autotune.table()[key]["winner"] is None
            assert autotune.stats()["re_explores"] == 1


@pytest.mark.parametrize(
    "alien_arms, alien_winner",
    [
        ({"dense": [0.001] * 3, "gather": [0.002] * 3}, "dense"),
        ({"classic": [0.002] * 3, "kernel": [0.001] * 3, "turbo": [1e-9] * 3}, "turbo"),
    ],
    ids=["another_familys_arms", "a_superset_with_an_unknown_winner"],
)
def test_planted_entry_with_other_arms_is_never_served(alien_arms, alien_winner, tmp_path):
    """A cache file is input from outside the program.  load() cannot
    know which arms a site dispatches; the site can: an entry under its
    key that names any other arm set is dropped at the consult, explored
    afresh, and leaves a ``fallback`` event."""
    key = autotune.key("kernel", "probe_site", 64, 8)
    path = tmp_path / "planted.json"
    path.write_text(json.dumps({
        "version": autotune.CACHE_VERSION,
        "library": ht.__version__,
        "entries": [{"fingerprint": key[0], "device_kind": key[1],
                     "winner": alien_winner, "best_s": 1e-9, "desc": "planted",
                     "arms": alien_arms}],
    }))
    arms = {"classic": _Arm(0), "kernel": _Arm(1)}
    with _Tuned():
        assert autotune.load(path) == 1
        assert autotune.winner(key) == alien_winner
        out = autotune.run(key, arms, prior="classic", desc="probe", site="probe")
        assert out is arms["classic"].out
        assert (arms["classic"].runs, arms["kernel"].runs) == (1, 1)
        entry = autotune.table()[key]
        assert set(entry["arms"]) == {"classic", "kernel"}
        assert entry["winner"] is None and not entry["loaded"]
        st = autotune.stats()
        assert (st["fallbacks"], st["explores"], st["cache_hits"]) == (1, 1, 1)
        (ev,) = [e for e in telemetry.events("fallback")
                 if e.get("site") == "autotune.decide"]
        assert ev["fingerprint"] == key[0] and alien_winner in ev["error"]


if __name__ == "__main__":
    unittest.main()
