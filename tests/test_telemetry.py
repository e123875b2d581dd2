"""Unified telemetry (ISSUE 8): registry laws, flight recorder, spans,
cost ledger, and FaultInjector-driven degradation trails.

Doctrine stays "no mocks": the trail tests inject faults through the real
:class:`~heat_tpu.utils.fault.FaultInjector` / ``guard`` hooks and read
the degradation back out of ``ht.telemetry.events()`` — the flight
recorder must witness the production OOM-backoff and eager-fallback paths
exactly as they ran.
"""

import importlib
import os
import re
import threading
import unittest
import warnings
from unittest import mock

import numpy as np

import jax

import heat_tpu as ht
from heat_tpu.core import fusion, guard, telemetry
from heat_tpu.parallel import overlap, transport
from heat_tpu.utils import fault

from .base import TestCase


def _mesh(n):
    from heat_tpu.parallel.mesh import local_mesh

    return local_mesh(n)


def _reset_counters():
    fusion.reset_cache()
    transport.reset_stats()
    overlap.reset_stats()


class _EventsLevel:
    """Scoped events level + clean recorder/ledger on both sides."""

    def __init__(self, level="events"):
        self.level = level

    def __enter__(self):
        self.prev = telemetry.set_level(self.level)
        telemetry.clear_events()
        return self

    def __exit__(self, *exc):
        telemetry.set_level(self.prev)
        telemetry.clear_events()
        return False


class TestRegistryLaws(TestCase):
    """snapshot()/reset_all() vs the per-module shim accessors."""

    def setUp(self):
        _reset_counters()

    def tearDown(self):
        _reset_counters()

    def test_snapshot_covers_all_three_groups(self):
        snap = telemetry.snapshot()
        for group in ("fusion", "transport", "overlap"):
            self.assertIn(group, snap)

    def _law(self, comm):
        """At any mesh size: run real traffic, then (a) each module shim
        returns exactly the registry snapshot, (b) reset_all() restores
        the registered defaults, (c) module-level aliases survive reset."""
        _reset_counters()
        rng = np.random.default_rng(comm.size)
        a = ht.array(
            rng.random((12, 8)).astype(np.float32), split=0, comm=comm
        )
        chained = (a + 1.0) * 2.0 - 0.5
        _ = chained.larray
        if comm.size > 1:
            _ = ((a * 3.0).resplit(1)).larray
        overlap.set_mode("gspmd")
        try:
            with fusion.fuse(False):
                _ = ht.matmul(a, a.T.resplit(None) if comm.size > 1 else a.T)
        finally:
            overlap.set_mode(None)

        snap = telemetry.snapshot()
        self.assertEqual(snap["fusion"], fusion.cache_stats())
        self.assertEqual(snap["transport"], transport.stats())
        self.assertEqual(snap["overlap"], overlap.stats())
        self.assertGreaterEqual(snap["fusion"]["misses"], 1)
        self.assertGreaterEqual(snap["overlap"]["calls"], 1)

        telemetry.reset_all()
        after = telemetry.snapshot()
        self.assertEqual(after["fusion"]["misses"], 0)
        self.assertEqual(after["fusion"]["roots_per_program"], {})
        self.assertEqual(after["transport"]["oom_retries"], 0)
        self.assertEqual(after["transport"]["retries_by_kind"], {})
        self.assertEqual(after["overlap"]["calls"], 0)
        self.assertIsNone(after["overlap"]["last"])
        # the in-place reset keeps module aliases live (the drift class the
        # registry exists to kill: one defaults dict, no hand-kept resets)
        self.assertIs(fusion._FALLBACK_REASONS, fusion._STATS["fallback_reasons"])
        self.assertIs(fusion._ROOTS_PER_PROGRAM, fusion._STATS["roots_per_program"])

    def test_laws_mesh1(self):
        self._law(_mesh(1))

    @unittest.skipUnless(len(jax.devices()) >= 4, "needs >= 4 devices")
    def test_laws_mesh4(self):
        self._law(_mesh(4))

    @unittest.skipUnless(len(jax.devices()) >= 8, "needs >= 8 devices")
    def test_laws_mesh8(self):
        self._law(self.comm)

    def test_prometheus_export_well_formed(self):
        _ = ((ht.arange(16, dtype=ht.float32, split=0) + 1.0) * 2.0).larray
        text = telemetry.export_prometheus()
        lines = [ln for ln in text.splitlines() if ln]
        self.assertTrue(lines)
        helped, typed = set(), set()
        for ln in lines:
            if ln.startswith("# HELP "):
                helped.add(ln.split(" ")[2])
            elif ln.startswith("# TYPE "):
                _, _, metric, mtype = ln.split(" ")
                self.assertEqual(mtype, "gauge")
                typed.add(metric)
            else:
                self.assertFalse(ln.startswith("#"))  # no stray comments
                metric, value = ln.rsplit(" ", 1)
                family = metric.split("{", 1)[0]  # labeled program samples
                self.assertIn(family, typed)   # every sample was typed
                self.assertIn(family, helped)  # ... and documented
                float(value)  # every sample is numeric
        for expected in (
            "heat_tpu_fusion_misses",
            "heat_tpu_transport_oom_retries",
            "heat_tpu_overlap_by_schedule_gspmd",
            "heat_tpu_telemetry_events",
        ):
            self.assertIn(expected, typed)

    def test_prometheus_golden_format(self):
        # one counter, golden exposition: metric-unsafe characters in the
        # group/counter names escape to `_`, the HELP line keeps the
        # original dotted path, TYPE precedes the sample
        telemetry.register_group("weird.group", {"hit rate%": 3})
        try:
            text = telemetry.export_prometheus()
        finally:
            telemetry._GROUPS.pop("weird.group", None)
        golden = (
            "# HELP heat_tpu_weird_group_hit_rate_ "
            "heat_tpu telemetry gauge weird.group.hit rate%\n"
            "# TYPE heat_tpu_weird_group_hit_rate_ gauge\n"
            "heat_tpu_weird_group_hit_rate_ 3"
        )
        self.assertIn(golden, text)
        self.assertTrue(text.endswith("\n"))

    def test_snapshot_has_telemetry_group(self):
        with _EventsLevel():
            telemetry.record_event("probe")
            snap = telemetry.snapshot()
        self.assertIn("telemetry", snap)
        tele = snap["telemetry"]
        self.assertEqual(tele["level"], "events")
        self.assertEqual(tele["events"], 1)
        self.assertEqual(tele["capacity"], telemetry._RING.maxlen)
        self.assertIn("events_dropped", tele)
        self.assertIn("programs", tele)

    def test_snapshot_counts_dropped_events(self):
        prev_cap = telemetry.set_capacity(4)
        try:
            with _EventsLevel():
                for i in range(10):
                    telemetry.record_event("probe", i=i)
                self.assertEqual(
                    telemetry.snapshot()["telemetry"]["events_dropped"], 6
                )
        finally:
            telemetry.set_capacity(prev_cap)


class TestFlightRecorder(TestCase):
    def test_ring_capacity_and_ordering(self):
        with _EventsLevel():
            prev_cap = telemetry.set_capacity(8)
            try:
                for i in range(20):
                    telemetry.record_event("probe", i=i)
                got = telemetry.events("probe")
                self.assertEqual(len(got), 8)
                # newest 8 survive, oldest first, seq strictly ascending
                self.assertEqual([e["i"] for e in got], list(range(12, 20)))
                seqs = [e["seq"] for e in got]
                self.assertEqual(seqs, sorted(seqs))
                ts = [e["ts"] for e in got]
                self.assertEqual(ts, sorted(ts))
            finally:
                telemetry.set_capacity(prev_cap)

    def test_events_since_cursor(self):
        with _EventsLevel():
            seqs = [telemetry.record_event("probe", i=i) for i in range(6)]
            # an external poller feeds back the last seq it saw
            got = telemetry.events(since=seqs[3])
            self.assertEqual([e["i"] for e in got], [4, 5])
            self.assertEqual(telemetry.events("probe", since=seqs[-1]), [])
            # since=None is the full ring (back-compat)
            self.assertEqual(len(telemetry.events("probe")), 6)

    def test_events_carry_thread_ident(self):
        with _EventsLevel():
            telemetry.record_event("probe")
            got = {}

            def worker():
                telemetry.record_event("probe")
                got["tid"] = threading.get_ident()

            t = threading.Thread(target=worker)
            t.start()
            t.join(timeout=5)
            evts = telemetry.events("probe")
            self.assertEqual(evts[0]["tid"], threading.get_ident())
            self.assertEqual(evts[1]["tid"], got["tid"])
            # a caller field named like an envelope key is re-keyed
            telemetry.record_event("probe", tid="shadow")
            self.assertEqual(telemetry.events("probe")[-1]["x_tid"], "shadow")

    def test_off_records_nothing(self):
        prev = telemetry.set_level("off")
        telemetry.clear_events()
        telemetry.reset_programs()
        try:
            x = ht.arange(24, dtype=ht.float32, split=0)
            _ = ((x + 1.0) * 2.0).larray
            self.assertEqual(telemetry.events(), [])
            self.assertEqual(telemetry.programs(), [])
            self.assertIsNone(telemetry.record_event("probe"))
            with telemetry.span("dead"):
                self.assertIsNone(telemetry.current_span())
            self.assertEqual(telemetry.events(), [])
        finally:
            telemetry.set_level(prev)

    def test_counters_level_has_ledger_but_no_events(self):
        prev = telemetry.set_level("counters")
        telemetry.clear_events()
        telemetry.reset_programs()
        fusion.reset_cache()
        try:
            x = ht.arange(24, dtype=ht.float32, split=0)
            _ = ((x + 1.0) * 2.0).larray
            self.assertEqual(telemetry.events(), [])
            self.assertTrue(telemetry.programs())
        finally:
            telemetry.set_level(prev)

    def test_dump_document(self):
        import io
        import json

        with _EventsLevel():
            telemetry.record_event("probe", i=1)
            buf = io.StringIO()
            telemetry.dump(buf)
            doc = json.loads(buf.getvalue())
            self.assertEqual(doc["telemetry_level"], "events")
            self.assertIn("fusion", doc["counters"])
            self.assertTrue(any(e["kind"] == "probe" for e in doc["events"]))


class TestSpans(TestCase):
    def setUp(self):
        fusion.reset_cache()

    @unittest.skipUnless(fusion.enabled(), "fusion engine disabled")
    def test_nesting_under_materialize_all(self):
        with _EventsLevel():
            x = ht.arange(32, dtype=ht.float32, split=0)
            with telemetry.span("user.outer", tag="t"):
                a = (x + 1.0) * 2.0
                b = (x - 3.0) / 4.0
                ht.materialize_all(a, b)
            begins = {e["name"]: e for e in telemetry.events("span_begin")}
            self.assertIn("user.outer", begins)
            self.assertIn("fusion.materialize", begins)
            self.assertIsNone(begins["user.outer"]["parent"])
            self.assertEqual(
                begins["fusion.materialize"]["parent"],
                begins["user.outer"]["id"],
            )
            ends = {e["name"]: e for e in telemetry.events("span_end")}
            self.assertIn("fusion.materialize", ends)
            self.assertGreaterEqual(ends["fusion.materialize"]["dur_s"], 0.0)
            # events inside the region carry the innermost open span id
            miss = telemetry.events("cache_miss")
            self.assertTrue(miss)
            self.assertEqual(
                miss[0]["span"], begins["fusion.materialize"]["id"]
            )

    def test_decorator_form(self):
        @telemetry.span("probe.fn", kind="test")
        def work(n):
            return n + 1

        with _EventsLevel():
            self.assertEqual(work(1), 2)
            self.assertEqual(work(2), 3)
            begins = telemetry.events("span_begin")
            self.assertEqual(len(begins), 2)  # fresh span per call
            self.assertNotEqual(begins[0]["id"], begins[1]["id"])

    def test_open_spans_visible_across_threads(self):
        with _EventsLevel():
            entered = threading.Event()
            release = threading.Event()
            seen = {}

            def worker():
                with telemetry.span("worker.busy"):
                    entered.set()
                    release.wait(timeout=5)

            t = threading.Thread(target=worker)
            t.start()
            try:
                self.assertTrue(entered.wait(timeout=5))
                seen["open"] = [s["name"] for s in telemetry.open_spans()]
            finally:
                release.set()
                t.join(timeout=5)
            self.assertIn("worker.busy", seen["open"])
            self.assertEqual(
                [s["name"] for s in telemetry.open_spans()], []
            )

    def test_span_error_exit_recorded(self):
        with _EventsLevel():
            with self.assertRaises(ValueError):
                with telemetry.span("probe.err"):
                    raise ValueError("boom")
            end = telemetry.events("span_end")[-1]
            self.assertEqual(end["status"], "error")
            self.assertEqual(end["error"], "ValueError")

    def test_decorator_preserves_metadata(self):
        @telemetry.span("probe.meta")
        def documented(n):
            """Adds one."""
            return n + 1

        self.assertEqual(documented.__name__, "documented")
        self.assertEqual(documented.__doc__, "Adds one.")
        self.assertEqual(documented.__wrapped__(41), 42)

    def test_decorated_raise_records_error_status(self):
        @telemetry.span("probe.meta.err")
        def boom():
            raise KeyError("k")

        with _EventsLevel():
            with self.assertRaises(KeyError):
                boom()
            end = telemetry.events("span_end")[-1]
            self.assertEqual(end["name"], "probe.meta.err")
            self.assertEqual(end["status"], "error")
            self.assertEqual(end["error"], "KeyError")

    def test_postmortem_dump_under_concurrent_spans(self):
        # two threads holding open spans while a postmortem fires: the
        # dump must list BOTH open spans, a sibling Chrome trace must be
        # written, and a second postmortem in the same process must take
        # the .2 suffix instead of overwriting the first trail
        import json
        import os
        import tempfile

        with _EventsLevel():
            entered = threading.Event()
            release = threading.Event()

            def worker():
                with telemetry.span("worker.holding"):
                    entered.set()
                    release.wait(timeout=5)

            t = threading.Thread(target=worker)
            t.start()
            try:
                self.assertTrue(entered.wait(timeout=5))
                with tempfile.TemporaryDirectory() as td:
                    path = os.path.join(td, "pm.json")
                    os.environ["HEAT_TPU_TELEMETRY_DUMP"] = path
                    try:
                        with telemetry.span("main.holding"):
                            telemetry.postmortem("test_reason", detail=1)
                            telemetry.postmortem("test_reason_again")
                    finally:
                        del os.environ["HEAT_TPU_TELEMETRY_DUMP"]
                    doc = json.load(open(path))
                    names = [s["name"] for s in doc["open_spans"]]
                    self.assertIn("worker.holding", names)
                    self.assertIn("main.holding", names)
                    self.assertTrue(os.path.exists(path + ".trace.json"))
                    trace = json.load(open(path + ".trace.json"))
                    self.assertTrue(
                        all("ph" in e and "ts" in e for e in trace)
                    )
                    # never-overwrite: the second trail took .2
                    self.assertTrue(os.path.exists(path + ".2"))
                    self.assertTrue(os.path.exists(path + ".2.trace.json"))
                    # ... and the first trail still ends at its own event
                    self.assertEqual(doc["events"][-1]["reason"],
                                     "test_reason")
            finally:
                release.set()
                t.join(timeout=5)


@unittest.skipUnless(fusion.enabled(), "fusion engine disabled")
class TestFaultTrails(TestCase):
    """The full degradation trail of injected faults must be readable out
    of telemetry.events() — budgets, reasons, correlation ids."""

    def setUp(self):
        _reset_counters()

    def tearDown(self):
        _reset_counters()

    def test_injected_oom_leaves_halving_trail(self):
        with _EventsLevel():
            inj = fault.FaultInjector(seed=0).oom_in("transport.resplit", times=2)
            x = ht.array(
                np.arange(64.0, dtype=np.float32).reshape(8, 8),
                split=0, comm=self.comm,
            )
            with fault.injected(inj):
                out = x.resplit(1)
                _ = out.larray
            trail = telemetry.events("oom_retry")
            self.assertEqual(len(trail), 2)
            self.assertTrue(all(e["kernel"] == "resplit" for e in trail))
            # each event carries the NEW budget: strictly halving
            self.assertEqual(
                trail[1]["tile_bytes"], trail[0]["tile_bytes"] // 2
            )
            self.assertEqual(
                transport.stats()["retries_by_kind"].get("resplit"), 2
            )
            # the retried transfer ran inside its transport span
            spans = {e["id"]: e for e in telemetry.events("span_begin")}
            self.assertTrue(
                all(spans[e["span"]]["name"] == "transport.resplit"
                    for e in trail)
            )

    def test_injected_compile_failure_emits_fallback_event(self):
        with _EventsLevel():
            inj = fault.FaultInjector(seed=0).error_in("fusion.compile", times=1)
            x = ht.arange(24, dtype=ht.float32, split=0)
            with fault.injected(inj):
                _ = ((x * 3.0) + 1.0).larray
            reasons = [e["reason"] for e in telemetry.events("fallback")]
            self.assertIn("compile_error", reasons)
            # the failed compile closed its compile_begin with ok=False
            ends = telemetry.events("compile_end")
            self.assertTrue(any(e.get("ok") is False for e in ends))

    def test_warning_carries_blame_event_id(self):
        prev_guard = guard.set_mode("warn")
        try:
            with _EventsLevel():
                x = ht.arange(24, dtype=ht.float32, split=0)
                z = ht.log(x - 100.0)  # negative operand: chain-introduced NaN
                with warnings.catch_warnings(record=True) as caught:
                    warnings.simplefilter("always")
                    _ = z.larray
                trips = [
                    w.message for w in caught
                    if issubclass(w.category, guard.NonFiniteWarning)
                ]
                self.assertTrue(trips)
                eid = trips[0].event_id
                self.assertIsNotNone(eid)
                blames = telemetry.events("guard_blame")
                self.assertTrue(any(e["seq"] == eid for e in blames))
        finally:
            guard.set_mode(prev_guard)

    def test_stall_detector_events(self):
        with _EventsLevel():
            stalls = []
            det = fault.StallDetector(timeout=0.15, on_stall=stalls.append)
            det.start()
            try:
                det.beat()
                with det.pause():
                    pass
                with telemetry.span("user.stalled_work"):
                    deadline = __import__("time").monotonic() + 5.0
                    while not stalls and __import__("time").monotonic() < deadline:
                        __import__("time").sleep(0.02)
            finally:
                det.stop()
            self.assertTrue(stalls)
            self.assertTrue(telemetry.events("heartbeat"))
            self.assertTrue(telemetry.events("stall_pause"))
            self.assertTrue(telemetry.events("stall_resume"))
            stall_events = telemetry.events("stall")
            self.assertTrue(stall_events)
            self.assertGreaterEqual(stall_events[0]["quiet_s"], 0.15)
            # the watchdog thread saw the workload's open span
            self.assertIn(
                "user.stalled_work",
                [s["name"] for s in stall_events[0]["open_spans"]],
            )


class TestCostLedger(TestCase):
    def setUp(self):
        _reset_counters()
        telemetry.reset_programs()

    def tearDown(self):
        _reset_counters()
        telemetry.reset_programs()

    @unittest.skipUnless(fusion.enabled(), "fusion engine disabled")
    def test_fused_moments_program_is_ledgered(self):
        x = ht.array(
            np.random.default_rng(0).random((64, 16)).astype(np.float32),
            split=0, comm=self.comm,
        )
        _ = ht.mean(x)
        _ = float(ht.var(x).larray) if hasattr(ht.var(x), "larray") else None
        progs = [p for p in telemetry.programs() if p["kind"] == "fused"]
        self.assertTrue(progs)
        biggest = max(progs, key=lambda p: p["flops"])
        self.assertGreater(biggest["flops"], 0.0)
        self.assertGreater(biggest["hbm_bytes"], 0.0)
        self.assertGreaterEqual(biggest["ops"], 1)
        self.assertEqual(biggest["mesh"], {"devices": self.comm.size})

    @unittest.skipUnless(len(jax.devices()) >= 4, "needs >= 4 devices")
    def test_ring_matmul_program_is_ledgered(self):
        comm = _mesh(4)
        rng = np.random.default_rng(1)
        m = k = n = 32
        A = rng.random((m, k)).astype(np.float32)
        B = rng.random((k, n)).astype(np.float32)
        a = ht.array(A, split=0, comm=comm)
        b = ht.array(B, split=0, comm=comm)  # row×row is the `ag` case
        overlap.set_mode("ring")
        try:
            with fusion.fuse(False):
                out = ht.matmul(a, b)
        finally:
            overlap.set_mode(None)
        self.assertEqual(overlap.stats()["last"]["schedule"], "ring_ag")
        np.testing.assert_allclose(out.numpy(), A @ B, rtol=2e-5, atol=2e-5)
        rings = [p for p in telemetry.programs() if p["kind"] == "ring_matmul"]
        self.assertTrue(rings)
        self.assertEqual(rings[-1]["flops"], 2.0 * m * k * n)
        self.assertGreater(rings[-1]["hbm_bytes"], 0.0)
        self.assertEqual(rings[-1]["schedule"], "ring_ag")

    @unittest.skipUnless(fusion.enabled(), "fusion engine disabled")
    def test_cache_hit_counts_on_ledger_entry(self):
        x = ht.arange(48, dtype=ht.float32, split=0)
        _ = ((x + 1.0) * 2.0).larray
        y = ht.arange(48, dtype=ht.float32, split=0)
        _ = ((y + 1.0) * 2.0).larray  # same topology: compile-cache hit
        progs = {p["fingerprint"]: p for p in telemetry.programs()}
        self.assertTrue(
            any(p["hits"] >= 1 for p in progs.values()),
            f"no ledger entry saw a hit: {list(progs.values())}",
        )


class TestSyncSpans(TestCase):
    """``telemetry.sync``: a counter at ``counters``, a span from ``events``
    up (ISSUE 25)."""

    def setUp(self):
        telemetry.reset_group("sync")

    def test_counts_at_counters_level_without_events(self):
        with _EventsLevel("counters"):
            x = ht.arange(6, dtype=ht.float32, split=0).sum()
            with telemetry.sync("probe.readback"):
                float(x.larray)
            int(x)  # the scalar-conversion protocol is a sync site of its own
            got = telemetry.snapshot_group("sync")
            self.assertEqual(got["by_site"]["probe.readback"], 1)
            self.assertEqual(got["by_site"]["dndarray.cast"], 1)
            self.assertEqual(got["count"], sum(got["by_site"].values()))
            self.assertEqual(telemetry.events(), [])

    def test_off_counts_nothing(self):
        with _EventsLevel("off"):
            with telemetry.sync("probe.readback"):
                pass
        self.assertEqual(telemetry.snapshot_group("sync")["count"], 0)
        self.assertEqual(telemetry.snapshot_group("sync")["by_site"], {})

    def test_span_at_events_nests_and_carries_root(self):
        with _EventsLevel():
            with telemetry.span("user.call"):
                with telemetry.span("user.inner"):
                    with telemetry.sync("probe.readback"):
                        pass
            begins = {e["name"]: e for e in telemetry.events("span_begin")}
            ends = {e["name"]: e for e in telemetry.events("span_end")}
            self.assertIn("sync:probe.readback", begins)
            self.assertEqual(
                begins["sync:probe.readback"]["parent"], begins["user.inner"]["id"]
            )
            root = begins["user.call"]["id"]
            self.assertEqual(begins["user.call"]["root"], root)
            for name in ("user.inner", "sync:probe.readback"):
                self.assertEqual(begins[name]["root"], root)
                self.assertEqual(ends[name]["root"], root)
            self.assertGreaterEqual(ends["sync:probe.readback"]["dur_s"], 0.0)
            # the next user call is another request
            with telemetry.span("user.call"):
                pass
            self.assertNotEqual(telemetry.events("span_begin")[-1]["root"], root)

    def test_decorator_form_counts_each_call(self):
        @telemetry.sync("probe.decorated")
        def read(v):
            return v + 1

        with _EventsLevel():
            self.assertEqual(read(1), 2)
            self.assertEqual(read(2), 3)
            names = [e["name"] for e in telemetry.events("span_begin")]
        self.assertEqual(names, ["sync:probe.decorated"] * 2)
        self.assertEqual(
            telemetry.snapshot_group("sync")["by_site"]["probe.decorated"], 2
        )

    def test_note_lands_on_span_end(self):
        with _EventsLevel():
            with telemetry.span("probe.dispatch", m=3) as sp:
                sp.note(path="left")
            end = telemetry.events("span_end")[-1]
            self.assertEqual(end["path"], "left")
            self.assertEqual(telemetry.events("span_begin")[-1]["m"], 3)
        with telemetry.span("probe.dispatch") as sp:  # counters: records nothing
            sp.note(path="left")
        self.assertEqual(telemetry.events(), [])

    def test_trace_annotation_carries_the_prefix(self):
        seen = []

        class Annotation:
            def __init__(self, name):
                seen.append(name)

            def __enter__(self):
                return self

            def __exit__(self, *exc):
                return False

        real = jax.profiler.TraceAnnotation
        jax.profiler.TraceAnnotation = Annotation
        try:
            with _EventsLevel("trace"):
                with telemetry.span("probe.outer"):
                    with telemetry.sync("probe.readback"):
                        pass
                names = [e["name"] for e in telemetry.events("span_begin")]
        finally:
            jax.profiler.TraceAnnotation = real
        self.assertEqual(seen, ["ht:probe.outer", "ht:sync:probe.readback"])
        # the flight recorder keeps the bare names
        self.assertEqual(names, ["probe.outer", "sync:probe.readback"])


class TestTracedRunIsTheTimedRun(TestCase):
    """At ``trace`` no code path of telemetry or memtrack adds a host sync, a
    ``memory_stats()`` read or a stack walk that ``counters`` does not make
    (ISSUE 25, point 4)."""

    def _hits(self, level, n=32):
        """``(fences, memory reads, ledgered buffers)`` of ``n`` cache hits of
        one fused program at ``level``."""
        from heat_tpu.core import memtrack

        fusion.reset_cache()
        x = ht.arange(64, dtype=ht.float32, split=0)
        _ = ((x + 1.0) * 2.0).larray  # the miss: compiled, never timed
        fences, reads = [], []
        real_fence, real_sample = jax.block_until_ready, memtrack.sample_bytes

        def fence(out):
            fences.append(1)
            return real_fence(out)

        def sample():
            reads.append(1)
            return real_sample()

        jax.block_until_ready, memtrack.sample_bytes = fence, sample
        prev_every = telemetry.set_sample_every(16)
        try:
            with _EventsLevel(level):
                registered = telemetry.snapshot_group("memtrack")["registered"]
                for _ in range(n):
                    _ = ((x + 1.0) * 2.0).larray
                registered = telemetry.snapshot_group("memtrack")["registered"] - registered
        finally:
            jax.block_until_ready, memtrack.sample_bytes = real_fence, real_sample
            telemetry.set_sample_every(prev_every)
        return len(fences), len(reads), registered

    @unittest.skipUnless(fusion.enabled(), "fusion engine disabled")
    def test_trace_keeps_the_counters_cadence(self):
        fences_c, reads_c, registered_c = self._hits("counters")
        fences_t, reads_t, registered_t = self._hits("trace")
        self.assertEqual((fences_c, reads_c), (2, 4))   # 1 execution in 16
        self.assertEqual((fences_t, reads_t), (fences_c, reads_c))
        self.assertEqual(registered_c, 0)
        self.assertEqual(registered_t, 0)               # no stack walk per output

    @unittest.skipUnless(fusion.enabled(), "fusion engine disabled")
    def test_events_level_still_times_every_call(self):
        fences, reads, registered = self._hits("events")
        self.assertEqual((fences, reads), (32, 64))
        self.assertGreater(registered, 0)

    def test_timing_active_by_level(self):
        prev_every = telemetry.set_sample_every(4)
        try:
            for level, want in (("off", 0), ("counters", 2), ("events", 8), ("trace", 2)):
                with _EventsLevel(level):
                    self.assertEqual(
                        sum(telemetry.timing_active() for _ in range(8)), want, level
                    )
        finally:
            telemetry.set_sample_every(prev_every)

    def test_timed_call_fence_is_a_sync_site(self):
        telemetry.reset_group("sync")
        with _EventsLevel("events"):
            telemetry.timed_call("probe-fp", lambda: jax.numpy.ones(4))
            names = [e["name"] for e in telemetry.events("span_begin")]
        self.assertEqual(names, ["sync:telemetry.timed_call"])
        self.assertEqual(
            telemetry.snapshot_group("sync")["by_site"], {"telemetry.timed_call": 1}
        )


class TestLayerSpans(TestCase):
    """Spans where a layer begins: ``linalg.qr``, ``autotune.decide`` /
    ``explore``, ``kmeans.init`` / ``labels``, ``qr.tsqr`` (ISSUE 25)."""

    @staticmethod
    def _tree():
        begins = telemetry.events("span_begin")
        by_id = {e["id"]: e["name"] for e in begins}
        return {e["name"]: by_id.get(e["parent"]) for e in begins}

    def test_kmeans_fit_spans_and_syncs(self):
        rng = np.random.default_rng(3)
        x = ht.array(rng.normal(size=(64, 4)).astype(np.float32), split=0)
        telemetry.reset_group("sync")
        with _EventsLevel():
            est = ht.cluster.KMeans(n_clusters=3, max_iter=4, random_state=0).fit(x)
            _ = est.labels_.larray
            tree = self._tree()
            roots = {e["root"] for e in telemetry.events("span_begin")
                     if e["name"].startswith("kmeans.")}
        self.assertEqual(tree["kmeans.init"], "kmeans.fit")
        self.assertEqual(tree["kmeans.labels"], "kmeans.fit")
        self.assertEqual(tree["sync:kmeans.n_iter"], "kmeans.fit")
        self.assertEqual(tree["sync:kmeans.inertia"], "kmeans.fit")
        self.assertEqual(len(roots), 1)  # one request, one identifier
        sites = telemetry.snapshot_group("sync")["by_site"]
        self.assertEqual(sites["kmeans.n_iter"], 1)
        self.assertEqual(sites["kmeans.inertia"], 1)

    def test_kmeans_fit_notes_the_lloyd_body(self):
        """``lloyd`` on the span's end event says which body ran: the
        classic one where the Pallas tier is off, the fused pass where it
        is on and the rule takes the shape."""
        rng = np.random.default_rng(5)
        x = ht.array(rng.normal(size=(64, 8)).astype(np.float32), split=0)
        with _EventsLevel():
            for how in ("off", "interpret"):
                with mock.patch.dict(os.environ, {"HEAT_TPU_PALLAS": how}):
                    ht.cluster.KMeans(n_clusters=3, max_iter=2, random_state=0).fit(x)
            ends = [e for e in telemetry.events("span_end") if e["name"] == "kmeans.fit"]
        self.assertEqual([e["lloyd"] for e in ends], ["classic", "fused"])

    def test_kmeans_labels_span_notes_what_assigned(self):
        """``assign`` on ``kmeans.labels``' end event, ``fit``'s and
        ``predict``'s: the lazy distances and argmin where the Pallas tier is
        off, the fused pass's own labels where it is on."""
        rng = np.random.default_rng(6)
        x = ht.array(rng.normal(size=(64, 8)).astype(np.float32), split=0)
        with _EventsLevel():
            for how in ("off", "interpret"):
                with mock.patch.dict(os.environ, {"HEAT_TPU_PALLAS": how}):
                    est = ht.cluster.KMeans(n_clusters=3, max_iter=2, random_state=0).fit(x)
                    est.predict(x)
            ends = [e for e in telemetry.events("span_end") if e["name"] == "kmeans.labels"]
            begins = {e["id"]: e for e in telemetry.events("span_begin")}
        self.assertEqual([e["assign"] for e in ends], ["classic"] * 2 + ["fused"] * 2)
        parents = [begins.get(e["parent"], {}).get("name") for e in ends]
        self.assertEqual(parents, ["kmeans.fit", None] * 2)

    def test_fused_kmeans_fit_waits_twice_and_never_for_the_guard(self):
        """A fit whose labels the kernel writes closes two sync spans in
        all, reading its labels included: no lazy program is left whose
        flag the guard would read."""
        rng = np.random.default_rng(7)
        x = ht.array(rng.normal(size=(64, 8)).astype(np.float32), split=0)
        init = ht.array(rng.normal(size=(3, 8)).astype(np.float32), split=None)
        syncs = {}
        with _EventsLevel():
            for how in ("off", "interpret"):
                telemetry.clear_events()
                with mock.patch.dict(os.environ, {"HEAT_TPU_PALLAS": how}):
                    est = ht.cluster.KMeans(n_clusters=3, init=init, max_iter=2).fit(x)
                    _ = est.labels_.larray
                syncs[how] = [e["name"] for e in telemetry.events("span_end")
                              if e["name"].startswith(telemetry.SYNC_PREFIX)]
        self.assertEqual(syncs["interpret"], ["sync:kmeans.n_iter", "sync:kmeans.inertia"])
        # the lazy labels program's guard (``guard.flag`` on a TPU) is the third
        self.assertEqual([n.split(".")[0] for n in syncs["off"][2:]], ["sync:guard"])

    def test_linalg_qr_span_names_the_path(self):
        rng = np.random.default_rng(4)
        tall = ht.array(rng.normal(size=(64, 4)).astype(np.float32), split=None)
        squarish = ht.array(rng.normal(size=(6, 4)).astype(np.float32), split=None)
        wide = ht.array(rng.normal(size=(3, 5)).astype(np.float32), split=None)
        split0 = ht.array(rng.normal(size=(64 * self.comm.size, 4)).astype(np.float32), split=0)
        tall1000 = ht.array(rng.normal(size=(2048, 1000)).astype(np.float32), split=None)
        with _EventsLevel():
            for a in (tall, squarish, wide, split0, tall1000):
                ht.linalg.qr(a)
            ends = [e for e in telemetry.events("span_end") if e["name"] == "linalg.qr"]
            begins = [e for e in telemetry.events("span_begin") if e["name"] == "linalg.qr"]
            tree = self._tree()
        want = ["cholqr2", "blocked", "householder",
                "tsqr" if self.comm.size > 1 else "cholqr2", "cholqr2"]
        self.assertEqual([e["path"] for e in ends], want)
        # how far the block-triangular GEMMs engaged: dense at width 4,
        # several blocks at width 1000, no note where no GEMM path ran
        qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")
        self.assertEqual([e.get("blocks") for e in ends[:3]], [1, 1, None])
        self.assertEqual(ends[4]["blocks"], len(qr_mod._block_edges(1000)) - 1)
        self.assertGreater(ends[4]["blocks"], 1)
        self.assertEqual([(e["m"], e["n"]) for e in begins][:2], [(64, 4), (6, 4)])
        self.assertEqual(tree["sync:qr.breakdown_check"], "linalg.qr")
        if self.comm.size > 1:
            self.assertEqual(tree["qr.tsqr"], "linalg.qr")

    def test_autotune_decide_and_explore_spans(self):
        from heat_tpu.core import autotune

        prev = autotune.set_enabled(True)
        try:
            with _EventsLevel():
                key = autotune.key("kernel", "probe_site", 8, 8, "float32")
                ones = lambda: jax.numpy.ones(4)  # noqa: E731
                with telemetry.span("probe.caller"):
                    d = autotune.decide(key, "classic", arms=("classic", "kernel"))
                    self.assertTrue(d.explore)
                    autotune.explore(
                        d, {"classic": ones, "kernel": ones}, site="probe_site"
                    )
                tree = self._tree()
        finally:
            autotune.set_enabled(prev)
            autotune.reset()
        self.assertEqual(tree["autotune.decide"], "probe.caller")
        self.assertEqual(tree["autotune.explore"], "probe.caller")
        self.assertEqual(tree["sync:autotune.timed"], "autotune.explore")


class TestDeviceScopes(TestCase):
    """The jitted programs name their stages (``jax.named_scope``) and carry
    a module name of their own (ISSUE 25, point 3)."""

    @staticmethod
    def _lowered(fn, *args, **kwargs):
        return fn.lower(*args, **kwargs).as_text(debug_info=True)

    def test_lloyd_loop_scopes(self):
        from heat_tpu.cluster import kmeans

        x, c = jax.numpy.ones((64, 4)), jax.numpy.ones((3, 4))
        text = self._lowered(kmeans._lloyd_loop, x, c, 3, 5, 0.0)
        self.assertIn("module @jit_ht_lloyd_loop", text)
        for scope in ("ht.kmeans.lloyd/while", "ht.kmeans.assign/ht.cdist",
                      "ht.kmeans.update/dot_general"):
            self.assertIn(scope, text)
        self.assertEqual(kmeans._lloyd_loop.__name__, "ht_lloyd_loop")

    def test_fused_lloyd_loop_scopes(self):
        """The kernel's operations sit under ``ht.kmeans.lloyd`` (what
        ``lloyd_ms_per_call`` reads) in a scope of their own, in the module
        the classic loop has."""
        from heat_tpu.cluster import kmeans

        x, c = jax.numpy.ones((64, 8)), jax.numpy.ones((3, 8))
        with mock.patch.dict(os.environ, {"HEAT_TPU_PALLAS": "interpret"}):
            lowered = kmeans._lloyd_loop.lower(x, c, 3, 5, 0.0, fused=(64, None, None))
        self.assertIn("module @jit_ht_lloyd_loop", lowered.as_text())
        # the compiled module carries whole paths (the call of the step is
        # inlined there): the ``op_name`` a device trace's events are given
        names = set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))
        step = "jit(ht_lloyd_loop)/ht.kmeans.lloyd/while/body/jit(ht_lloyd_step)/"
        kernel = {n for n in names if "ht_lloyd_pass" in n}  # the kernel's own name
        self.assertTrue(kernel and all(
            n.startswith(step + "ht.kmeans.pass/ht_lloyd_pass") for n in kernel))
        self.assertTrue(any(n.startswith(step + "ht.kmeans.update/") for n in names))
        self.assertFalse(any("ht.kmeans.assign" in n for n in names))

    def test_packed_lloyd_loop_scopes(self):
        from heat_tpu.cluster import kmeans

        x2 = jax.numpy.ones((32, 128), jax.numpy.bfloat16)
        valid = jax.numpy.ones((32, 2), jax.numpy.float32)
        c = jax.numpy.ones((3, 64), jax.numpy.bfloat16)
        text = self._lowered(
            kmeans._lloyd_loop_packed, x2, jax.numpy.zeros((1, 1)), valid, c, 3, 2, 5, 0.0,
            with_inertia=False,
        )
        self.assertIn("module @jit_ht_lloyd_loop_packed", text)
        for scope in ("ht.kmeans.lloyd", "ht.kmeans.assign", "ht.kmeans.update"):
            self.assertIn(scope, text)

    def test_cholesky_qr2_and_blocked_scopes(self):
        qr = importlib.import_module("heat_tpu.core.linalg.qr")
        text = self._lowered(qr._cholesky_qr2, jax.numpy.ones((64, 4)))
        self.assertIn("module @jit_ht_cholesky_qr2", text)
        for stage in ("gram1", "chol1", "apply1", "gram2", "chol2", "apply2"):
            self.assertIn(f"ht.qr.{stage}/", text)
        # with several blocks a stage, every block sits under its stage's
        # scope and no other ht.qr scope appears
        text = self._lowered(qr._cholesky_qr2, jax.numpy.ones((2048, 1000), jax.numpy.float32))
        stages = {f"ht.qr.{s}{i}" for s in ("gram", "chol", "apply") for i in (1, 2)}
        self.assertEqual(set(re.findall(r"ht\.qr\.\w+", text)), stages)
        self.assertGreater(len(qr._block_edges(1000)), 2)
        for stage in ("gram1", "apply1", "gram2", "apply2"):
            self.assertIn(f'/ht.qr.{stage}/dot_general"', text)
        text = self._lowered(qr._blocked_qr, jax.numpy.ones((6, 4)))
        self.assertIn("module @jit_ht_blocked_qr", text)
        self.assertIn("ht.qr.panel/", text)

    def test_tsqr_scopes(self):
        from heat_tpu.core.linalg.qr import _build_tsqr

        fn = jax.jit(_build_tsqr(self.comm.mesh, self.comm.split_axis, True))
        text = self._lowered(fn, jax.numpy.ones((32 * self.comm.size, 4)))
        self.assertIn("module @jit_ht_tsqr", text)
        for stage in ("leaf", "gather", "merge", "apply"):
            self.assertIn(f"ht.tsqr.{stage}/", text)

    @unittest.skipUnless(fusion.enabled(), "fusion engine disabled")
    def test_fused_program_scopes_name_the_ops(self):
        fusion.reset_cache()
        x = ht.arange(32, dtype=ht.float32, split=0)
        _ = ((x + 1.0) * 2.0).larray
        text = fusion.last_hlo()
        self.assertIn("jit_ht_fused", text)
        for op in ("add", "mul"):
            self.assertIn(f"ht.fused/{op}/", text)


if __name__ == "__main__":
    unittest.main()
