"""Quantized collectives (ISSUE 16): absmax wire formats for the movers.

Laws under test, per the wire doctrine (``heat_tpu/core/wire.py``):

* grid math — int8 round-trip error is bounded by ``absmax/254`` per
  scale row (half the grid step), all-zero rows round-trip EXACTLY
  (scale 1, never 0/0), fp8 stays finite and close;
* off restores f32 — ``HEAT_TPU_WIRE=off`` (and ``HEAT_TPU_AUTOTUNE=
  off``) keeps every engine bit-for-bit on today's wire with ZERO
  wire-arm table decisions;
* forced arms — ``HEAT_TPU_WIRE=int8|fp8`` quantizes every eligible
  dispatch (resplit, fused resplit tail, ring matmul, ring cdist) with
  no table decisions, a >= 3x modeled on-wire byte win, and bounded
  elementwise error;
* the decline matrix — bool/int payloads, ``exact=True`` callers, index
  gathers (``tiled_take``), the traveling ``rs`` accumulator, and
  below-threshold transfers stay byte-identical f32 and only bump
  ``declined_static``;
* tuning — mode ``on`` explores all three arms per (site, geometry,
  device kind), returns the f32 result during explore, resolves a
  winner, and persists it through save/load.

Doctrine stays "no mocks": every law runs the real shard_map programs on
the real host mesh.
"""

import os
import tempfile
import unittest

import numpy as np

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core import autotune, roofline, telemetry, wire
from heat_tpu.parallel import overlap, transport

from .base import TestCase

_MULTI = len(jax.local_devices()) > 1


class _Wired:
    """Scoped wire plane: events level, tiny eligibility threshold,
    optional forced mode / tuning plane, clean counters and table on
    both sides."""

    def __init__(self, mode=None, tuned=False, min_bytes=1):
        self.mode = mode
        self.tuned = tuned
        self.min_bytes = min_bytes

    def __enter__(self):
        self.prev_level = telemetry.set_level("events")
        self.prev_on = autotune.set_enabled(True) if self.tuned else None
        self.prev_mode = wire.set_mode(self.mode)
        self.prev_env = os.environ.get("HEAT_TPU_WIRE_MIN_BYTES")
        os.environ["HEAT_TPU_WIRE_MIN_BYTES"] = str(self.min_bytes)
        telemetry.reset_all()
        telemetry.clear_events()
        telemetry.reset_programs()
        autotune.reset()
        return self

    def __exit__(self, *exc):
        if self.prev_env is None:
            os.environ.pop("HEAT_TPU_WIRE_MIN_BYTES", None)
        else:
            os.environ["HEAT_TPU_WIRE_MIN_BYTES"] = self.prev_env
        wire.set_mode(self.prev_mode)
        if self.prev_on is not None or self.tuned:
            autotune.set_enabled(self.prev_on)
        autotune.reset()
        telemetry.reset_all()
        telemetry.clear_events()
        telemetry.reset_programs()
        telemetry.set_level(self.prev_level)
        return False


def _phys(comm, x, split):
    from heat_tpu.core.dndarray import _to_physical

    return _to_physical(jnp.asarray(x), x.shape, split, comm)


def _wire_events(site=None):
    evs = [e for e in telemetry.events() if e["kind"] == "wire_dispatch"]
    if site is not None:
        evs = [e for e in evs if e["site"] == site]
    return evs


class TestGridMath(unittest.TestCase):
    def test_int8_error_bound_per_scale_row(self):
        rng = np.random.default_rng(0)
        x = (rng.standard_normal((16, 64)) * rng.uniform(0.1, 30, (16, 1))
             ).astype(np.float32)
        q, scale = wire.absmax_encode(jnp.asarray(x), "int8", (0,))
        self.assertEqual(q.dtype, jnp.int8)
        self.assertEqual(scale.shape, (16,))
        back = np.asarray(wire.absmax_decode(q, scale, (0,), jnp.float32))
        # half the grid step per row: absmax/127/2 = absmax/254
        bound = np.abs(x).max(axis=1) / 254.0 + 1e-7
        err = np.abs(back - x).max(axis=1)
        self.assertTrue((err <= bound).all(), (err, bound))

    def test_all_zero_rows_round_trip_exactly(self):
        x = np.zeros((4, 32), np.float32)
        x[1] = np.linspace(-3, 3, 32)
        q, scale = wire.absmax_encode(jnp.asarray(x), "int8", (0,))
        self.assertEqual(float(scale[0]), 1.0)  # never 0/0
        back = np.asarray(wire.absmax_decode(q, scale, (0,), jnp.float32))
        self.assertTrue((back[0] == 0.0).all())
        self.assertTrue((back[2:] == 0.0).all())

    def test_scalar_scale(self):
        x = np.arange(-12.0, 12.0, dtype=np.float32).reshape(4, 6)
        q, scale = wire.absmax_encode(jnp.asarray(x), "int8", ())
        self.assertEqual(scale.shape, ())
        back = np.asarray(wire.absmax_decode(q, scale, (), jnp.float32))
        self.assertLessEqual(np.abs(back - x).max(), np.abs(x).max() / 254 + 1e-7)

    @unittest.skipUnless(wire.fp8_available(), "no float8_e4m3fn in this jax")
    def test_fp8_round_trip_close_and_finite(self):
        rng = np.random.default_rng(1)
        x = rng.standard_normal((8, 48)).astype(np.float32) * 5.0
        q, scale = wire.absmax_encode(jnp.asarray(x), "fp8", (0,))
        back = np.asarray(wire.absmax_decode(q, scale, (0,), jnp.float32))
        self.assertTrue(np.isfinite(back).all())
        # e4m3: 3 mantissa bits -> relative step 2^-3 of the row absmax
        self.assertLessEqual(
            np.abs(back - x).max(), np.abs(x).max() * (2.0 ** -3)
        )

    def test_payload_byte_model(self):
        # 1-byte grid elements + f32 scales beside them
        self.assertEqual(wire.payload_nbytes(1000, 10, "int8"), 1040)
        self.assertEqual(wire.payload_nbytes(0, 0, "fp8"), 0)


class TestModeKnob(unittest.TestCase):
    def test_mode_parses_and_rejects(self):
        self.assertEqual(wire.mode({}), "on")
        self.assertEqual(wire.mode({"HEAT_TPU_WIRE": "off"}), "off")
        self.assertEqual(wire.mode({"HEAT_TPU_WIRE": " INT8 "}), "int8")
        with self.assertRaises(ValueError) as ctx:
            wire.mode({"HEAT_TPU_WIRE": "int4"})
        self.assertIn("HEAT_TPU_WIRE", str(ctx.exception))

    def test_set_mode_scoping(self):
        prev = wire.set_mode("int8")
        try:
            self.assertEqual(wire.mode({"HEAT_TPU_WIRE": "off"}), "int8")
        finally:
            wire.set_mode(prev)
        with self.assertRaises(ValueError):
            wire.set_mode("int4")

    def test_min_bytes_knob(self):
        self.assertEqual(
            wire.min_bytes({}), 64 << 10
        )
        self.assertEqual(
            wire.min_bytes({"HEAT_TPU_WIRE_MIN_BYTES": "128"}), 128
        )
        with self.assertRaises(ValueError):
            wire.min_bytes({"HEAT_TPU_WIRE_MIN_BYTES": "lots"})

    def test_eligibility_matrix(self):
        with _Wired(mode="int8"):
            self.assertTrue(wire.eligible(jnp.float32, 1 << 20))
            before = wire.stats()["declined_static"]
            self.assertFalse(wire.eligible(jnp.float32, 1 << 20, exact=True))
            self.assertFalse(wire.eligible(jnp.int32, 1 << 20))
            self.assertFalse(wire.eligible(jnp.bool_, 1 << 20))
            self.assertFalse(wire.eligible(jnp.int8, 1 << 20))
            self.assertEqual(wire.stats()["declined_static"], before + 4)
        with _Wired(mode="off"):
            before = wire.stats()["declined_static"]
            self.assertFalse(wire.eligible(jnp.float32, 1 << 20))
            # off-mode consults are free: not even a declined count
            self.assertEqual(wire.stats()["declined_static"], before)

    def test_min_bytes_gate(self):
        with _Wired(mode="int8", min_bytes=1 << 16):
            self.assertFalse(wire.eligible(jnp.float32, 100))
            self.assertGreaterEqual(wire.stats()["declined_static"], 1)


@unittest.skipUnless(_MULTI, "wire engines need a multi-device mesh")
class TestForcedResplit(TestCase):
    def _roundtrip(self, x, mode):
        comm = self.comm
        with _Wired(mode="off"):
            ref = np.asarray(transport.tiled_resplit(
                _phys(comm, x, 0), x.shape, 0, 1, comm
            ))
        with _Wired(mode=mode) as _:
            out = np.asarray(transport.tiled_resplit(
                _phys(comm, x, 0), x.shape, 0, 1, comm
            ))
            st = wire.stats()
        return ref, out, st

    def test_forced_int8_bounded_error_and_3x_bytes(self):
        rng = np.random.default_rng(2)
        x = rng.standard_normal((64, 96)).astype(np.float32)
        ref, out, st = self._roundtrip(x, "int8")
        self.assertEqual(out.shape, ref.shape)
        # scale rows span tile columns: the global absmax bounds them all
        self.assertLessEqual(
            np.abs(out - ref).max(), np.abs(x).max() / 254 + 1e-6
        )
        self.assertGreaterEqual(st["quantized_dispatches"], 1)
        self.assertEqual(st["by_arm"]["wire_int8"],
                         st["quantized_dispatches"])
        # the acceptance byte law: >= 3x less on the wire (4x elements,
        # ratio diluted only by the f32 scales riding beside them)
        self.assertGreaterEqual(st["bytes_logical"], 3 * st["bytes_wire"])
        # forced mode took ZERO table decisions
        self.assertEqual(autotune.table_size(), 0)

    @unittest.skipUnless(wire.fp8_available(), "no float8_e4m3fn in this jax")
    def test_forced_fp8_bounded_error(self):
        rng = np.random.default_rng(3)
        x = rng.standard_normal((64, 96)).astype(np.float32)
        ref, out, st = self._roundtrip(x, "fp8")
        self.assertLessEqual(
            np.abs(out - ref).max(), np.abs(x).max() * (2.0 ** -3)
        )
        self.assertEqual(st["by_arm"]["wire_fp8"], st["quantized_dispatches"])

    def test_off_mode_is_bitwise_f32_even_with_autotune_on(self):
        rng = np.random.default_rng(4)
        x = rng.standard_normal((64, 96)).astype(np.float32)
        comm = self.comm
        with _Wired(mode="off"):
            ref = np.asarray(transport.tiled_resplit(
                _phys(comm, x, 0), x.shape, 0, 1, comm
            ))
        with _Wired(mode="off", tuned=True):
            out = np.asarray(transport.tiled_resplit(
                _phys(comm, x, 0), x.shape, 0, 1, comm
            ))
            self.assertEqual(autotune.table_size(), 0)
            self.assertEqual(wire.stats()["quantized_dispatches"], 0)
        self.assertTrue(np.array_equal(ref, out))

    def test_forced_mode_ledgers_wire_bytes_on_the_program(self):
        rng = np.random.default_rng(5)
        x = rng.standard_normal((64, 96)).astype(np.float32)
        comm = self.comm
        with _Wired(mode="int8"):
            _ = transport.tiled_resplit(_phys(comm, x, 0), x.shape, 0, 1, comm)
            rows = [p for p in telemetry.programs() if p.get("wire")]
            self.assertTrue(rows)
            for p in rows:
                self.assertEqual(p["wire"], "int8")
                self.assertGreater(p["logical_bytes"], 0)
                self.assertGreaterEqual(
                    p["logical_bytes"], 3 * p["wire_bytes"]
                )
            (ev,) = _wire_events("resplit")
            self.assertEqual(ev["arm"], "wire_int8")
            self.assertGreaterEqual(ev["logical_bytes"], 3 * ev["wire_bytes"])


@unittest.skipUnless(_MULTI, "wire engines need a multi-device mesh")
class TestDeclineMatrix(TestCase):
    """Forced int8 everywhere: any eligible path WOULD quantize, so a
    byte-identical result proves the static decline."""

    def test_integer_payload_stays_bitwise(self):
        comm = self.comm
        x = np.arange(64 * 96, dtype=np.int32).reshape(64, 96)
        with _Wired(mode="off"):
            ref = np.asarray(transport.tiled_resplit(
                _phys(comm, x, 0), x.shape, 0, 1, comm
            ))
        with _Wired(mode="int8"):
            out = np.asarray(transport.tiled_resplit(
                _phys(comm, x, 0), x.shape, 0, 1, comm
            ))
            self.assertEqual(wire.stats()["quantized_dispatches"], 0)
            self.assertGreaterEqual(wire.stats()["declined_static"], 1)
        self.assertTrue(np.array_equal(ref, out))

    def test_exact_caller_stays_bitwise(self):
        comm = self.comm
        rng = np.random.default_rng(6)
        x = rng.standard_normal((64, 96)).astype(np.float32)
        with _Wired(mode="off"):
            ref = np.asarray(transport.tiled_resplit(
                _phys(comm, x, 0), x.shape, 0, 1, comm, exact=True
            ))
        with _Wired(mode="int8"):
            out = np.asarray(transport.tiled_resplit(
                _phys(comm, x, 0), x.shape, 0, 1, comm, exact=True
            ))
            self.assertEqual(wire.stats()["quantized_dispatches"], 0)
        self.assertTrue(np.array_equal(ref, out))

    def test_tiled_take_declines_index_gather(self):
        comm = self.comm
        rng = np.random.default_rng(7)
        x = rng.standard_normal((64, 32)).astype(np.float32)
        rows = np.asarray([3, 9, 1, 60, 17], np.int32)
        with _Wired(mode="off"):
            ref = np.asarray(transport.tiled_take(
                _phys(comm, x, 0), rows, comm.mesh, comm.split_axis, 0
            ))
        with _Wired(mode="int8"):
            out = np.asarray(transport.tiled_take(
                _phys(comm, x, 0), rows, comm.mesh, comm.split_axis, 0
            ))
            self.assertEqual(wire.stats()["quantized_dispatches"], 0)
            self.assertGreaterEqual(wire.stats()["declined_static"], 1)
        self.assertTrue(np.array_equal(ref, out))

    def test_ring_rs_keeps_the_accumulator_exact(self):
        # a k-split matmul rides the `rs` schedule: the traveling partial
        # sum must never be re-quantized, so forced int8 is bit-for-bit
        comm = self.comm
        rng = np.random.default_rng(8)
        A = rng.standard_normal((48, 128)).astype(np.float32)
        B = rng.standard_normal((128, 40)).astype(np.float32)

        def run():
            a = ht.array(A, split=1, comm=comm)
            b = ht.array(B, split=0, comm=comm)
            overlap.set_mode("ring")
            try:
                from heat_tpu.core import fusion

                with fusion.fuse(False):
                    return np.asarray(ht.matmul(a, b).larray)
            finally:
                overlap.set_mode(None)

        with _Wired(mode="off"):
            ref = run()
        with _Wired(mode="int8"):
            out = run()
            if overlap.stats()["last"]["schedule"] != "ring_rs":
                self.skipTest("rs ring not taken on this mesh")
            self.assertEqual(wire.stats()["quantized_dispatches"], 0)
            self.assertGreaterEqual(wire.stats()["declined_static"], 1)
        self.assertTrue(np.array_equal(ref, out))

    def test_below_threshold_stays_bitwise(self):
        comm = self.comm
        rng = np.random.default_rng(9)
        x = rng.standard_normal((64, 96)).astype(np.float32)
        with _Wired(mode="off"):
            ref = np.asarray(transport.tiled_resplit(
                _phys(comm, x, 0), x.shape, 0, 1, comm
            ))
        with _Wired(mode="int8", min_bytes=1 << 20):
            out = np.asarray(transport.tiled_resplit(
                _phys(comm, x, 0), x.shape, 0, 1, comm
            ))
            self.assertEqual(wire.stats()["quantized_dispatches"], 0)
        self.assertTrue(np.array_equal(ref, out))


@unittest.skipUnless(_MULTI, "ring schedules need a multi-device mesh")
class TestForcedRing(TestCase):
    def _mm(self, mode, split=0):
        comm = self.comm
        rng = np.random.default_rng(10)
        A = rng.standard_normal((64, 128)).astype(np.float32)
        B = rng.standard_normal((128, 48)).astype(np.float32)

        def run():
            a = ht.array(A, split=split, comm=comm)
            b = ht.array(B, split=split, comm=comm)
            overlap.set_mode("ring")
            try:
                from heat_tpu.core import fusion

                with fusion.fuse(False):
                    return np.asarray(ht.matmul(a, b).larray)
            finally:
                overlap.set_mode(None)

        with _Wired(mode="off"):
            ref = run()
        with _Wired(mode=mode) as _:
            out = run()
            sched = overlap.stats()["last"]["schedule"]
            st = wire.stats()
        return ref, out, sched, st

    def test_forced_int8_ag_ring(self):
        ref, out, sched, st = self._mm("int8", split=0)
        self.assertEqual(sched, "ring_ag")
        self.assertGreaterEqual(st["quantized_dispatches"], 1)
        self.assertGreaterEqual(st["bytes_logical"], 3 * st["bytes_wire"])
        # one absmax row per k-slice of 128: dot error stays well under
        # 1% of the output magnitude for unit-normal operands
        self.assertLessEqual(
            np.abs(out - ref).max(), 0.02 * np.abs(ref).max() + 1e-4
        )

    def test_forced_int8_col_ring(self):
        ref, out, sched, st = self._mm("int8", split=1)
        if sched != "ring_col":
            self.skipTest(f"col ring not taken ({sched})")
        self.assertGreaterEqual(st["quantized_dispatches"], 1)
        self.assertLessEqual(
            np.abs(out - ref).max(), 0.02 * np.abs(ref).max() + 1e-4
        )

    def test_forced_int8_ring_cdist(self):
        comm = self.comm
        rng = np.random.default_rng(11)
        a = rng.standard_normal((64, 5)).astype(np.float32)
        b = rng.standard_normal((32, 5)).astype(np.float32)

        def run():
            return ht.spatial.cdist(
                ht.array(a, split=0, comm=comm),
                ht.array(b, split=0, comm=comm),
            ).numpy()

        with _Wired(mode="off"):
            ref = run()
        with _Wired(mode="int8"):
            out = run()
            st = wire.stats()
            if not st["quantized_dispatches"]:
                self.skipTest("ring cdist path not taken on this mesh")
            (ev,) = _wire_events("cdist")
            self.assertGreaterEqual(ev["logical_bytes"], 3 * ev["wire_bytes"])
        np.testing.assert_allclose(out, ref, rtol=0.05, atol=0.05)

    def test_forced_int8_fused_resplit_tail(self):
        # the consume-only site: a lazy chain ending in .resplit lowers
        # through the fused tail, which must honor the forced arm
        comm = self.comm
        rng = np.random.default_rng(12)
        x = rng.standard_normal((64, 96)).astype(np.float32)

        def run():
            a = ht.array(x, split=0, comm=comm)
            return np.asarray(((a * 2.0).resplit(1)).larray)

        with _Wired(mode="off"):
            ref = run()
        with _Wired(mode="int8"):
            out = run()
            evs = _wire_events("resplit_tail")
            if not evs:
                self.skipTest("fused tail not taken (fusion off?)")
            self.assertGreaterEqual(
                evs[0]["logical_bytes"], 3 * evs[0]["wire_bytes"]
            )
        self.assertLessEqual(
            np.abs(out - ref).max(), 2.0 * np.abs(x).max() / 254 + 1e-6
        )

    def test_forced_int8_reshape_rechunk(self):
        comm = self.comm
        rng = np.random.default_rng(13)
        x = rng.standard_normal((37, 15)).astype(np.float32)

        def run():
            phys = _phys(comm, x, 0)
            return np.asarray(transport.tiled_reshape(
                phys, x.shape, 0, (555,), 0, comm, tile_bytes=512
            ))

        with _Wired(mode="off"):
            ref = run()
        with _Wired(mode="int8"):
            out = run()
            st = wire.stats()
        # the rechunk ppermute chain may or may not move non-divisible
        # chunks on this mesh; when it quantized, the bytes must win
        if st["quantized_dispatches"]:
            self.assertGreaterEqual(st["bytes_logical"], 3 * st["bytes_wire"])
            self.assertLessEqual(
                np.abs(out - ref).max(), np.abs(x).max() / 254 * 2 + 1e-6
            )
        else:
            self.assertTrue(np.array_equal(ref, out))


@unittest.skipUnless(_MULTI, "the tuned wire needs a multi-device mesh")
class TestTunedWire(TestCase):
    def _resplit_once(self, x):
        comm = self.comm
        return np.asarray(transport.tiled_resplit(
            _phys(comm, x, 0), x.shape, 0, 1, comm
        ))

    def _wire_rows(self):
        return [
            r for r in autotune.report()["rows"]
            if set(r["arms"]) == set(wire.WIRE_ARMS)
        ]

    def test_explore_returns_f32_then_resolves(self):
        rng = np.random.default_rng(14)
        x = rng.standard_normal((64, 96)).astype(np.float32)
        with _Wired(mode="off"):
            ref = self._resplit_once(x)
        with _Wired(mode="on", tuned=True):
            k = autotune.explore_k()
            for _ in range(k):
                out = self._resplit_once(x)
                # mid-explore numerics never depend on tuning state
                self.assertTrue(np.array_equal(out, ref))
            self.assertEqual(wire.stats()["explores"], k)
            (row,) = self._wire_rows()
            self.assertIn(row["winner"], wire.WIRE_ARMS)
            for arm in wire.WIRE_ARMS:
                if arm == "wire_fp8" and not wire.fp8_available():
                    continue
                self.assertGreaterEqual(row[arm + "_samples"], k)
            # steady state serves the winner without further explores
            _ = self._resplit_once(x)
            self.assertEqual(wire.stats()["explores"], k)

    def test_winner_persists_through_save_load(self):
        rng = np.random.default_rng(15)
        x = rng.standard_normal((64, 96)).astype(np.float32)
        with _Wired(mode="on", tuned=True):
            for _ in range(autotune.explore_k()):
                self._resplit_once(x)
            (row,) = self._wire_rows()
            winner = row["winner"]
            with tempfile.TemporaryDirectory() as td:
                path = os.path.join(td, "wire.json")
                self.assertGreaterEqual(autotune.save(path), 1)
                autotune.reset()
                self.assertGreaterEqual(autotune.load(path), 1)
                (row2,) = self._wire_rows()
                self.assertEqual(row2["winner"], winner)

    def test_mesh1_is_safe(self):
        from heat_tpu.parallel.mesh import local_mesh

        comm = local_mesh(1)
        x = np.arange(48.0, dtype=np.float32).reshape(12, 4)
        with _Wired(mode="int8", tuned=True):
            a = ht.array(x, split=0, comm=comm)
            out = ht.matmul(a, a.T)
            np.testing.assert_allclose(
                out.numpy(), x @ x.T, rtol=1e-5, atol=1e-5
            )


class TestWireObservability(TestCase):
    def test_prometheus_wire_gauges_golden(self):
        with _Wired(mode="int8"):
            wire.account("resplit", "wire_int8", 1000, 250)
            telemetry.record_program(
                'fpq"1', kind="transport_resplit", wire="int8",
                logical_bytes=1000.0, wire_bytes=250.0,
            )
            text = telemetry.export_prometheus()
        # the aggregate group counters ride the generic exposition
        self.assertIn("# TYPE heat_tpu_wire_quantized_dispatches gauge", text)
        self.assertIn("heat_tpu_wire_quantized_dispatches 1", text)
        self.assertIn("heat_tpu_wire_bytes_logical 1000", text)
        self.assertIn("heat_tpu_wire_by_arm_wire_int8 1", text)
        # the labeled per-program gauges: HELP/TYPE precede samples, the
        # quote in the fingerprint escapes per the exposition format
        golden = (
            "# TYPE heat_tpu_wire_program_bytes gauge\n"
            'heat_tpu_wire_program_bytes{fingerprint="fpq\\"1",arm="int8"} 250.0'
        )
        self.assertIn(golden, text)
        self.assertIn(
            'heat_tpu_wire_program_logical_bytes{fingerprint="fpq\\"1"'
            ',arm="int8"} 1000.0',
            text,
        )
        self.assertIn(
            'heat_tpu_wire_program_ratio{fingerprint="fpq\\"1",arm="int8"} 4.0',
            text,
        )

    def test_roofline_rows_carry_wire_fields_and_flip(self):
        peaks = {"device": "x", "known": True, "bf16_tflops": 197.0,
                 "f32_tflops": 49.25, "hbm_gbps": 819.0, "source": "env"}
        # compute-bound with the compressed wire, memory-bound had the
        # f32 bytes moved: compression flipped the verdict
        row = roofline.attribute(
            {"fingerprint": "fw", "kind": "ring_matmul", "calls": 2,
             "total_s": 0.2, "p50_s": 0.1, "min_s": 0.1,
             "flops": 1.0e12, "hbm_bytes": 1.0e9,
             "wire": "int8", "logical_bytes": 2.0e10, "wire_bytes": 5.0e9},
            peaks,
        )
        self.assertEqual(row["wire"], "int8")
        self.assertEqual(row["wire_ratio"], 4.0)
        self.assertTrue(row["wire_verdict_flip"])
        # a small wire volume cannot flip anything
        row2 = roofline.attribute(
            {"fingerprint": "fw2", "kind": "ring_matmul", "calls": 2,
             "total_s": 0.2, "p50_s": 0.1, "min_s": 0.1,
             "flops": 1.0e12, "hbm_bytes": 1.0e9,
             "wire": "int8", "logical_bytes": 4.0e8, "wire_bytes": 1.0e8},
            peaks,
        )
        self.assertFalse(row2["wire_verdict_flip"])
        # non-wire rows stay clean
        row3 = roofline.attribute(
            {"fingerprint": "fp", "kind": "fused", "calls": 1,
             "total_s": 0.1, "p50_s": 0.1, "min_s": 0.1,
             "flops": 1e9, "hbm_bytes": 1e9},
            peaks,
        )
        self.assertIsNone(row3["wire"])
        self.assertIsNone(row3["wire_ratio"])
        self.assertIsNone(row3["wire_verdict_flip"])

    def test_render_has_wire_columns_and_flip_marker(self):
        peaks = {"device": "x", "known": True, "bf16_tflops": 197.0,
                 "f32_tflops": 49.25, "hbm_gbps": 819.0, "source": "env"}
        doc = roofline.report(
            [
                {"fingerprint": "fw", "kind": "ring_matmul", "calls": 2,
                 "total_s": 0.2, "p50_s": 0.1, "min_s": 0.1, "compiles": 1,
                 "hits": 1, "n_roots": 1, "ops": 1,
                 "flops": 1.0e12, "hbm_bytes": 1.0e9, "wire": "int8",
                 "logical_bytes": 2.0e10, "wire_bytes": 5.0e9},
                {"fingerprint": "fp", "kind": "fused", "calls": 1,
                 "total_s": 0.1, "p50_s": 0.1, "min_s": 0.1, "compiles": 1,
                 "hits": 0, "n_roots": 1, "ops": 1,
                 "flops": 1e9, "hbm_bytes": 1e9},
            ],
            peaks=peaks,
        )
        text = roofline.render(doc)
        self.assertIn("lgclMB", text)
        self.assertIn("wireMB", text)
        self.assertIn("wire_x", text)
        self.assertIn("[wire-flip]", text)
        wire_line = [l for l in text.splitlines() if l.startswith("fw")][0]
        self.assertIn("20000.00", wire_line)  # logical MB
        self.assertIn("5000.00", wire_line)   # wire MB
        self.assertIn("4.0", wire_line)       # compression ratio
        plain_line = [l for l in text.splitlines() if l.startswith("fp")][0]
        self.assertNotIn("[wire-flip]", plain_line)


if __name__ == "__main__":
    unittest.main()
