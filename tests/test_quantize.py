"""Quantized inference epilogues (round 16, ``core/quantize.py``).

The tentpole laws, pinned at whatever mesh ``HEAT_TEST_DEVICES`` forces
(scripts/ci.sh stage 19 runs this file at 8/4/1):

* per-channel absmax round trip is bounded by half a quantization step;
* the sharded int8 GEMM agrees with the replicated one (k-pad masking
  keeps shard-boundary exactness) and with an f64 oracle to bounded
  error;
* explore returns the bf16 reference result bitwise, and with the
  tuning plane off the quantized entry IS the bf16 path bit-for-bit
  with zero tuning-table decisions;
* ``("bf16", "int8")`` arm entries survive the save/load warm-start
  cache round trip;
* epilogue extras are validated at construction / call-site (satellite:
  a wrong-extent scale names the expected axis and length instead of
  dying inside the ring program);
* the memtrack ledger attributes the residency win per dtype
  (``bytes_by_dtype``, ≥3x int8-vs-f32 — the acceptance bar).
"""

import os
import tempfile
import unittest

import jax
import jax.numpy as jnp
import numpy as np

import heat_tpu as ht
from heat_tpu.core import autotune, memtrack, quantize, telemetry
from heat_tpu.parallel import overlap
from heat_tpu.parallel.expert import moe_ffn

from .base import TestCase

_MULTI = len(jax.local_devices()) > 1
_HAS_FP8 = hasattr(jnp, "float8_e4m3fn")


class _Tuned:
    """Scoped tuning plane (the test_autotune idiom): enabled via API,
    events level, clean table/counters on both sides."""

    def __enter__(self):
        self.prev_level = telemetry.set_level("events")
        self.prev_on = autotune.set_enabled(True)
        telemetry.reset_all()
        telemetry.clear_events()
        autotune.reset()
        return self

    def __exit__(self, *exc):
        autotune.set_enabled(self.prev_on)
        autotune.reset()
        telemetry.reset_all()
        telemetry.clear_events()
        telemetry.set_level(self.prev_level)
        return False


class _EventsLevel:
    """Scoped events level + clean memtrack ledger on both sides."""

    def __enter__(self):
        self.prev = telemetry.set_level("events")
        telemetry.clear_events()
        memtrack.reset()
        return self

    def __exit__(self, *exc):
        telemetry.set_level(self.prev)
        telemetry.clear_events()
        memtrack.reset()
        return False


def _rand(shape, seed, dtype=np.float32, scale=1.0):
    return (
        np.random.default_rng(seed).standard_normal(shape) * scale
    ).astype(dtype)


class TestRoundTrip(TestCase):
    """Per-channel absmax numerics."""

    def test_int8_error_bounded_by_half_step(self):
        w_np = _rand((33, 17), 0)
        w = ht.array(w_np, split=0)
        for axis in (0, 1):
            qw = quantize.quantize_weights(w, "int8", axis=axis)
            self.assertEqual(qw.qdtype, "int8")
            self.assertEqual(tuple(qw.scale.shape), (w_np.shape[axis],))
            deq = qw.dequantize()
            self.assertEqual(deq.dtype, ht.float32)
            step = np.asarray(qw.scale)
            bound = 0.5 * (step[:, None] if axis == 0 else step[None, :])
            err = np.abs(deq.numpy() - w_np)
            self.assertTrue(
                (err <= bound + 1e-7).all(),
                f"axis={axis} max excess {(err - bound).max()}",
            )

    def test_all_zero_channel_is_exact(self):
        w_np = _rand((8, 6), 1)
        w_np[3, :] = 0.0
        qw = quantize.quantize_weights(ht.array(w_np, split=0), "int8", axis=0)
        deq = qw.dequantize().numpy()
        self.assertTrue(np.isfinite(deq).all())
        self.assertTrue((deq[3] == 0.0).all())

    @unittest.skipUnless(_HAS_FP8, "no float8_e4m3fn in this jax")
    def test_fp8_roundtrip_bounded(self):
        w_np = _rand((16, 12), 2)
        qw = quantize.quantize_weights(ht.array(w_np, split=0), "fp8", axis=0)
        self.assertIn("float8", qw.qdtype)
        err = np.abs(qw.dequantize().numpy() - w_np)
        # e4m3: 3 mantissa bits → relative error ≤ 2^-4 of the value,
        # plus one scale quantum for the subnormal tail
        bound = np.abs(w_np) * 2.0 ** -4 + np.asarray(qw.scale)[:, None]
        self.assertTrue((err <= bound).all(), f"excess {(err - bound).max()}")

    def test_tensor_tier_tuple_axes(self):
        w = jnp.asarray(_rand((4, 6, 8), 3))
        qt = quantize.quantize_tensor(w, "int8", axis=(0, 2))
        self.assertEqual(qt.axes, (0, 2))
        self.assertEqual(tuple(qt.scale.shape), (4, 8))
        deq = np.asarray(quantize.dequantize_tensor(qt))
        bound = 0.5 * np.asarray(qt.scale)[:, None, :] + 1e-7
        self.assertTrue((np.abs(deq - np.asarray(w)) <= bound).all())

    def test_quantize_params_walks_targets(self):
        params = {
            "moe": {
                "w_in": jnp.asarray(_rand((4, 8, 16), 4)),
                "w_out": jnp.asarray(_rand((4, 16, 8), 5)),
                "gate": jnp.asarray(_rand((8, 4), 6)),
            }
        }
        out = quantize.quantize_params(params, "int8")
        self.assertIsInstance(out["moe"]["w_in"], quantize.QuantizedTensor)
        self.assertIsInstance(out["moe"]["w_out"], quantize.QuantizedTensor)
        self.assertIs(out["moe"]["gate"], params["moe"]["gate"])

    def test_bad_dtype_rejected(self):
        w = ht.array(_rand((4, 4), 7), split=0)
        with self.assertRaises(ValueError):
            quantize.quantize_weights(w, "int4")


class TestExactnessLaw(TestCase):
    """The sharded int8 GEMM equals the replicated one (k-pad masking at
    shard boundaries) and tracks an f64 oracle to bounded error."""

    def _operands(self, m, k, n, split):
        x_np = _rand((m, k), 10)
        w_np = _rand((n, k), 11)  # torch (out, in) layout
        x = ht.array(x_np, split=split)
        w = ht.array(w_np, split=split)
        qw = quantize.quantize_weights(w, "int8", axis=0)
        return x_np, w_np, x, qw

    def _oracle(self, x_np, qw):
        q = np.asarray(qw.q).astype(np.float64)
        s = np.asarray(qw.scale).astype(np.float64)
        return (x_np.astype(np.float64) @ q.T) * s[None, :]

    def test_int8_arm_matches_f64_oracle(self):
        # k and m chosen NOT mesh-divisible so the ring path (when it
        # engages) exercises the k-pad mask
        m, k, n = 13, 30, 16
        x_np, _, x, qw = self._operands(m, k, n, split=0)
        out = quantize.matmul_quantized(x, qw.T, arm="int8")
        self.assertEqual(tuple(out.shape), (m, n))
        ref = self._oracle(x_np, qw)
        np.testing.assert_allclose(out.numpy(), ref, rtol=2e-5, atol=2e-5)

    @unittest.skipUnless(_MULTI, "needs a multi-device mesh")
    def test_sharded_matches_replicated(self):
        m, k, n = 24, 30, 16
        x_np, w_np, x, qw = self._operands(m, k, n, split=0)
        out_split = quantize.matmul_quantized(x, qw.T, arm="int8")
        x_rep = ht.array(x_np, split=None)
        qw_rep = quantize.quantize_weights(
            ht.array(w_np, split=None), "int8", axis=0
        )
        out_rep = quantize.matmul_quantized(x_rep, qw_rep.T, arm="int8")
        # same int8 grid on both layouts (quantization is elementwise),
        # so only accumulation order may differ
        np.testing.assert_array_equal(
            np.asarray(qw.q), np.asarray(qw_rep.q)
        )
        np.testing.assert_allclose(
            out_split.numpy(), out_rep.numpy(), rtol=1e-5, atol=1e-5
        )

    def test_linear_routes_quantized(self):
        m, k, n = 8, 12, 16
        x_np, w_np, x, qw = self._operands(m, k, n, split=0)
        from heat_tpu.nn import functional as F

        bias = ht.array(np.zeros(n, np.float32), split=None)
        out = F.linear(x, qw, bias)
        ref = self._oracle(x_np, qw)
        np.testing.assert_allclose(out.numpy(), ref, rtol=2e-5, atol=2e-5)

    def test_ht_matmul_routes_quantized(self):
        m, k, n = 8, 12, 16
        x_np, _, x, qw = self._operands(m, k, n, split=0)
        out = ht.matmul(x, qw.T)
        ref = self._oracle(x_np, qw)
        np.testing.assert_allclose(out.numpy(), ref, rtol=2e-5, atol=2e-5)

    def test_shape_and_axis_validation(self):
        x = ht.array(_rand((4, 6), 12), split=0)
        w = ht.array(_rand((8, 6), 13), split=0)
        qw = quantize.quantize_weights(w, "int8", axis=0)
        with self.assertRaisesRegex(ValueError, "channel axis"):
            quantize.matmul_quantized(x, qw)  # axis 0, needs transpose
        with self.assertRaisesRegex(ValueError, "inner dimensions"):
            quantize.matmul_quantized(
                ht.array(_rand((4, 5), 14), split=0), qw.T
            )
        with self.assertRaisesRegex(ValueError, "channel axis 0"):
            quantize.linear(x, qw.T)


class TestArmDispatch(TestCase):
    """Explore-returns-reference, off-restores-bf16, winner execution,
    and the error-fallback guarantee."""

    def test_autotune_off_is_bf16_bitwise_with_zero_decisions(self):
        # conftest exports HEAT_TPU_AUTOTUNE=off for the whole suite
        self.assertFalse(autotune.enabled())
        x = ht.array(_rand((8, 12), 20), split=0)
        qw = quantize.quantize_weights(
            ht.array(_rand((16, 12), 21), split=0), "int8", axis=0
        )
        before = len(autotune._TABLE)
        out = quantize.matmul_quantized(x, qw.T)
        ref = quantize.matmul_quantized(x, qw.T, arm="bf16")
        np.testing.assert_array_equal(out.numpy(), ref.numpy())
        self.assertEqual(len(autotune._TABLE), before)

    def test_explore_returns_bf16_bitwise(self):
        x_np, w_np = _rand((8, 12), 22), _rand((16, 12), 23)
        with _Tuned():
            x = ht.array(x_np, split=0)
            qw = quantize.quantize_weights(
                ht.array(w_np, split=0), "int8", axis=0
            )
            out = quantize.matmul_quantized(x, qw.T)  # first call: explore
            rows = [
                r for r in autotune.report()["rows"]
                if tuple(r.get("arms", ())) == quantize.QUANT_ARMS
            ]
            self.assertTrue(rows, autotune.report()["rows"])
        with _Tuned():  # fresh table: the same inner-dispatch route
            x = ht.array(x_np, split=0)
            qw = quantize.quantize_weights(
                ht.array(w_np, split=0), "int8", axis=0
            )
            ref = quantize.matmul_quantized(x, qw.T, arm="bf16")
        np.testing.assert_array_equal(out.numpy(), ref.numpy())

    def test_explore_returns_reference_value(self):
        with _Tuned():
            out = quantize.tuned_arm(
                "law", (1,), lambda: "reference", lambda: "quantized"
            )
            self.assertEqual(out, "reference")

    def test_resolved_winner_runs_alone(self):
        with _Tuned():
            key = autotune.key("quant", "law2", 7)
            autotune.decide(key, "bf16", desc="law2", arms=quantize.QUANT_ARMS)
            for i in range(autotune.explore_k()):
                autotune.observe(key, "bf16", 0.010 + i * 1e-4)
                autotune.observe(key, "int8", 0.001 + i * 1e-4)
            self.assertEqual(autotune.winner(key), "int8")
            seen = {"bf16": 0, "int8": 0}

            def bf16():
                seen["bf16"] += 1
                return "b"

            def int8():
                seen["int8"] += 1
                return "i"

            out = quantize.tuned_arm("law2", (7,), bf16, int8)
            self.assertEqual(out, "i")
            self.assertEqual(seen, {"bf16": 0, "int8": 1})

    def test_int8_arm_error_falls_back_to_bf16(self):
        with _Tuned():
            key = autotune.key("quant", "law3", 7)
            autotune.decide(key, "bf16", desc="law3", arms=quantize.QUANT_ARMS)
            for i in range(autotune.explore_k()):
                autotune.observe(key, "bf16", 0.010)
                autotune.observe(key, "int8", 0.001)
            self.assertEqual(autotune.winner(key), "int8")

            def int8():
                raise RuntimeError("boom")

            out = quantize.tuned_arm("law3", (7,), lambda: "b", int8)
            self.assertEqual(out, "b")
            self.assertEqual(quantize.stats()["int8_fallbacks"], 1)

    def test_traced_path_declines_without_table_writes(self):
        gate = jnp.asarray(_rand((8, 4), 24))
        q_in = quantize.quantize_tensor(
            jnp.asarray(_rand((4, 8, 16), 25)), "int8", axis=(0, 2)
        )
        q_out = quantize.quantize_tensor(
            jnp.asarray(_rand((4, 16, 8), 26)), "int8", axis=(0, 2)
        )
        with _Tuned():
            fn = jax.jit(
                lambda v: moe_ffn(v, gate, q_in, q_out, k=2)[0]
            )
            y = fn(jnp.asarray(_rand((16, 8), 27)))
            jax.block_until_ready(y)  # ht: HT002 ok — test fence
            quant_rows = [
                r for r in autotune.report()["rows"]
                if tuple(r.get("arms", ())) == quantize.QUANT_ARMS
            ]
            self.assertEqual(quant_rows, [])


class TestPersistence(TestCase):
    """("bf16","int8") entries ride the versioned warm-start cache."""

    def test_save_load_roundtrip_quant_arms(self):
        with _Tuned():
            key = autotune.key("quant", "linear", 64, 128, 256, 8, "float32")
            autotune.decide(key, "bf16", desc="q", arms=quantize.QUANT_ARMS)
            for i in range(autotune.explore_k()):
                autotune.observe(key, "bf16", 0.01 + i * 1e-4)
                autotune.observe(key, "int8", 0.002 + i * 1e-4)
            self.assertEqual(autotune.winner(key), "int8")
            with tempfile.TemporaryDirectory() as d:
                path = os.path.join(d, "tune.json")
                self.assertGreaterEqual(autotune.save(path), 1)
                autotune.reset()
                self.assertIsNone(autotune.winner(key))
                self.assertGreaterEqual(autotune.load(path), 1)
                self.assertEqual(autotune.winner(key), "int8")
                self.assertEqual(
                    tuple(autotune._TABLE[key]["arms"]), quantize.QUANT_ARMS
                )


class TestEpilogueValidation(TestCase):
    """Satellite: bad epilogue operands fail early with the expected
    axis/length in the message, not deep inside the ring program."""

    def test_construction_rejects_3d_scale(self):
        with self.assertRaisesRegex(ValueError, "scalar, 1-D, or 2-D"):
            overlap.Epilogue(scale=np.ones((2, 3, 4), np.float32))

    def test_construction_rejects_non_numeric(self):
        with self.assertRaisesRegex(TypeError, "numeric"):
            overlap.Epilogue(bias=np.array(["a", "b"]))

    def test_construction_rejects_non_callable_activation(self):
        with self.assertRaisesRegex(TypeError, "callable"):
            overlap.Epilogue(activation="relu")

    def test_construction_rejects_bad_dtype(self):
        with self.assertRaises(TypeError):
            overlap.Epilogue(dtype="not-a-dtype")

    def test_wrong_extent_extra_names_axis_and_length(self):
        a = ht.array(_rand((16, 8), 30), split=0)
        b = ht.array(_rand((8, 24), 31), split=0)
        bad = overlap.Epilogue(scale=np.ones(23, np.float32))  # n is 24
        with self.assertRaisesRegex(
            ValueError, r"expected 1 or the full result extent 24"
        ):
            overlap.matmul(a, b, epilogue=bad)

    def test_wrong_extent_extra_raw_entry(self):
        a = ht.array(_rand((16, 8), 32), split=0)
        b = ht.array(_rand((8, 24), 33), split=0)
        bad = overlap.Epilogue(bias=np.ones((15, 1), np.float32))  # m is 16
        with self.assertRaisesRegex(ValueError, r"axis 0 of \(16, 24\)"):
            overlap.matmul_raw(
                a.comm, a.parray, b.parray, (16, 8), (8, 24), 0, 0, 0,
                epilogue=bad,
            )

    def test_valid_epilogue_still_passes(self):
        a = ht.array(_rand((16, 8), 34), split=0)
        b = ht.array(_rand((8, 24), 35), split=0)
        ep = overlap.Epilogue(scale=np.full(24, 2.0, np.float32))
        out = overlap.matmul(a, b, epilogue=ep)
        if out is not None:  # dispatcher may decline to GSPMD; law holds
            ref = 2.0 * (a.numpy() @ b.numpy())
            np.testing.assert_allclose(out.numpy(), ref, rtol=1e-5, atol=1e-5)


class TestResidencyLedger(TestCase):
    """Satellite: bytes_by_dtype in summary/census/Prometheus, and the
    ≥3x int8-vs-f32 acceptance bar measured from the ledger."""

    def test_bytes_by_dtype_attributes_quantized_buffers(self):
        with _EventsLevel():
            w = ht.array(_rand((256, 128), 40), split=0)
            memtrack.register_buffer(w.parray, tag="leaf")
            qw = quantize.quantize_weights(w, "int8", axis=0)
            s = memtrack.summary()
            self.assertIn("int8", s["bytes_by_dtype"])
            self.assertGreaterEqual(
                s["bytes_by_dtype"]["int8"], 256 * 128
            )
            self.assertIn("bytes_by_dtype", memtrack.census())
            # the acceptance bar: quantized residency (buffer + scales)
            # is at least 3x below the f32 master it replaces
            master_bytes = int(w.parray.nbytes)
            self.assertLessEqual(3 * qw.nbytes, master_bytes)
            text = telemetry.export_prometheus()
            self.assertIn('heat_tpu_mem_bytes_by_dtype{dtype="int8"}', text)

    def test_donate_drops_and_tags_master(self):
        with _EventsLevel():
            w = ht.array(_rand((64, 32), 41), split=0)
            memtrack.register_buffer(w.parray, tag="leaf")
            quantize.quantize_weights(w, "int8", axis=0, donate=True)
            tags = [
                rec["tag"] for rec in memtrack._LEDGER.values()
            ]
            self.assertIn("donated", tags)


class TestMoEQuantized(TestCase):
    """Quantized expert weights through the (sharded) MoE FFN."""

    def _fixture(self, seed=50):
        t, d, h, E = 32, 16, 32, 8
        x = jnp.asarray(_rand((t, d), seed))
        gate = jnp.asarray(_rand((d, E), seed + 1))
        w_in = jnp.asarray(_rand((E, d, h), seed + 2, scale=0.1))
        w_out = jnp.asarray(_rand((E, h, d), seed + 3, scale=0.1))
        q_in = quantize.quantize_tensor(w_in, "int8", axis=(0, 2))
        q_out = quantize.quantize_tensor(w_out, "int8", axis=(0, 2))
        return x, gate, w_in, w_out, q_in, q_out

    def test_bf16_arm_bitwise_vs_dequantized_masters(self):
        x, gate, _, _, q_in, q_out = self._fixture()
        y_q, _ = moe_ffn(x, gate, q_in, q_out, k=2)  # autotune off → bf16
        y_d, _ = moe_ffn(
            x, gate, quantize.dequantize_tensor(q_in),
            quantize.dequantize_tensor(q_out), k=2,
        )
        np.testing.assert_array_equal(np.asarray(y_q), np.asarray(y_d))

    def test_int8_path_bounded_error(self):
        from heat_tpu.parallel.expert import _moe_run

        x, gate, w_in, w_out, q_in, q_out = self._fixture()
        y_ref, _ = moe_ffn(x, gate, w_in, w_out, k=2)
        y_i, _ = _moe_run(
            x, gate, q_in.q, q_out.q, q_in.scale, q_out.scale, k=2,
            capacity_factor=2.0, activation=jax.nn.gelu, mesh=None, axis="ep",
        )
        scale = float(np.abs(np.asarray(y_ref)).max())
        err = float(np.abs(np.asarray(y_i) - np.asarray(y_ref)).max())
        self.assertLess(err, 0.02 * max(scale, 1.0))

    @unittest.skipUnless(_MULTI, "needs a multi-device mesh")
    def test_sharded_quantized_matches_sharded_master(self):
        from jax.sharding import Mesh
        from heat_tpu.parallel.expert import _moe_run

        mesh = Mesh(np.array(jax.devices()), ("ep",))
        x, gate, w_in, w_out, q_in, q_out = self._fixture()
        y_ref, _ = moe_ffn(x, gate, w_in, w_out, k=2, mesh=mesh, axis="ep")
        y_i, _ = _moe_run(
            x, gate, q_in.q, q_out.q, q_in.scale, q_out.scale, k=2,
            capacity_factor=2.0, activation=jax.nn.gelu, mesh=mesh, axis="ep",
        )
        scale = float(np.abs(np.asarray(y_ref)).max())
        err = float(np.abs(np.asarray(y_i) - np.asarray(y_ref)).max())
        self.assertLess(err, 0.02 * max(scale, 1.0))

    def test_mixed_quantization_rejected(self):
        x, gate, w_in, _, _, q_out = self._fixture()
        with self.assertRaisesRegex(ValueError, "both w_in and w_out"):
            moe_ffn(x, gate, w_in, q_out, k=2)

    def test_wrong_axes_rejected(self):
        x, gate, w_in, w_out, _, _ = self._fixture()
        bad_in = quantize.quantize_tensor(w_in, "int8", axis=2)
        bad_out = quantize.quantize_tensor(w_out, "int8", axis=2)
        with self.assertRaisesRegex(ValueError, r"axis=\(0, 2\)"):
            moe_ffn(x, gate, bad_in, bad_out, k=2)

    def test_moemlp_call_time_quantize(self):
        from heat_tpu.models.transformer import MoEMlp

        x = jnp.asarray(_rand((4, 16, 8), 60))
        model = MoEMlp(num_experts=4, hidden=16, quantize="int8")
        params = model.init(jax.random.PRNGKey(0), x)
        y = model.apply(params, x)
        self.assertEqual(y.shape, x.shape)
        self.assertTrue(np.isfinite(np.asarray(y)).all())


class TestKnnQuantized(TestCase):
    """The quantized corpus behind the k-NN serving workload."""

    def _fit(self, n=64, d=16, seed=70):
        X = _rand((n, d), seed)
        y = np.random.default_rng(seed + 1).integers(0, 3, n)
        clf = ht.classification.KNeighborsClassifier(n_neighbors=3)
        clf.fit(ht.array(X, split=0), ht.array(y, split=0))
        return clf, X, y

    def test_predict_parity_after_quantize(self):
        clf, X, _ = self._fit()
        q = ht.array(_rand((16, X.shape[1]), 72), split=0)
        ref = clf.predict(q).numpy()
        clf.quantize_("int8")
        self.assertIsNone(clf.x)  # master released — the residency win
        got = clf.predict(q).numpy()
        # int8 corpus perturbs distances by <0.5 quantization step per
        # feature; ties can flip, so demand near-total agreement rather
        # than exactness
        self.assertGreaterEqual(float((ref == got).mean()), 0.9)

    def test_cdist_quantized_matches_dequantized_cdist(self):
        from heat_tpu.spatial import distance

        clf, X, _ = self._fit()
        clf.quantize_("int8")
        q = ht.array(_rand((16, X.shape[1]), 73), split=0)
        via_deq = distance.cdist(q, clf._qx.dequantize()).numpy()
        d = distance.cdist_quantized(q, clf._qx)
        if d is None:  # single-device mesh: ring ineligible by design
            self.assertFalse(_MULTI)
            return
        np.testing.assert_allclose(d.numpy(), via_deq, rtol=1e-4, atol=1e-4)

    def test_ring_ineligible_rows_fall_back(self):
        clf, X, _ = self._fit()
        clf.quantize_("int8")
        # 13 query rows are not divisible by any multi-device mesh → the
        # quantized ring declines and predict dequantizes for the call
        q = ht.array(_rand((13, X.shape[1]), 74), split=0)
        labels = clf.predict(q).numpy()
        self.assertEqual(labels.shape, (13,))

    def test_quantize_guards(self):
        clf = ht.classification.KNeighborsClassifier(n_neighbors=3)
        with self.assertRaisesRegex(RuntimeError, "fit"):
            clf.quantize_()
        clf, _, _ = self._fit()
        clf.quantize_()
        with self.assertRaisesRegex(RuntimeError, "already quantized"):
            clf.quantize_()


if __name__ == "__main__":
    unittest.main()
