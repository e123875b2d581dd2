"""Pallas kernel tier (ISSUE 12): fused CholeskyQR2 panel and fused lasso
sweep — dispatched through autotune.

Everything runs on the CPU mesh through Pallas interpret mode
(``HEAT_TPU_PALLAS=interpret`` scoped per test), so kernel *logic* is
exercised with no TPU: value equality against the classic lowerings,
the autotune arm-registration laws (explore-then-sticky, safe decline
on unsupported layouts, ``HEAT_TPU_AUTOTUNE=off`` restoring today's
dispatch bit-for-bit), and ``HEAT_TPU_PALLAS=off`` restoring the classic
lowering with no kernel arm registered.  The suite
default keeps autotune off (conftest); kernel-arm tests opt back in
via the API, mirroring tests/test_autotune.py."""

import json
import os
import tempfile
import unittest

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core import autotune, telemetry
from heat_tpu.core.linalg.qr import _cholesky_qr2, orthogonality_defect
from heat_tpu.ops import _pallas_common, lasso_sweep, qr_panel
from heat_tpu.regression import lasso as lasso_mod
from heat_tpu.regression.lasso import Lasso, _cd_sweep

from .base import TestCase, scripted_clock

_MULTI = len(jax.local_devices()) > 1


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


class _Interpret:
    """Scoped ``HEAT_TPU_PALLAS=interpret`` (restores the prior value)."""

    def __init__(self, value="interpret"):
        self.value = value

    def __enter__(self):
        self.prev = os.environ.get("HEAT_TPU_PALLAS")
        if self.value is None:
            os.environ.pop("HEAT_TPU_PALLAS", None)
        else:
            os.environ["HEAT_TPU_PALLAS"] = self.value
        return self

    def __exit__(self, *exc):
        if self.prev is None:
            os.environ.pop("HEAT_TPU_PALLAS", None)
        else:
            os.environ["HEAT_TPU_PALLAS"] = self.prev
        return False


def _table_rows():
    return [
        (k[0], e.get("winner"), tuple(e["arms"]),
         {a: len(s) for a, s in e["arms"].items()})
        for k, e in autotune._TABLE.items()
    ]


class TestPallasCommon(TestCase):
    """Satellite: the shared kernel plumbing all the kernels route
    through (mode selection, tile geometry helpers)."""

    def test_mode_forced_by_env(self):
        with _Interpret("interpret"):
            self.assertEqual(_pallas_common.mode(), "interpret")
        with _Interpret("tpu"):
            self.assertEqual(_pallas_common.mode(), "tpu")
        with _Interpret("off"):
            self.assertEqual(_pallas_common.mode(), "off")
        with _Interpret(None):
            # CPU backend, nothing forced: Pallas tier is off
            self.assertEqual(_pallas_common.mode(), "off")

    def test_sublane_and_pad(self):
        self.assertEqual(_pallas_common.sublane(jnp.dtype(jnp.float32)), 8)
        self.assertEqual(_pallas_common.sublane(jnp.dtype(jnp.bfloat16)), 16)
        self.assertEqual(_pallas_common.sublane(jnp.dtype(jnp.int8)), 32)
        x = jnp.ones((5, 10), jnp.float32)
        p = _pallas_common.pad_to(x, (8, 128))
        self.assertEqual(p.shape, (8, 128))
        np.testing.assert_array_equal(np.asarray(p[:5, :10]), np.asarray(x))
        self.assertEqual(float(jnp.sum(jnp.abs(p))), 50.0)


class TestNarrowMinorReshape(TestCase):
    """The split-crossing reshape to a narrow minor dim: one lowering,
    no kernel arm (Mosaic has no lane->sublane shape cast)."""

    @unittest.skipUnless(_MULTI, "needs a multi-device mesh")
    def test_pad_lane_regression_source_pads(self):
        """ISSUE 12 satellite: a narrow-minor reshape whose SOURCE shard
        carries pad rows (999 % mesh != 0) must match eager exactly —
        including with a fused elementwise tail, where chain garbage on
        source-axis pad rows would cross the all_to_all."""
        x = (np.arange(999 * 20, dtype=np.float32).reshape(999, 20)
             % 37) / 11.0
        want = np.exp(x).reshape(1998, 10)
        a = ht.array(x, split=0)
        out = ht.reshape(ht.exp(a), (1998, 10))
        self.assert_array_equal(out, want, rtol=1e-5, atol=1e-6)
        # tuned dispatch registers no classic/kernel entry at this site
        with _Tuned():
            for _ in range(4):
                again = ht.reshape(ht.exp(ht.array(x, split=0)), (1998, 10))
            np.testing.assert_array_equal(again.numpy(), out.numpy())
            self.assertEqual(
                [r for r in _table_rows() if r[2] == ("classic", "kernel")], []
            )


class TestQRPanelKernel(TestCase):
    """Tentpole kernel 2: fused syrk + Cholesky + trsm panel for
    CholeskyQR2 (classic-equivalent to f32 rounding)."""

    def test_fused_panel_matches_classic_chain(self):
        rng = np.random.default_rng(12)
        for m, n in [(64, 8), (200, 24), (513, 100)]:
            x = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
            r, rinv = qr_panel.fused_gram_chol(x, interpret=True)
            l = jnp.linalg.cholesky(x.T @ x)
            rinv_ref = jax.lax.linalg.triangular_solve(
                l, jnp.eye(n, dtype=x.dtype), lower=True, left_side=True
            ).T
            np.testing.assert_allclose(
                np.asarray(r), np.asarray(l.T), rtol=1e-4, atol=1e-4
            )
            np.testing.assert_allclose(
                np.asarray(rinv), np.asarray(rinv_ref), rtol=1e-3, atol=1e-4
            )

    def test_breakdown_nan_latches_like_classic(self):
        rng = np.random.default_rng(13)
        x = rng.standard_normal((64, 8)).astype(np.float32)
        x[:, 3] = 0.0  # zero pivot: Cholesky breaks down deterministically
        r, _ = qr_panel.fused_gram_chol(jnp.asarray(x), interpret=True)
        self.assertTrue(bool(jnp.any(jnp.isnan(r))))
        # parity: the classic lowering NaN-latches the same input
        l = jnp.linalg.cholesky(jnp.asarray(x).T @ jnp.asarray(x))
        self.assertTrue(bool(jnp.any(jnp.isnan(l))))

    def test_panel_mode_declines(self):
        f32, f64 = jnp.dtype(jnp.float32), jnp.dtype(jnp.float64)
        with _Interpret():
            self.assertEqual(
                qr_panel.panel_mode(512, 64, f32, False, None, 1), "interpret"
            )
            # mixed precision: bf16 pass-1 belongs to the classic path
            self.assertEqual(
                qr_panel.panel_mode(512, 64, f32, True, None, 1), "off"
            )
            self.assertEqual(
                qr_panel.panel_mode(512, 64, f64, False, None, 1), "off"
            )
            # sharded operand: single-device kernel program — decline
            self.assertEqual(
                qr_panel.panel_mode(512, 64, f32, False, 0, 8), "off"
            )
            # leaf panel wider than the VMEM budget
            self.assertEqual(
                qr_panel.panel_mode(4096, 4096, f32, False, None, 1), "off"
            )
        with _Interpret(None):
            self.assertEqual(
                qr_panel.panel_mode(512, 64, f32, False, None, 1), "off"
            )

    def test_qr_kernel_arm_explore_then_sticky(self):
        rng = np.random.default_rng(14)
        for shape in [(512, 64), (256, 256)]:  # CholeskyQR2 and blocked BCGS2
            a_np = rng.standard_normal(shape).astype(np.float32)
            with _Interpret(), _Tuned(), scripted_clock(classic=0.002, kernel=0.001):
                a = ht.array(a_np)
                for _ in range(7):
                    q, r = ht.linalg.qr(a)
                rows = [r_ for r_ in _table_rows() if r_[2] == ("classic", "kernel")]
                self.assertTrue(rows, _table_rows())
                self.assertEqual(rows[0][3], {"classic": 3, "kernel": 3})
                self.assertEqual(rows[0][1], "kernel")
                # value quality of the winning (kernel) arm
                self.assertLess(float(orthogonality_defect(q).larray), 3e-4)
                recon = np.asarray(q.larray) @ np.asarray(r.larray)
                np.testing.assert_allclose(recon, a_np, rtol=1e-3, atol=1e-3)

    def test_explore_returns_classic_result(self):
        rng = np.random.default_rng(15)
        a_np = rng.standard_normal((512, 64)).astype(np.float32)
        a = ht.array(a_np)
        with _Interpret():
            q_c, r_c = ht.linalg.qr(a)  # autotune off: pure classic
            with _Tuned():
                q_e, r_e = ht.linalg.qr(a)  # first call: explore round
            np.testing.assert_array_equal(
                np.asarray(q_e.larray), np.asarray(q_c.larray)
            )
            np.testing.assert_array_equal(
                np.asarray(r_e.larray), np.asarray(r_c.larray)
            )

    def test_fused_kernel_value_equality_in_dispatch_path(self):
        # run _cholesky_qr2 with the kernel flag directly: same factors
        # as the classic lowering to documented tolerance
        rng = np.random.default_rng(16)
        arr = jnp.asarray(rng.standard_normal((512, 64)), jnp.float32)
        q_c, r_c = _cholesky_qr2(arr, calc_q=True, mixed=False, kernel="")
        q_k, r_k = _cholesky_qr2(
            arr, calc_q=True, mixed=False, kernel="interpret"
        )
        np.testing.assert_allclose(
            np.asarray(q_k), np.asarray(q_c), rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            np.asarray(r_k), np.asarray(r_c), rtol=1e-4, atol=1e-4
        )


class TestLassoSweepKernel(TestCase):
    """Tentpole kernel 3: fused CD sweep with the residual resident in
    VMEM across all coordinates."""

    def test_sweep_matches_classic(self):
        rng = np.random.default_rng(18)
        for m, n in [(50, 6), (200, 129), (333, 17)]:
            X = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
            y = jnp.asarray(rng.standard_normal(m), jnp.float32)
            th = jnp.asarray(rng.standard_normal(n) * 0.1, jnp.float32)
            ref = _cd_sweep(X, y, th, 0.1)
            got = lasso_sweep.sweep(X, y, th, 0.1, interpret=True)
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(ref), rtol=1e-4, atol=1e-5
            )

    def test_sweep_mode_declines(self):
        f32 = jnp.dtype(jnp.float32)
        with _Interpret():
            self.assertEqual(lasso_sweep.sweep_mode(200, 30, f32, None, 1), "interpret")
            # sharded design matrix
            self.assertEqual(lasso_sweep.sweep_mode(200, 30, f32, 0, 8), "off")
            # residual taller than the VMEM budget
            self.assertEqual(
                lasso_sweep.sweep_mode(100_000, 30, f32, None, 1), "off"
            )
            self.assertEqual(
                lasso_sweep.sweep_mode(200, 30, jnp.dtype(jnp.int32), None, 1),
                "off",
            )
        with _Interpret(None):
            self.assertEqual(lasso_sweep.sweep_mode(200, 30, f32, None, 1), "off")

    def _problem(self, seed=19, m=200, n=30):
        rng = np.random.default_rng(seed)
        X = rng.standard_normal((m, n)).astype(np.float32)
        w = np.zeros(n, np.float32)
        w[:5] = rng.standard_normal(5)
        y = X @ w + 0.01 * rng.standard_normal(m).astype(np.float32)
        return ht.array(X), ht.array(y.reshape(-1, 1))

    def test_fit_kernel_arm_explore_then_sticky(self):
        xa, ya = self._problem()
        with _Interpret(), _Tuned(), scripted_clock(classic=0.002, kernel=0.001):
            thetas = []
            for _ in range(7):
                est = Lasso(lam=0.05, max_iter=100, tol=1e-6)
                est.fit(xa, ya)
                thetas.append(np.asarray(est.theta.larray).ravel())
            rows = [r for r in _table_rows() if r[2] == ("classic", "kernel")]
            self.assertTrue(rows, _table_rows())
            self.assertEqual(rows[0][3], {"classic": 3, "kernel": 3})
            # coefficients agree across explore and sticky phases
            for th in thetas[1:]:
                np.testing.assert_allclose(th, thetas[0], rtol=1e-3, atol=1e-4)

    def test_explore_returns_classic_coefficients(self):
        xa, ya = self._problem(seed=20)
        with _Interpret():
            est = Lasso(lam=0.05, max_iter=100, tol=1e-6)
            est.fit(xa, ya)  # autotune off: pure classic
            ref = np.asarray(est.theta.larray)
            with _Tuned():
                est2 = Lasso(lam=0.05, max_iter=100, tol=1e-6)
                est2.fit(xa, ya)  # explore round
            np.testing.assert_array_equal(np.asarray(est2.theta.larray), ref)

    def test_fused_fit_value_equality(self):
        rng = np.random.default_rng(21)
        m, n = 200, 30
        X = rng.standard_normal((m, n)).astype(np.float32)
        y = (X[:, 0] - X[:, 1]).astype(np.float32)
        Xa = jnp.asarray(np.c_[np.ones(m, np.float32), X])
        yv = jnp.asarray(y)
        th0 = jnp.zeros(n + 1, jnp.float32)
        th_c = lasso_mod._cd_fit(Xa, yv, th0, 0.05, 100, 1e-6, kernel="")[0]
        th_k = lasso_mod._cd_fit(
            Xa, yv, th0, 0.05, 100, 1e-6, kernel="interpret"
        )[0]
        np.testing.assert_allclose(
            np.asarray(th_k), np.asarray(th_c), rtol=1e-4, atol=1e-5
        )


class TestKernelArmPersistence(TestCase):
    """Kernel arms ride the same versioned warm-start cache as
    ring/GSPMD entries: save/load round-trips the per-entry arm set."""

    def test_save_load_roundtrip_kernel_arms(self):
        with _Tuned():
            key = autotune.key("kernel", "qr_panel", 512, 64, "float32", True, 1)
            # decide seeds the entry with the kernel arm set; observes
            # then fill both arms to resolution
            autotune.decide(
                key, "classic", desc="qr", arms=_pallas_common.KERNEL_ARMS
            )
            for i in range(3):
                autotune.observe(key, "classic", 0.01 + i * 1e-4)
                autotune.observe(key, "kernel", 0.002 + i * 1e-4)
            self.assertEqual(autotune.winner(key), "kernel")
            with tempfile.TemporaryDirectory() as d:
                path = os.path.join(d, "tune.json")
                self.assertGreaterEqual(autotune.save(path), 1)
                autotune.reset()
                self.assertIsNone(autotune.winner(key))
                self.assertGreaterEqual(autotune.load(path), 1)
                self.assertEqual(autotune.winner(key), "kernel")
                ent = autotune._TABLE[key]
                self.assertEqual(tuple(ent["arms"]), _pallas_common.KERNEL_ARMS)

    def test_report_carries_kernel_rows(self):
        with _Tuned():
            key = autotune.key("kernel", "lasso_sweep", 200, 31, "float32", 1)
            autotune.decide(key, "classic", desc="lasso", arms=_pallas_common.KERNEL_ARMS)
            for i in range(3):
                autotune.observe(key, "classic", 0.01)
                autotune.observe(key, "kernel", 0.002)
            rows = [
                r for r in autotune.report()["rows"]
                if tuple(r.get("arms", ())) == _pallas_common.KERNEL_ARMS
            ]
            self.assertTrue(rows)
            self.assertEqual(rows[0]["winner"], "kernel")
            self.assertIn("classic_min_s", rows[0])
            self.assertIn("kernel_min_s", rows[0])


def _qr_factors(shape, seed):
    a = ht.array(
        np.random.default_rng(seed).standard_normal(shape).astype(np.float32)
    )
    q, r = ht.linalg.qr(a)
    return np.asarray(q.larray), np.asarray(r.larray)


def _lasso_theta(seed):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((200, 30)).astype(np.float32)
    y = (X[:, 0] - X[:, 1]).astype(np.float32).reshape(-1, 1)
    est = Lasso(lam=0.05, max_iter=100, tol=1e-6)
    est.fit(ht.array(X), ht.array(y))
    return (np.asarray(est.theta.larray),)


@pytest.mark.parametrize(
    "site",
    [
        lambda: _qr_factors((512, 64), 17),    # CholeskyQR2
        lambda: _qr_factors((256, 256), 23),   # blocked BCGS2
        lambda: _lasso_theta(22),
    ],
    ids=["qr_tall", "qr_blocked", "lasso"],
)
def test_pallas_off_restores_classic_and_registers_no_arm(site):
    """``HEAT_TPU_PALLAS=off`` is the one switch in front of the kernel
    tier: with the tuning plane live it leaves the classic lowering bit
    for bit, and no classic/kernel entry, decision or explore."""
    with _Interpret("off"):
        want = site()  # autotune off (suite default): pure classic
        with _Tuned():
            got = site()
            assert [r for r in _table_rows() if r[2] == ("classic", "kernel")] == []
            assert autotune.stats()["explores"] == 0
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)
    # the same call with the tier on does register the arm
    with _Interpret(), _Tuned():
        site()
        assert [r[2] for r in _table_rows()] == [("classic", "kernel")]


# written by the parent commit's `autotune.save` after four
# `ht.linalg.qr` of a (512, 64) float32 under HEAT_TPU_PALLAS=interpret
_PARENT_TABLE = {
    "version": 1,
    "library": "0.1.0-dev",
    "entries": [{
        "fingerprint": "e640005b82ef",
        "device_kind": "cpu:cpu",
        "desc": "qr 512x64 float32",
        "winner": "classic",
        "best_s": 0.013684708013897762,
        "arms": {
            "classic": [2.1315906069939956, 0.027628385985735804,
                        0.013684708013897762],
            "kernel": [0.6019594539829995, 0.0172544400265906,
                       0.015469934995053336],
        },
    }],
}


def test_table_saved_by_the_parent_serves_without_an_explore(tmp_path):
    """The five families' keys are the parent's, so a warm-start file
    from before the seam still warms: loaded, then served at the site."""
    doc = dict(_PARENT_TABLE, library=ht.__version__)
    path = tmp_path / "tune.json"
    path.write_text(json.dumps(doc))
    with _Interpret(), _Tuned():
        assert autotune.load(path) == 1
        _qr_factors((512, 64), 0)
        st = autotune.stats()
        assert (st["explores"], st["cache_hits"], st["fallbacks"]) == (0, 1, 0)


class TestMosaicLowering(TestCase):
    """The half of "will Mosaic take it?" that the CPU can answer: the
    Pallas -> Mosaic lowering for platform ``tpu`` runs here and raises
    on illegal block shapes, primitives with no lowering, and a kernel
    GSPMD would have to partition.  (The Mosaic compiler proper runs only
    on the chip: ``chip_smoke.py``.)  32-bit types, as on the chip."""

    @staticmethod
    def _lower(fn, *avals):
        with jax.enable_x64(False):
            jax.jit(fn).trace(*avals).lower(lowering_platforms=("tpu",))

    @staticmethod
    def _aval(shape, dtype=jnp.float32, sharding=None):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    def test_every_kernel_lowers_for_tpu(self):
        from heat_tpu.ops import attention, cdist

        a = self._aval
        self._lower(
            lambda X, y, t: lasso_sweep.sweep(X, y, t, 0.1),
            a((2048, 130)), a((2048,)), a((130,)),
        )
        self._lower(qr_panel.fused_gram_chol, a((4096, 100)))
        self._lower(cdist._cdist_pallas, a((512, 64)), a((256, 64)))
        for causal in (True, False):
            self._lower(
                lambda q, c=causal: attention._flash_pallas(q, q, q, c, 0.1),
                a((2, 1024, 128), jnp.bfloat16),
            )

    @unittest.skipUnless(_MULTI, "needs a multi-device mesh")
    def test_replicated_operands_on_a_mesh_run_per_device(self):
        """GSPMD cannot partition a Mosaic custom call, even over
        replicated operands: the call sites go through shard_map."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from heat_tpu.core.linalg.qr import _fact_on_each_device
        from heat_tpu.parallel.collectives import jit_shard_map_cached

        mesh = ht.parallel.get_comm().mesh
        rep = NamedSharding(mesh, P())
        a = lambda shape: self._aval(shape, sharding=rep)  # noqa: E731
        with self.assertRaisesRegex(NotImplementedError, "automatically partitioned"):
            self._lower(qr_panel.fused_gram_chol, a((4096, 100)))
        self._lower(
            jit_shard_map_cached(lasso_mod._cd_fit_on_each_device, mesh, "tpu"),
            a((2048, 130)), a((2048,)), a((130,)), 0.1, 5, 1e-6,
        )
        for tall, shape in ((True, (4096, 100)), (False, (256, 256))):
            self._lower(
                jit_shard_map_cached(
                    _fact_on_each_device, mesh, tall, True, False, "tpu"
                ),
                a(shape),
            )
