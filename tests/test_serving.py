"""Serving front door (ISSUE 14): bucketed dynamic batching, the
compile-once step cache, admission control, and the telemetry surface.

The headline law is **no-retrace**: after a warmup pass over an
endpoint's bucket ladder, sustained mixed-size traffic must produce
ZERO new fusion/overlap compile-cache misses and zero new serving step
compiles — every request lands in an already-compiled bucket shape.
``scripts/ci.sh`` stage 18 re-runs this file at mesh sizes 1/4/8.

Doctrine stays "no mocks": correctness tests serve the real fitted
estimators on the real mesh and compare against direct ``predict``;
the stall test wedges a real fused execution through ``FaultInjector``
and asserts the documented ``RequestRejected`` fast-fail instead of a
hang."""

import threading
import time
import unittest
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import heat_tpu as ht
from heat_tpu import serving
from heat_tpu.core import memtrack, telemetry
from heat_tpu.serving import AdmissionController, DynamicBatcher, RequestRejected
from heat_tpu.serving.batcher import Request
from heat_tpu.serving.engine import _pow2_buckets
from heat_tpu.utils import fault

from .base import TestCase

_RNG = np.random.default_rng(4114)


def _engine(**kwargs):
    telemetry.reset_group("serving")
    return serving.ServingEngine(**kwargs)


def _fitted_kmeans(f=16, clusters=4):
    X = _RNG.normal(size=(64, f)).astype(np.float32)
    km = ht.cluster.KMeans(n_clusters=clusters, init="kmeans++", max_iter=5, random_state=0)
    km.fit(ht.array(X, split=0))
    return km


class TestBucketLadder(TestCase):
    def test_pow2_ladder(self):
        self.assertEqual(_pow2_buckets(8, 32), (8, 16, 32))
        self.assertEqual(_pow2_buckets(3, 20), (4, 8, 16, 32))
        self.assertEqual(_pow2_buckets(16, 16), (16,))
        with self.assertRaises(ValueError):
            _pow2_buckets(0, 8)

    def test_bucket_for_picks_smallest_cover(self):
        eng = _engine()
        try:
            ep = eng.register(
                "e", predict=lambda x: x, feature_dim=4, min_bucket=8, max_batch=32
            )
            self.assertEqual(ep.bucket_for(1), 8)
            self.assertEqual(ep.bucket_for(8), 8)
            self.assertEqual(ep.bucket_for(9), 16)
            self.assertEqual(ep.bucket_for(32), 32)
            with self.assertRaises(ValueError):
                ep.bucket_for(33)
        finally:
            eng.close()

    def test_register_contract(self):
        eng = _engine()
        try:
            with self.assertRaisesRegex(ValueError, "exactly one"):
                eng.register("x", feature_dim=4)
            eng.register("x", predict=lambda x: x, feature_dim=4)
            with self.assertRaisesRegex(ValueError, "already registered"):
                eng.register("x", predict=lambda x: x, feature_dim=4)
            with self.assertRaises(KeyError):
                eng.submit("nope", np.zeros((1, 4), dtype=np.float32))
        finally:
            eng.close()

    def test_submit_shape_validation_and_too_large(self):
        eng = _engine()
        try:
            eng.register("x", predict=lambda x: x, feature_dim=4, max_batch=8)
            with self.assertRaisesRegex(ValueError, r"\(rows, 4\)"):
                eng.submit("x", np.zeros((2, 5), dtype=np.float32))
            with self.assertRaisesRegex(RequestRejected, "too_large"):
                eng.submit("x", np.zeros((9, 4), dtype=np.float32))
            self.assertGreaterEqual(eng.stats()["shed"]["too_large"], 1)
        finally:
            eng.close()


class TestBatcherUnit(unittest.TestCase):
    """Pure queue mechanics — no mesh, stub executor."""

    def _run(self, requests, caps, **kwargs):
        flushed = []
        done = threading.Event()

        def execute(name, reqs, cause):
            flushed.append((name, [r.rows for r in reqs], cause))
            for r in reqs:
                r.future.set_result(r.rows)
            if sum(len(f[1]) for f in flushed) >= len(requests):
                done.set()

        b = DynamicBatcher(execute)
        for r in requests:
            b.enqueue(r, caps[r.endpoint])
        done.wait(5.0)
        return b, flushed

    @staticmethod
    def _req(endpoint, rows, delay):
        now = time.perf_counter()
        return Request(endpoint=endpoint, payload=None, rows=rows, t0=now, deadline=now + delay)

    def test_full_bucket_flushes_immediately_as_max_batch(self):
        reqs = [self._req("a", 4, 10.0), self._req("a", 4, 10.0)]
        b, flushed = self._run(reqs, {"a": 8})
        try:
            self.assertEqual(flushed, [("a", [4, 4], "max_batch")])
        finally:
            b.stop()

    def test_timer_flush_ships_partial_batch(self):
        reqs = [self._req("a", 2, 0.02)]
        b, flushed = self._run(reqs, {"a": 8})
        try:
            self.assertEqual(flushed, [("a", [2], "timer")])
        finally:
            b.stop()

    def test_drain_flushes_everything_with_drain_cause(self):
        flushed = []

        def execute(name, reqs, cause):
            flushed.append(cause)
            for r in reqs:
                r.future.set_result(None)

        b = DynamicBatcher(execute)
        b.enqueue(self._req("a", 1, 60.0), 8)
        b.enqueue(self._req("b", 1, 60.0), 8)
        self.assertTrue(b.drain(timeout=5.0))
        b.stop()
        self.assertEqual(flushed, ["drain", "drain"])

    def test_requests_never_split_across_batches(self):
        # 5 + 4 rows against cap 8: the 4-row request must NOT be torn
        # to fill the first bucket
        reqs = [self._req("a", 5, 0.02), self._req("a", 4, 0.02)]
        b, flushed = self._run(reqs, {"a": 8})
        try:
            self.assertEqual(sorted(rows for _, batch, _ in flushed for rows in batch), [4, 5])
            for _, batch, _ in flushed:
                self.assertLessEqual(sum(batch), 8)
        finally:
            b.stop()


class TestAdmissionUnit(unittest.TestCase):
    """Decision layer alone — no engine, no mesh."""

    def test_queue_bound_and_release(self):
        adm = AdmissionController(max_queue_rows=4)
        adm.admit("e", 3, 0)
        with self.assertRaisesRegex(RequestRejected, "queue_full") as ctx:
            adm.admit("e", 2, 0)
        self.assertEqual(ctx.exception.reason, "queue_full")
        self.assertIsNotNone(ctx.exception.retry_after_s)
        adm.release(3)
        adm.admit("e", 4, 0)  # freed budget admits again

    def test_documented_error_message(self):
        adm = AdmissionController(max_queue_rows=1, retry_after_s=0.25)
        adm.admit("e", 1, 0)
        with self.assertRaisesRegex(
            RequestRejected, r"serving request rejected \(queue_full\).*retry after 0\.25s"
        ):
            adm.admit("e", 1, 0)

    def test_statsless_backend_never_sheds_on_memory(self):
        # CPU reports no memory stats: would_fit is None -> admit
        self.assertIsNone(memtrack.would_fit(1 << 40))
        AdmissionController(max_queue_rows=8).admit("e", 1, 1 << 40)

    def test_hbm_pressure_sheds_under_injected_starvation(self):
        inj = fault.FaultInjector().low_hbm(1024)
        with fault.injected(inj):
            self.assertIs(memtrack.would_fit(10_000, fraction=0.5), False)
            self.assertIs(memtrack.would_fit(256, fraction=0.5), True)
            adm = AdmissionController(max_queue_rows=8, memory_fraction=0.5)
            with self.assertRaisesRegex(RequestRejected, "hbm_pressure"):
                adm.admit("e", 1, 10_000)
            adm.admit("e", 1, 256)

    def test_drain_then_close_reasons(self):
        adm = AdmissionController()
        adm.begin_drain()
        with self.assertRaisesRegex(RequestRejected, "draining"):
            adm.admit("e", 1, 0)
        adm.close()
        with self.assertRaisesRegex(RequestRejected, "closed"):
            adm.admit("e", 1, 0)

    def test_low_class_sheds_first_under_queue_pressure(self):
        # low rides 0.5 of the bound by default: at 3/8 queued rows a
        # 2-row low request overflows its bound (4) while normal/high
        # still admit against the full 8
        adm = AdmissionController(max_queue_rows=8)
        adm.admit("e", 3, 0, priority="normal")
        with self.assertRaisesRegex(RequestRejected, "queue_full") as ctx:
            adm.admit("e", 2, 0, priority="low")
        self.assertIn("'low'", str(ctx.exception))
        adm.admit("e", 2, 0, priority="high")
        adm.admit("e", 2, 0)  # normal keeps the full bound

    def test_class_threshold_validation(self):
        with self.assertRaisesRegex(ValueError, r"\(0, 1\]"):
            AdmissionController(class_thresholds={"low": 0.0})
        adm = AdmissionController(
            max_queue_rows=10, class_thresholds={"batch": 0.2}
        )
        adm.admit("e", 2, 0, priority="batch")
        with self.assertRaisesRegex(RequestRejected, "queue_full"):
            adm.admit("e", 1, 0, priority="batch")
        with self.assertRaisesRegex(ValueError, "unknown SLO class"):
            adm.admit("e", 1, 0, priority="platinum")

    def test_stall_latch_via_subscription_and_recovery(self):
        det = fault.StallDetector(timeout=60.0)  # never fires on its own
        adm = AdmissionController().attach_stall_detector(det)
        det._notify("stall", quiet_s=1.0)
        self.assertTrue(adm.stalled)
        with self.assertRaisesRegex(RequestRejected, "stalled"):
            adm.admit("e", 1, 0)
        det._notify("recover")
        self.assertFalse(adm.stalled)
        adm.admit("e", 1, 0)
        adm.detach_stall_detector()
        det._notify("stall")
        self.assertFalse(adm.stalled)  # detached: no longer listening


class TestServingCorrectness(TestCase):
    """Every served endpoint returns exactly what direct predict returns
    — padding rows and batch coalescing must be invisible."""

    def _serve_and_compare(self, eng, name, model_predict, requests):
        # expected values computed FIRST, single-threaded, on the same mesh
        expected = [np.asarray(model_predict(ht.array(r, split=0)).numpy()) for r in requests]
        futures = [eng.submit(name, r) for r in requests]
        for want, fut in zip(expected, futures):
            got = np.asarray(fut.result(30))
            self.assertEqual(got.shape, want.shape)
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_kmeans_endpoint(self):
        km = _fitted_kmeans()
        eng = _engine()
        try:
            eng.register("kmeans", km, feature_dim=16, max_batch=16, warm=True)
            reqs = [_RNG.normal(size=(r, 16)).astype(np.float32) for r in (1, 3, 2, 5)]
            self._serve_and_compare(eng, "kmeans", km.predict, reqs)
        finally:
            eng.close()

    def test_lasso_endpoint(self):
        X = _RNG.normal(size=(32, 8)).astype(np.float32)
        y = (X @ _RNG.normal(size=(8, 1))).astype(np.float32)
        lasso = ht.regression.Lasso(max_iter=10)
        lasso.fit(ht.array(X, split=0), ht.array(y, split=0))
        eng = _engine()
        try:
            eng.register("lasso", lasso, feature_dim=8, max_batch=16)
            reqs = [_RNG.normal(size=(r, 8)).astype(np.float32) for r in (2, 1, 4)]
            self._serve_and_compare(eng, "lasso", lasso.predict, reqs)
        finally:
            eng.close()

    def test_gaussian_nb_endpoint(self):
        X = _RNG.normal(size=(48, 8)).astype(np.float32)
        labels = (X[:, 0] > 0).astype(np.int32)
        gnb = ht.naive_bayes.GaussianNB()
        gnb.fit(ht.array(X, split=0), ht.array(labels, split=0))
        eng = _engine()
        try:
            eng.register("gnb", gnb, feature_dim=8, max_batch=16)
            reqs = [_RNG.normal(size=(r, 8)).astype(np.float32) for r in (3, 2)]
            self._serve_and_compare(eng, "gnb", gnb.predict, reqs)
        finally:
            eng.close()

    def test_knn_endpoint(self):
        X = _RNG.normal(size=(32, 8)).astype(np.float32)
        labels = (X[:, 0] > 0).astype(np.int32)
        knn = ht.classification.KNeighborsClassifier(n_neighbors=3)
        knn.fit(ht.array(X, split=0), ht.array(labels, split=0))
        eng = _engine()
        try:
            eng.register("knn", knn, feature_dim=8, max_batch=16)
            reqs = [_RNG.normal(size=(r, 8)).astype(np.float32) for r in (2, 4)]
            self._serve_and_compare(eng, "knn", knn.predict, reqs)
        finally:
            eng.close()

    def test_nn_linear_endpoint(self):
        w = ht.array(_RNG.normal(size=(4, 8)).astype(np.float32))
        b = ht.array(_RNG.normal(size=(4,)).astype(np.float32))

        def predict(x):
            return ht.nn.functional.linear(x, w, b)

        eng = _engine()
        try:
            eng.register("linear", predict=predict, feature_dim=8, max_batch=16)
            reqs = [_RNG.normal(size=(r, 8)).astype(np.float32) for r in (1, 6)]
            self._serve_and_compare(eng, "linear", predict, reqs)
        finally:
            eng.close()

    def test_single_row_request_accepts_1d(self):
        eng = _engine()
        try:
            eng.register("id", predict=lambda x: x, feature_dim=4, max_batch=8)
            out = eng.predict("id", np.arange(4, dtype=np.float32))
            np.testing.assert_allclose(np.asarray(out).ravel(), np.arange(4.0))
        finally:
            eng.close()

    def test_endpoint_failure_resolves_futures_with_exception(self):
        def boom(x):
            raise RuntimeError("model exploded")

        eng = _engine()
        try:
            eng.register("boom", predict=boom, feature_dim=4, max_batch=8)
            fut = eng.submit("boom", np.zeros((2, 4), dtype=np.float32))
            with self.assertRaisesRegex(RuntimeError, "model exploded"):
                fut.result(10)
            # the failure freed queue budget: the engine still serves
            eng.register("ok", predict=lambda x: x, feature_dim=4, max_batch=8)
            eng.predict("ok", np.zeros((1, 4), dtype=np.float32))
        finally:
            eng.close()


class TestNoRetraceLaw(TestCase):
    """THE acceptance law: after warmup over the bucket ladder, mixed
    steady traffic adds zero fusion misses, zero overlap ring builds,
    and zero serving step compiles — on every mesh size (ci.sh stage 18
    re-runs this at HEAT_TEST_DEVICES=1/4/8)."""

    def test_steady_traffic_over_three_buckets_never_retraces(self):
        km = _fitted_kmeans(f=16)
        eng = _engine()
        try:
            ep = eng.register(
                "kmeans", km, feature_dim=16, min_bucket=8, max_batch=32,
                max_delay_s=0.002, warm=True,
            )
            self.assertEqual(len(ep.buckets), 3)  # 8, 16, 32

            sizes = [1, 3, 8, 2, 16, 5, 7, 4, 1, 12, 32, 6] * 3
            payloads = [_RNG.normal(size=(s, 16)).astype(np.float32) for s in sizes]
            # warm every shape once more via live traffic, then measure
            for p in payloads[: len(ep.buckets)]:
                eng.predict("kmeans", p)

            fusion_before = telemetry.snapshot_group("fusion").get("misses", 0)
            overlap_before = telemetry.snapshot_group("overlap").get("ring_builds", 0)
            steps_before = eng.stats()["step_compiles"]

            with ThreadPoolExecutor(max_workers=8) as pool:
                futures = list(pool.map(lambda p: eng.submit("kmeans", p), payloads))
                results = [f.result(60) for f in futures]
            for p, r in zip(payloads, results):
                self.assertEqual(np.asarray(r).shape[0], p.shape[0])

            stats = eng.stats()
            self.assertEqual(
                telemetry.snapshot_group("fusion").get("misses", 0), fusion_before,
                "steady bucketed traffic must not MISS the fusion compile cache",
            )
            self.assertEqual(
                telemetry.snapshot_group("overlap").get("ring_builds", 0), overlap_before,
                "steady bucketed traffic must not rebuild overlap programs",
            )
            self.assertEqual(stats["step_compiles"], steps_before,
                             "every bucket was compiled during warmup")
            self.assertGreaterEqual(stats["batches"], 1)
            self.assertGreaterEqual(stats["padded_rows"], 1)
            self.assertEqual(stats["batched"], stats["accepted"])
        finally:
            eng.close()


class TestStallShedding(TestCase):
    """A wedged mesh must FAIL requests fast with the documented error,
    not hang them — driven by a real injected stall in fused exec."""

    def test_injected_stall_sheds_then_recovers(self):
        eng = _engine(admission=AdmissionController(retry_after_s=0.05))
        det = fault.StallDetector(timeout=0.08)
        eng.attach_stall_detector(det)
        det.start()
        stalled = threading.Event()
        det.subscribe(lambda kind, info: stalled.set() if kind == "stall" else None)
        inj = _HeldStall("fusion.exec")
        # quiet by right: the warm-up compile and the hand-over to the
        # worker (a stall fired there would leave `stalled` set before
        # the mesh is wedged, or shed the request that is to wedge it)
        det.pause()
        try:
            eng.register(
                "exp", predict=lambda x: ht.exp(x), feature_dim=8,
                min_bucket=8, max_batch=8, warm=True,
            )
            with fault.injected(inj):
                wedged = eng.submit("exp", np.ones((2, 8), dtype=np.float32))
                self.assertTrue(inj.entered.wait(30.0), "stall never injected")
                det.resume()  # the clock starts with the mesh already wedged
                self.assertTrue(stalled.wait(5.0), "stall never detected")
                with self.assertRaisesRegex(
                    RequestRejected, r"serving request rejected \(stalled\)"
                ) as ctx:
                    eng.submit("exp", np.ones((1, 8), dtype=np.float32))
                self.assertEqual(ctx.exception.reason, "stalled")
                self.assertIsNotNone(ctx.exception.retry_after_s)
                inj.release.set()
                # the wedged request itself completes — shed, not lost
                out = wedged.result(30)
                self.assertEqual(np.asarray(out).shape[0], 2)
            self.assertGreaterEqual(eng.stats()["shed"]["stalled"], 1)
            # the completed batch beat the detector: admission re-admits
            deadline = time.monotonic() + 5.0
            while time.monotonic() < deadline:
                if not eng.admission.stalled:
                    break
                time.sleep(0.01)
            out = eng.predict("exp", np.ones((1, 8), dtype=np.float32), timeout=30)
            self.assertEqual(np.asarray(out).shape[0], 1)
        finally:
            inj.release.set()
            det.stop()
            eng.close()


class _HeldStall(fault.FaultInjector):
    """One stall at ``site`` that lasts until the test releases it (a
    wedged collective does not end on a timer either), so it outlasts
    its own detection by construction, however loaded the machine is."""

    def __init__(self, site):
        super().__init__()
        self.site = site
        self.entered = threading.Event()
        self.release = threading.Event()

    def fire_site(self, site):
        if site == self.site and not self.entered.is_set():
            self.fired.append(("stall", site))
            self.entered.set()
            self.release.wait(60.0)


class TestSLOAndDeadlines(TestCase):
    """ISSUE 18: per-request SLO classes and client deadlines on the
    single-engine path — low sheds first, lapsed deadlines are dropped
    at flush (``expired``) instead of computing dead work."""

    def test_engine_counts_accepted_and_shed_per_class(self):
        eng = _engine(admission=AdmissionController(max_queue_rows=8))
        try:
            eng.register(
                "id", predict=lambda x: x, feature_dim=4, max_batch=8,
                max_delay_s=30.0, warm=True,  # hold the queue open
            )
            eng.submit("id", np.ones((3, 4), dtype=np.float32), priority="high")
            with self.assertRaisesRegex(RequestRejected, "queue_full"):
                eng.submit("id", np.ones((2, 4), dtype=np.float32), priority="low")
            stats = eng.stats()
            self.assertEqual(stats["accepted_by_class"]["high"], 1)
            self.assertEqual(stats["shed_by_class"]["low"], 1)
        finally:
            eng.close()

    def test_lapsed_client_deadline_dropped_at_flush_as_expired(self):
        eng = _engine()
        try:
            eng.register(
                "id", predict=lambda x: x, feature_dim=4, min_bucket=8,
                max_batch=8, max_delay_s=0.25, warm=True,
            )
            # deadline (0.05s) lapses before the flush timer (0.25s):
            # the request must resolve `expired`, not compute
            doomed = eng.submit(
                "id", np.ones((1, 4), dtype=np.float32),
                priority="low", deadline_s=0.05,
            )
            with self.assertRaisesRegex(
                RequestRejected, r"serving request rejected \(expired\)"
            ) as ctx:
                doomed.result(10)
            self.assertEqual(ctx.exception.reason, "expired")
            stats = eng.stats()
            self.assertGreaterEqual(stats["shed"]["expired"], 1)
            self.assertGreaterEqual(stats["shed_by_class"]["low"], 1)
            # the expired rows freed queue budget: the engine still serves
            out = eng.predict("id", np.ones((2, 4), dtype=np.float32))
            self.assertEqual(np.asarray(out).shape[0], 2)
        finally:
            eng.close()

    def test_deadline_validation(self):
        eng = _engine()
        try:
            eng.register("id", predict=lambda x: x, feature_dim=4, max_batch=8)
            with self.assertRaisesRegex(ValueError, "deadline_s"):
                eng.submit(
                    "id", np.ones((1, 4), dtype=np.float32), deadline_s=0.0
                )
        finally:
            eng.close()


class TestErrorPathLiveness(TestCase):
    """Satellite of ISSUE 18: a failing step is liveness, not a stall.
    Before the fix, `_execute`'s exception path never beat the detector,
    so a burst of consecutive injected step errors latched `stalled` and
    shed all traffic from a live worker."""

    def test_error_burst_never_latches_stall(self):
        eng = _engine(admission=AdmissionController(retry_after_s=0.02))
        det = fault.StallDetector(timeout=0.12)
        eng.attach_stall_detector(det)
        det.start()
        try:
            eng.register(
                "id", predict=lambda x: x, feature_dim=4, min_bucket=8,
                max_batch=8, max_delay_s=0.001, warm=True,
            )
            det.beat()
            # every batch for ~4x the stall timeout fails via a real
            # injected fault at the serving.step site
            inj = fault.FaultInjector().error_in("serving.step", times=64)
            with fault.injected(inj):
                deadline = time.monotonic() + 0.5
                while time.monotonic() < deadline:
                    fut = eng.submit("id", np.ones((1, 4), dtype=np.float32))
                    with self.assertRaisesRegex(
                        fault.FaultInjector.InjectedFault, "injected failure"
                    ):
                        fut.result(10)
                    self.assertFalse(
                        eng.admission.stalled,
                        "error burst latched `stalled` on a live worker",
                    )
                    time.sleep(0.03)
            self.assertEqual(eng.stats()["shed"]["stalled"], 0)
            self.assertGreaterEqual(eng.stats()["step_errors"], 3)
            # the worker was never wedged: the next clean batch serves
            out = eng.predict("id", np.ones((2, 4), dtype=np.float32))
            self.assertEqual(np.asarray(out).shape[0], 2)
        finally:
            det.stop()
            eng.close()


class TestWeightSwap(TestCase):
    """ISSUE 18: `swap_weights` exchanges operands under traffic with
    zero step compiles — and refuses shape/dtype/split changes (those
    are retraces, not swaps)."""

    class _Linear:
        def __init__(self, w):
            self.w = ht.array(w, split=None)

        def predict(self, x):
            return x @ self.w

    def test_swap_serves_new_weights_with_zero_step_compiles(self):
        w_old = _RNG.normal(size=(8, 4)).astype(np.float32)
        w_new = _RNG.normal(size=(8, 4)).astype(np.float32)
        model = self._Linear(w_old)
        eng = _engine()
        try:
            eng.register(
                "lin", model, feature_dim=8, min_bucket=8, max_batch=8, warm=True
            )
            x = _RNG.normal(size=(2, 8)).astype(np.float32)
            np.testing.assert_allclose(
                np.asarray(eng.predict("lin", x)), x @ w_old, rtol=1e-4, atol=1e-4
            )
            steps_before = eng.stats()["step_compiles"]
            fusion_before = telemetry.snapshot_group("fusion").get("misses", 0)
            old = eng.swap_weights("lin", {"w": ht.array(w_new, split=None)})
            np.testing.assert_allclose(
                np.asarray(eng.predict("lin", x)), x @ w_new, rtol=1e-4, atol=1e-4
            )
            self.assertEqual(
                eng.stats()["step_compiles"], steps_before,
                "a weight swap is new operands, not a retrace",
            )
            self.assertEqual(
                telemetry.snapshot_group("fusion").get("misses", 0), fusion_before
            )
            self.assertGreaterEqual(eng.stats()["swaps"], 1)
            # the returned old operands roll back
            eng.swap_weights("lin", old)
            np.testing.assert_allclose(
                np.asarray(eng.predict("lin", x)), x @ w_old, rtol=1e-4, atol=1e-4
            )
        finally:
            eng.close()

    def test_swap_refuses_retrace_shapes_and_bare_predict(self):
        model = self._Linear(_RNG.normal(size=(8, 4)).astype(np.float32))
        eng = _engine()
        try:
            eng.register("lin", model, feature_dim=8, max_batch=8)
            eng.register("bare", predict=lambda x: x, feature_dim=8, max_batch=8)
            with self.assertRaisesRegex(ValueError, "shape.*retrace"):
                eng.swap_weights(
                    "lin", {"w": ht.array(np.zeros((8, 5), dtype=np.float32))}
                )
            with self.assertRaisesRegex(ValueError, "dtype"):
                eng.swap_weights(
                    "lin", {"w": ht.array(np.zeros((8, 4), dtype=np.int32))}
                )
            with self.assertRaisesRegex(ValueError, "no operand"):
                eng.swap_weights("lin", {"nope": np.zeros((8, 4))})
            with self.assertRaisesRegex(ValueError, "model="):
                eng.swap_weights("bare", {"w": np.zeros((8, 4))})
        finally:
            eng.close()


class TestDrainAndClose(TestCase):
    def test_close_drains_queued_work(self):
        eng = _engine()
        eng.register(
            "id", predict=lambda x: x, feature_dim=4, max_batch=32,
            max_delay_s=30.0, warm=True,  # timer will never fire
        )
        futures = [eng.submit("id", np.ones((2, 4), dtype=np.float32)) for _ in range(3)]
        eng.close(drain=True)
        for fut in futures:
            self.assertEqual(np.asarray(fut.result(10)).shape[0], 2)
        stats = eng.stats()
        self.assertGreaterEqual(stats["flush_cause"]["drain"], 1)
        self.assertGreaterEqual(stats["drains"], 1)
        with self.assertRaisesRegex(RequestRejected, "closed"):
            eng.submit("id", np.ones((1, 4), dtype=np.float32))
        eng.close()  # idempotent

    def test_close_without_drain_fails_pending_with_closed(self):
        eng = _engine()
        eng.register(
            "id", predict=lambda x: x, feature_dim=4, max_batch=32,
            max_delay_s=30.0, warm=True,
        )
        fut = eng.submit("id", np.ones((1, 4), dtype=np.float32))
        eng.close(drain=False)
        try:
            fut.result(10)
        except RequestRejected as exc:
            self.assertEqual(exc.reason, "closed")
        # drained-before-pop races are fine: either outcome resolved the
        # future, which is the actual contract (never a hang)


class TestTelemetrySurface(TestCase):
    def test_latency_histograms_reach_prometheus(self):
        eng = _engine()
        try:
            eng.register("id", predict=lambda x: x, feature_dim=4, max_batch=8, warm=True)
            for _ in range(4):
                eng.predict("id", np.ones((2, 4), dtype=np.float32))
            lat = eng.stats()["latency"]["id"]
            self.assertEqual(lat["count"], 4)
            self.assertGreater(lat["p50_s"], 0.0)
            self.assertLessEqual(lat["p50_s"], lat["p99_s"])
            prom = telemetry.export_prometheus()
            self.assertIn("heat_tpu_serving_latency_id_p50_s", prom)
            self.assertIn("heat_tpu_serving_latency_id_p99_s", prom)
            self.assertIn("heat_tpu_serving_accepted", prom)
            report = telemetry.serving_report()
            self.assertEqual(report["accepted"], eng.stats()["accepted"])
        finally:
            eng.close()

    def test_shed_and_drain_reach_flight_recorder(self):
        with telemetry.telemetry_level("events"):
            telemetry.clear_events()
            eng = _engine()
            eng.register("id", predict=lambda x: x, feature_dim=4, max_batch=8)
            with self.assertRaises(RequestRejected):
                eng.submit("id", np.ones((9, 4), dtype=np.float32))  # too_large
            eng.close()
            kinds = [e["kind"] for e in telemetry.events()]
            self.assertIn("serving_endpoint", kinds)
            self.assertIn("serving_shed", kinds)
            self.assertIn("serving_drain", kinds)


class TestQuantizedKnnServing(TestCase):
    """ISSUE 15 workload: a k-NN endpoint registered with
    ``quantize=True`` serves batched queries against the int8 corpus —
    correct labels, released f32 master, and the same no-retrace law as
    every other endpoint (steady bucketed traffic adds zero fusion
    misses, zero ring builds, zero step compiles)."""

    def _fitted_knn(self, n=64, f=16):
        X = _RNG.normal(size=(n, f)).astype(np.float32)
        labels = (X[:, 0] > 0).astype(np.int32)
        knn = ht.classification.KNeighborsClassifier(n_neighbors=3)
        knn.fit(ht.array(X, split=0), ht.array(labels, split=0))
        return knn

    def test_register_quantize_requires_hook(self):
        eng = _engine()
        try:
            with self.assertRaisesRegex(ValueError, "quantize_"):
                eng.register(
                    "q", predict=lambda x: x, feature_dim=4, quantize=True
                )
        finally:
            eng.close()

    def test_quantized_endpoint_serves_and_never_retraces(self):
        knn = self._fitted_knn()
        eng = _engine()
        try:
            ep = eng.register(
                "knn_q", knn, feature_dim=16, min_bucket=8, max_batch=32,
                max_delay_s=0.002, warm=True, quantize=True,
            )
            self.assertIsNone(knn.x)  # master released at registration
            self.assertIsNotNone(knn._qx)

            sizes = [1, 3, 8, 2, 16, 5, 7, 4, 1, 12, 32, 6] * 2
            payloads = [
                _RNG.normal(size=(s, 16)).astype(np.float32) for s in sizes
            ]
            for p in payloads[: len(ep.buckets)]:
                eng.predict("knn_q", p)

            fusion_before = telemetry.snapshot_group("fusion").get("misses", 0)
            overlap_before = telemetry.snapshot_group("overlap").get(
                "ring_builds", 0
            )
            steps_before = eng.stats()["step_compiles"]

            with ThreadPoolExecutor(max_workers=8) as pool:
                futures = list(
                    pool.map(lambda p: eng.submit("knn_q", p), payloads)
                )
                results = [f.result(60) for f in futures]
            for p, r in zip(payloads, results):
                self.assertEqual(np.asarray(r).shape[0], p.shape[0])

            self.assertEqual(
                telemetry.snapshot_group("fusion").get("misses", 0),
                fusion_before,
                "steady traffic on the quantized corpus must not miss "
                "the fusion compile cache",
            )
            self.assertEqual(
                telemetry.snapshot_group("overlap").get("ring_builds", 0),
                overlap_before,
                "the quantized ring cdist must reuse its shard program",
            )
            self.assertEqual(eng.stats()["step_compiles"], steps_before)
        finally:
            eng.close()

    def test_quantized_endpoint_labels_agree_with_f32(self):
        knn = self._fitted_knn(n=48, f=8)
        q = _RNG.normal(size=(8, 8)).astype(np.float32)
        ref = np.asarray(knn.predict(ht.array(q, split=0)).numpy())
        eng = _engine()
        try:
            eng.register(
                "knn_q", knn, feature_dim=8, max_batch=16, quantize=True
            )
            got = np.asarray(eng.predict("knn_q", q)).ravel()
            # int8 corpus can flip exact distance ties; near-total
            # agreement is the contract (test_quantize pins the bound)
            self.assertGreaterEqual(float((ref.ravel() == got).mean()), 0.9)
        finally:
            eng.close()


if __name__ == "__main__":
    unittest.main()
