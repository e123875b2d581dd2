"""Out-of-core streaming engine (ISSUE 20): slab-boundary parity against
the in-memory fits, measured residency-budget proofs, injected-OOM
mid-stream retry, the no-retrace law for a streamed serving corpus, and
the thread-leak fix for abandoned iterators.

``scripts/ci.sh`` stage 23 re-runs this file at mesh sizes 1/4/8 — slab
rows are always a multiple of the mesh size, so every slab boundary
moves with the mesh and parity must hold at each.

Doctrine stays "no mocks": parity tests run the real estimators on the
real mesh against their own in-memory fits; the budget tests drive the
real planner through ``FaultInjector.low_hbm`` and read the proof off
the ``memtrack`` ledger's per-tag high-water mark."""

import os
import queue
import tempfile
import threading
import unittest

import numpy as np

import heat_tpu as ht
from heat_tpu.classification import KNeighborsClassifier
from heat_tpu.cluster import KMeans
from heat_tpu.core import autotune, memtrack, stream, telemetry
from heat_tpu.naive_bayes import GaussianNB
from heat_tpu.utils import fault

from .base import TestCase

_RNG = np.random.default_rng(2022)


def _blobs(n=600, f=8, classes=3, seed=7):
    rng = np.random.default_rng(seed)
    y = rng.integers(0, classes, size=n)
    x = rng.normal(size=(n, f)).astype(np.float32) + 2.5 * y[:, None]
    return x, y


class _Streaming:
    """Scoped events level + clean recorder/ledger/memtrack/stream
    counters on both sides (the per-tag peak proof needs the ledger on)."""

    def __enter__(self):
        self.prev = telemetry.set_level("events")
        telemetry.clear_events()
        telemetry.reset_programs()
        telemetry.reset_group("stream")
        memtrack.reset()
        return self

    def __exit__(self, *exc):
        memtrack.reset()
        telemetry.reset_group("stream")
        telemetry.clear_events()
        telemetry.set_level(self.prev)
        return False


class _RaisingSource(stream.ChunkSource):
    """Real ChunkSource whose read fails after ``ok`` slabs — drives the
    reader-thread error-propagation contract without mocking the engine."""

    def __init__(self, data, ok=1):
        self._data = data
        self.shape = data.shape
        self.np_dtype = data.dtype
        self._ok = ok
        self._reads = 0

    def read(self, lo, hi):
        self._reads += 1
        if self._reads > self._ok:
            raise IOError("disk went away")
        return self._data[lo:hi]


class TestChunkSources(TestCase):
    def test_npy_and_array_sources(self):
        data = _RNG.normal(size=(32, 4)).astype(np.float32)
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "x.npy")
            np.save(path, data)
            with stream.open_source(path) as src:
                self.assertEqual(src.shape, (32, 4))
                got = src.read(3, 9)
                np.testing.assert_array_equal(got, data[3:9])
                # memory maps are copied: the slab must outlive the handle
                self.assertNotIsInstance(got, np.memmap)
        src = stream.open_source(data, np_dtype=np.float64)
        self.assertEqual(src.read(0, 2).dtype, np.float64)
        # an already-open ChunkSource passes through, caller keeps ownership
        self.assertIs(stream.open_source(src), src)

    def test_unsupported_sources_raise(self):
        with self.assertRaises(ValueError):
            stream.open_source("corpus.parquet")
        with self.assertRaises(ValueError):
            stream.open_source("corpus.h5")  # needs a dataset name
        with self.assertRaises(TypeError):
            stream.open_source(object())

    def test_plan_slab_rows_divide_mesh_and_budget(self):
        data = np.zeros((256, 16), np.float32)
        src = stream.open_source(data)
        pl = stream.plan_pass(src, site="t", budget=64 << 10)
        n_dev = self.get_size()
        self.assertEqual(pl.slab_rows % n_dev, 0)
        # three slabs transiently live under double buffering
        self.assertLessEqual(3 * pl.slab_rows * pl.row_bytes, pl.budget)
        self.assertGreaterEqual(pl.depth, 1)


class TestSlabParity(TestCase):
    """Streamed fits equal the in-memory fits across every slab boundary.

    KMeans centroids agree to 1e-4 (documented tolerance: identical f32
    math, only the slab-wise accumulation order differs); k-NN labels are
    BITWISE equal (the squared-distance top-k merge is order-exact)."""

    def test_kmeans_fit_stream_matches_fit(self):
        x_np, _ = _blobs(n=600, f=8)
        init = ht.array(x_np[:4].copy(), split=None)
        km_mem = KMeans(n_clusters=4, init=init, max_iter=50, tol=1e-6)
        km_mem.fit(ht.array(x_np, split=0))
        km_str = KMeans(n_clusters=4, init=init, max_iter=50, tol=1e-6)
        km_str.fit_stream(x_np, budget=x_np.nbytes // 4)  # >= 4 slabs
        self.assertEqual(km_str._n_iter, km_mem._n_iter)
        np.testing.assert_allclose(
            np.asarray(km_str.cluster_centers_.larray),
            np.asarray(km_mem.cluster_centers_.larray),
            rtol=1e-4, atol=1e-5,
        )
        self.assertAlmostEqual(
            km_str._inertia, km_mem._inertia,
            delta=1e-3 * abs(km_mem._inertia),
        )
        # labels stay out-of-core by design
        self.assertIsNone(km_str._labels)
        rep = km_str.last_stream_report
        self.assertGreaterEqual(rep["slabs"], 4)
        self.assertEqual(rep["oom_retries"], 0)

    def test_kmeans_stream_random_and_plusplus_init(self):
        x_np, _ = _blobs(n=400, f=4)
        for init in ("random", "kmeans++"):
            km = KMeans(n_clusters=3, init=init, max_iter=10,
                        random_state=0)
            km.fit_stream(x_np, budget=x_np.nbytes // 4)
            self.assertEqual(km.cluster_centers_.shape, (3, 4))
            self.assertGreaterEqual(km._n_iter, 1)

    def test_gaussiannb_fit_stream_matches_fit(self):
        x_np, y_np = _blobs(n=500, f=6)
        g_mem = GaussianNB().fit(ht.array(x_np, split=0),
                                 ht.array(y_np, split=0))
        g_str = GaussianNB().fit_stream(x_np, y_np,
                                        budget=x_np.nbytes // 4)
        np.testing.assert_allclose(
            np.asarray(g_str.theta_.larray),
            np.asarray(g_mem.theta_.larray), rtol=1e-4, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(g_str.var_.larray),
            np.asarray(g_mem.var_.larray), rtol=1e-4, atol=1e-5,
        )
        np.testing.assert_allclose(
            np.asarray(g_str.class_count_.larray),
            np.asarray(g_mem.class_count_.larray),
        )
        # epsilon_ is reconstructed via the law of total variance, so it
        # matches the single in-memory call too (not the last slab's)
        self.assertAlmostEqual(
            g_str.epsilon_, g_mem.epsilon_,
            delta=1e-3 * abs(g_mem.epsilon_),
        )

    def test_knn_streamed_corpus_labels_bitwise(self):
        x_np, y_np = _blobs(n=480, f=8)
        q = ht.array(
            _RNG.normal(size=(48, 8)).astype(np.float32) + 2.0, split=0
        )
        mem = KNeighborsClassifier(n_neighbors=5)
        mem.fit(ht.array(x_np, split=0), ht.array(y_np, split=0))
        want = np.asarray(mem.predict(q).larray)
        srv = KNeighborsClassifier(n_neighbors=5)
        srv.fit_stream(x_np, y_np, budget=x_np.nbytes // 4)
        try:
            got = srv.predict(q)
            self.assert_array_equal(got, want)
            self.assertGreaterEqual(srv.last_stream_report["slabs"], 4)
        finally:
            srv.close_stream()

    def test_partial_h5_loader_rides_the_engine(self):
        try:
            import h5py
        except ImportError:
            raise unittest.SkipTest("h5py not installed")
        from heat_tpu.utils.data.partial_dataset import PartialH5Dataset

        data = _RNG.normal(size=(64, 4)).astype(np.float32)
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "d.h5")
            with h5py.File(path, "w") as f:
                f.create_dataset("data", data=data)
            ds = PartialH5Dataset(path, dataset_names=["data"],
                                  initial_load=20)
            slabs = [np.asarray(b.larray) for b in ds]
            np.testing.assert_allclose(np.concatenate(slabs), data,
                                       rtol=1e-6)


class TestResidencyBudget(TestCase):
    """The budget proof: planner seeds its slab from measured (injected)
    free HBM, and the ``staging`` tag's ledgered high-water mark stays
    under the budget for the whole pass."""

    def test_low_hbm_seeds_slab_and_peak_stays_under_budget(self):
        x_np, _ = _blobs(n=8192, f=8)  # 256 KiB: > 4x the seeded budget
        free = 96 << 10  # 96 KiB free → 48 KiB budget, far under default
        with _Streaming():
            inj = fault.FaultInjector(seed=0).low_hbm(free)
            with fault.injected(inj):
                budget = stream.residency_budget()
                self.assertEqual(budget, free // 2)
                self.assertGreaterEqual(
                    autotune.stats()["budget_seeds"], 1,
                    "a shrunk budget must be ledgered as a seed",
                )
                km = KMeans(n_clusters=4,
                            init=ht.array(x_np[:4].copy(), split=None),
                            max_iter=3, tol=0.0)
                km.fit_stream(x_np)  # budget resolved from injected stats
            rep = km.last_stream_report
            self.assertEqual(rep["budget"], free // 2)
            self.assertGreaterEqual(rep["slabs"], 4)
            peak = memtrack.summary()["peak_bytes_by_tag"].get("staging", 0)
            self.assertGreater(peak, 0, "staging slabs must be ledgered")
            self.assertLessEqual(
                peak, free // 2,
                "ledgered staging high-water mark exceeded the budget",
            )
            evs = telemetry.events("stream_slab")
            self.assertGreaterEqual(len(evs), 4)
            self.assertTrue(telemetry.events("stream_pass"))

    def test_explicit_budget_env_override(self):
        os.environ["HEAT_TPU_STREAM_BUDGET"] = str(1 << 20)
        try:
            self.assertEqual(stream.residency_budget(), 1 << 20)
        finally:
            del os.environ["HEAT_TPU_STREAM_BUDGET"]
        self.assertEqual(stream.residency_budget(7777), 7777)


class TestInjectedOOMRetry(TestCase):
    """RESOURCE_EXHAUSTED mid-stream shrinks the slab and re-chunks the
    in-flight rows instead of dying — and the answer doesn't change."""

    def test_knn_equal_through_mid_stream_oom(self):
        x_np, y_np = _blobs(n=480, f=8)
        q = ht.array(
            _RNG.normal(size=(32, 8)).astype(np.float32) + 2.0, split=0
        )
        clean = KNeighborsClassifier(n_neighbors=5)
        clean.fit_stream(x_np, y_np, budget=x_np.nbytes // 4)
        try:
            want = np.asarray(clean.predict(q).larray)
        finally:
            clean.close_stream()
        with _Streaming():
            hurt = KNeighborsClassifier(n_neighbors=5)
            hurt.fit_stream(x_np, y_np, budget=x_np.nbytes // 4)
            try:
                inj = fault.FaultInjector(seed=0).oom_in(
                    "stream.slab", times=1
                )
                with fault.injected(inj):
                    got = np.asarray(hurt.predict(q).larray)
                rep = hurt.last_stream_report
            finally:
                hurt.close_stream()
            self.assertEqual(rep["oom_retries"], 1)
            self.assertEqual(stream.stats()["slab_shrinks"], 1)
            self.assertTrue(telemetry.events("stream_oom_retry"))
            np.testing.assert_array_equal(got, want)

    def test_kmeans_close_through_mid_stream_oom(self):
        x_np, _ = _blobs(n=400, f=4)
        init = ht.array(x_np[:3].copy(), split=None)
        km_clean = KMeans(n_clusters=3, init=init, max_iter=5, tol=1e-6)
        km_clean.fit_stream(x_np, budget=x_np.nbytes // 4)
        km_hurt = KMeans(n_clusters=3, init=init, max_iter=5, tol=1e-6)
        with _Streaming():
            inj = fault.FaultInjector(seed=0).oom_in("stream.slab", times=1)
            with fault.injected(inj):
                km_hurt.fit_stream(x_np, budget=x_np.nbytes // 4)
            # the retry lands in pass 1 of several: read the counter group,
            # not the last pass's report
            self.assertEqual(stream.stats()["oom_retries"], 1)
        np.testing.assert_allclose(
            np.asarray(km_hurt.cluster_centers_.larray),
            np.asarray(km_clean.cluster_centers_.larray),
            rtol=1e-4, atol=1e-5,
        )

    def test_oom_at_floor_reraises(self):
        data = np.zeros((self.get_size() * 2, 4), np.float32)
        sp = stream.StreamPass(
            stream.open_source(data), site="floor",
            budget=3 * 4 * 4 * self.get_size(),  # slab floor: 1 row/device
        )
        self.assertEqual(sp.slab_rows, self.get_size())
        inj = fault.FaultInjector(seed=0).oom_in("stream.slab", times=8)
        with fault.injected(inj):
            with self.assertRaises(fault.InjectedOOM):
                list(sp)
        sp.close()


class TestAutotunedSlabArm(TestCase):
    """The slab fraction is an autotune arm: exploration rotates through
    the (numerically identical) sizes and observes each pass's wall."""

    def test_arms_rotate_and_observe(self):
        prev = autotune.set_enabled(True)
        autotune.reset()
        try:
            data = np.zeros((256, 8), np.float32)
            src = stream.open_source(data)
            arms = []
            for _ in range(len(stream.STREAM_ARMS)):
                sp = stream.StreamPass(src, site="arm_test",
                                       budget=16 << 10)
                for slab in sp:
                    del slab
                stream.finish_pass(sp)
                arms.append(sp.plan.arm)
            self.assertEqual(sorted(arms),
                             sorted(stream.STREAM_ARMS))
            key = sp.plan.key
            entry = autotune.table()[key]
            for arm in stream.STREAM_ARMS:
                self.assertEqual(len(entry["arms"][arm]), 1)
        finally:
            autotune.set_enabled(prev)
            autotune.reset()

    def test_tuner_off_means_full_slab(self):
        prev = autotune.set_enabled(False)
        try:
            src = stream.open_source(np.zeros((64, 8), np.float32))
            pl = stream.plan_pass(src, site="off", budget=16 << 10)
            self.assertEqual(pl.arm, "slab_full")
            self.assertIsNone(pl.key)
        finally:
            autotune.set_enabled(prev)


class TestServingNoRetrace(TestCase):
    """A streamed-corpus endpoint obeys the serving no-retrace law: after
    bucket warmup, steady traffic adds zero fusion-cache misses, zero
    step compiles, and zero new top-k-merge traces (slab shape is fixed
    by the cached plan, so every slab of every later pass lands in the
    warmed executable)."""

    def test_streamed_knn_endpoint_never_retraces(self):
        from heat_tpu import serving
        from heat_tpu.spatial import distance

        x_np, y_np = _blobs(n=256, f=8)
        model = KNeighborsClassifier(n_neighbors=3)
        model.fit_stream(x_np, y_np, budget=x_np.nbytes // 4)
        telemetry.reset_group("serving")
        prev = telemetry.set_level("events")
        eng = serving.ServingEngine()
        try:
            eng.register("knn", model, feature_dim=8, min_bucket=8,
                         max_batch=16, max_delay_s=0.001, warm=True)
            sizes = [3, 8, 1, 16, 5, 12, 7, 2] * 2
            payloads = [
                _RNG.normal(size=(s, 8)).astype(np.float32) + 2.0
                for s in sizes
            ]
            for p in payloads[:2]:  # warm live-traffic shapes too
                eng.predict("knn", p)

            fusion_before = telemetry.snapshot_group("fusion").get(
                "misses", 0)
            steps_before = eng.stats()["step_compiles"]
            cache_size = getattr(
                distance._stream_topk_merge, "_cache_size", None)
            merge_before = cache_size() if cache_size else None

            for p in payloads:
                out = np.asarray(eng.predict("knn", p))
                self.assertEqual(out.shape[0], p.shape[0])

            self.assertEqual(
                telemetry.snapshot_group("fusion").get("misses", 0),
                fusion_before,
                "streamed serving traffic must not miss the fusion cache",
            )
            self.assertEqual(eng.stats()["step_compiles"], steps_before,
                             "every bucket was compiled during warmup")
            if merge_before is not None:
                self.assertEqual(
                    cache_size(), merge_before,
                    "the slab top-k merge retraced after warmup",
                )
            evs = telemetry.events("serving_stream")
            self.assertTrue(evs, "streamed batches must flight-record "
                            "their I/O overlap")
            self.assertIn("overlap_frac", evs[-1])
        finally:
            eng.close()
            model.close_stream()
            telemetry.set_level(prev)


class TestThreadAndHandleHygiene(TestCase):
    """The satellite fix: abandoning a pass or a PartialH5 iterator
    mid-epoch leaks neither the reader thread nor the source handle."""

    @staticmethod
    def _reader_threads():
        return [
            t for t in threading.enumerate()
            if t.name == "heat-tpu-stream-reader" and t.is_alive()
        ]

    def test_abandoned_pass_joins_reader(self):
        before = len(self._reader_threads())
        data = _RNG.normal(size=(512, 8)).astype(np.float32)
        sp = stream.StreamPass(stream.open_source(data), site="leak",
                               budget=data.nbytes // 4)
        for slab in sp:
            break  # abandon mid-pass
        sp.close()
        self.assertEqual(len(self._reader_threads()), before)

    def test_abandoned_partial_h5_iter_joins_readers(self):
        try:
            import h5py
        except ImportError:
            raise unittest.SkipTest("h5py not installed")
        from heat_tpu.utils.data.partial_dataset import PartialH5Dataset

        before = len(self._reader_threads())
        data = _RNG.normal(size=(64, 4)).astype(np.float32)
        with tempfile.TemporaryDirectory() as td:
            path = os.path.join(td, "d.h5")
            with h5py.File(path, "w") as f:
                f.create_dataset("data", data=data)
                f.create_dataset("labels", data=np.arange(64))
            ds = PartialH5Dataset(path, dataset_names=["data", "labels"],
                                  initial_load=8)
            with iter(ds) as it:
                next(it)  # consume one slab tuple, then abandon
            self.assertEqual(len(self._reader_threads()), before)
            # close() is idempotent and __del__-safe
            it.close()

    def test_reader_error_propagates_and_joins(self):
        before = len(self._reader_threads())
        data = _RNG.normal(size=(64, 4)).astype(np.float32)
        src = _RaisingSource(data, ok=1)
        sp = stream.StreamPass(src, site="err", budget=16 * 4 * 4 * 3)
        with self.assertRaisesRegex(RuntimeError, "stream reader failed"):
            for slab in sp:
                del slab
        self.assertEqual(len(self._reader_threads()), before)

    def test_queue_thread_poison_pill_exits(self):
        from heat_tpu.utils.data.partial_dataset import queue_thread

        q = queue.Queue()
        hits = []
        t = threading.Thread(target=queue_thread, args=(q,), daemon=True)
        t.start()
        q.put(lambda: hits.append(1))
        q.put((hits.append, 2))
        q.put(None)  # poison pill: the satellite's shutdown path
        q.join()
        t.join(timeout=5.0)
        self.assertFalse(t.is_alive())
        self.assertEqual(hits, [1, 2])


if __name__ == "__main__":
    unittest.main()
