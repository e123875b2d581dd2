"""Estimator contract matrix (reference model: the per-estimator test
files under heat/cluster/tests, heat/regression/tests,
heat/classification/tests, heat/naive_bayes/tests — each proves the
sklearn-style surface: params roundtrip, unfitted errors, input
validation, fit-result invariances across splits and dtypes).
"""

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.core import telemetry
from .base import TestCase


def _blobs(n=120, f=4, k=3, seed=61):
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((k, f)).astype(np.float32) * 6
    X = np.concatenate(
        [centers[i] + rng.standard_normal((n // k, f)).astype(np.float32)
         for i in range(k)]
    )
    y = np.repeat(np.arange(k), n // k)
    perm = rng.permutation(len(X))
    return X[perm], y[perm]


class TestParamsRoundtrip(TestCase):
    ESTIMATORS = [
        lambda: ht.cluster.KMeans(n_clusters=5, max_iter=7, tol=0.5),
        lambda: ht.cluster.KMedians(n_clusters=4),
        lambda: ht.cluster.KMedoids(n_clusters=4),
        lambda: ht.cluster.Spectral(n_clusters=3),
        lambda: ht.regression.Lasso(lam=0.3, max_iter=9),
        lambda: ht.classification.KNeighborsClassifier(n_neighbors=3),
        lambda: ht.naive_bayes.GaussianNB(),
    ]

    def test_get_params_returns_constructor_args(self):
        km = ht.cluster.KMeans(n_clusters=5, max_iter=7, tol=0.5)
        p = km.get_params()
        self.assertEqual(p["n_clusters"], 5)
        self.assertEqual(p["max_iter"], 7)
        self.assertEqual(p["tol"], 0.5)

    def test_set_params_roundtrip_all(self):
        for make in self.ESTIMATORS:
            est = make()
            name = type(est).__name__
            with self.subTest(est=name):
                params = est.get_params()
                est2 = make()
                est2.set_params(**params)
                self.assertEqual(est2.get_params(), params)

    def test_set_params_unknown_raises(self):
        for make in self.ESTIMATORS[:5]:
            est = make()
            with self.subTest(est=type(est).__name__):
                with self.assertRaises(ValueError):
                    est.set_params(definitely_not_a_param=1)

    def test_set_params_returns_self(self):
        km = ht.cluster.KMeans(n_clusters=2)
        self.assertIs(km.set_params(n_clusters=3), km)
        self.assertEqual(km.n_clusters, 3)

    def test_repr_mentions_class(self):
        for make in self.ESTIMATORS[:5]:
            est = make()
            self.assertIn(type(est).__name__, repr(est))


class TestUnfittedAndValidation(TestCase):
    def test_kcluster_predict_before_fit_raises(self):
        X = ht.random.randn(20, 3, split=0)
        for est in [
            ht.cluster.KMeans(n_clusters=2),
            ht.cluster.KMedians(n_clusters=2),
            ht.cluster.KMedoids(n_clusters=2),
        ]:
            with self.subTest(est=type(est).__name__):
                with self.assertRaises((RuntimeError, AttributeError, ValueError)):
                    est.predict(X)

    def test_kmeans_more_clusters_than_samples_raises(self):
        X = ht.random.randn(3, 2, split=0)
        with self.assertRaises(ValueError):
            ht.cluster.KMeans(n_clusters=8).fit(X)

    def test_kmeans_invalid_init_raises(self):
        X = ht.random.randn(30, 2, split=0)
        with self.assertRaises((ValueError, NotImplementedError)):
            ht.cluster.KMeans(n_clusters=2, init="bogus").fit(X)

    def test_lasso_unfitted_coef_is_none(self):
        est = ht.regression.Lasso(lam=0.1)
        self.assertIsNone(getattr(est, "coef_", None))

    def test_gnb_predict_before_fit_raises(self):
        X = ht.random.randn(10, 3, split=0)
        with self.assertRaises((RuntimeError, AttributeError, ValueError)):
            ht.naive_bayes.GaussianNB().predict(X)

    def test_spectral_unsupported_metric_raises(self):
        # mirrors the reference's own NotImplementedError branch
        with self.assertRaises((NotImplementedError, ValueError)):
            ht.cluster.Spectral(n_clusters=2, metric="cityblock").fit(
                ht.random.randn(20, 3, split=0)
            )


class TestFitInvariances(TestCase):
    """Fit results must not depend on the input's split or (within
    tolerance) on bf16 vs f32 data — the GSPMD analog of the reference's
    rank-count invariance tests."""

    def test_kmeans_split_invariance(self):
        X, _ = _blobs(seed=67)
        fits = {}
        for s in (None, 0):
            km = ht.cluster.KMeans(n_clusters=3, init="kmeans++", max_iter=50,
                                   random_state=5)
            km.fit(ht.array(X, split=s))
            fits[s] = np.sort(np.round(np.asarray(km.cluster_centers_.numpy()), 3), axis=0)
        np.testing.assert_allclose(fits[None], fits[0], rtol=1e-3, atol=1e-3)

    def test_kmeans_labels_partition_data(self):
        X, _ = _blobs(seed=71)
        km = ht.cluster.KMeans(n_clusters=3, max_iter=50, random_state=1)
        km.fit(ht.array(X, split=0))
        labels = km.predict(ht.array(X, split=0)).numpy().ravel()
        self.assertEqual(labels.shape[0], X.shape[0])
        self.assertTrue(set(np.unique(labels)).issubset({0, 1, 2}))
        # inertia equals the sum of squared distances to assigned centers
        centers = km.cluster_centers_.numpy()
        d = ((X - centers[labels]) ** 2).sum()
        self.assertLess(abs(d - float(km.inertia_)) / d, 0.01)

    def test_gnb_split_invariance(self):
        X, y = _blobs(seed=73)
        preds = {}
        for s in (None, 0):
            gnb = ht.naive_bayes.GaussianNB()
            gnb.fit(ht.array(X, split=s), ht.array(y, split=s))
            preds[s] = gnb.predict(ht.array(X, split=s)).numpy().ravel()
        np.testing.assert_array_equal(preds[None], preds[0])
        self.assertGreater((preds[0] == y).mean(), 0.9)

    def test_knn_split_invariance(self):
        X, y = _blobs(seed=79)
        Xtr, ytr, Xte = X[:90], y[:90], X[90:]
        preds = {}
        for s in (None, 0):
            knn = ht.classification.KNeighborsClassifier(n_neighbors=5)
            knn.fit(ht.array(Xtr, split=s), ht.array(ytr, split=s))
            preds[s] = knn.predict(ht.array(Xte, split=s)).numpy().ravel()
        np.testing.assert_array_equal(preds[None], preds[0])

    def test_lasso_split_invariance_and_sparsity(self):
        rng = np.random.default_rng(83)
        X = rng.standard_normal((200, 20)).astype(np.float32)
        beta = np.zeros(20, np.float32)
        beta[[2, 7, 11]] = [2.0, -3.0, 1.5]
        yv = (X @ beta + 0.01 * rng.standard_normal(200)).astype(np.float32)
        coefs = {}
        for s in (None, 0):
            est = ht.regression.Lasso(lam=0.05, max_iter=200)
            est.fit(ht.array(X, split=s), ht.array(yv[:, None], split=s))
            coefs[s] = np.asarray(est.coef_.numpy()).ravel()
        np.testing.assert_allclose(coefs[None], coefs[0], rtol=1e-3, atol=1e-4)
        # support recovery: the three true coefficients dominate
        # (coef_ carries the feature weights; the intercept is separate)
        top = np.argsort(-np.abs(coefs[0]))[:3]
        self.assertEqual(set(top.tolist()), {2, 7, 11})

    def test_partial_fit_matches_batch_fit(self):
        X, y = _blobs(seed=89)
        full = ht.naive_bayes.GaussianNB()
        full.fit(ht.array(X, split=0), ht.array(y, split=0))
        inc = ht.naive_bayes.GaussianNB()
        classes = ht.array(np.arange(3))
        inc.partial_fit(ht.array(X[:40], split=0), ht.array(y[:40], split=0), classes=classes)
        inc.partial_fit(ht.array(X[40:], split=0), ht.array(y[40:], split=0))
        pf = full.predict(ht.array(X, split=0)).numpy().ravel()
        pi = inc.predict(ht.array(X, split=0)).numpy().ravel()
        self.assertGreater((pf == pi).mean(), 0.98)


class TestSpatialGraphContracts(TestCase):
    def test_cdist_metrics_and_self_distance(self):
        rng = np.random.default_rng(97)
        X = rng.standard_normal((25, 4)).astype(np.float32)
        d = ht.spatial.cdist(ht.array(X, split=0), ht.array(X)).numpy()
        from scipy.spatial.distance import cdist as sp_cdist

        np.testing.assert_allclose(d, sp_cdist(X, X), rtol=1e-3, atol=2e-3)
        np.testing.assert_allclose(np.diag(d), 0, atol=2e-3)
        np.testing.assert_allclose(d, d.T, rtol=1e-3, atol=2e-3)

    def test_laplacian_rowsums_zero(self):
        rng = np.random.default_rng(101)
        X = rng.standard_normal((20, 3)).astype(np.float32)
        lap = ht.graph.Laplacian(
            lambda a: ht.exp(-ht.spatial.cdist(a, a) ** 2),
            definition="simple", mode="fully_connected",
        )
        L = lap.construct(ht.array(X, split=0)).numpy()
        np.testing.assert_allclose(L.sum(axis=1), 0, atol=1e-3)
        # off-diagonals nonpositive, diagonal nonnegative
        off = L - np.diag(np.diag(L))
        self.assertLessEqual(off.max(), 1e-6)
        self.assertGreaterEqual(np.diag(L).min(), -1e-6)


# --------------------------------------------------- the seams labels pass through
# ``perf/tests/test_faults.py`` plants its faults by replacing
# ``_KCluster._assign_to_cluster``, ``kmeans._lloyd_loop`` and
# ``kmeans._lloyd_step``; these hold the three seams where KMeans takes its
# labels from the fused pass (ISSUE 33), with the kernel in the interpreter.

def _far_blobs(n, f, k, seed=83):
    """Row r in blob r % k, blobs far apart, values bfloat16 holds exactly."""
    rng = np.random.default_rng(seed)
    centres = np.round(8.0 * rng.normal(size=(k, f)))
    return (centres[np.arange(n) % k] + np.round(4.0 * rng.normal(size=(n, f))) / 8.0).astype(np.float32)


@pytest.fixture
def label_spans(monkeypatch):
    monkeypatch.setenv("HEAT_TPU_PALLAS", "interpret")
    prev = telemetry.set_level("events")
    telemetry.clear_events()
    yield lambda: [e["assign"] for e in telemetry.events("span_end") if e["name"] == "kmeans.labels"]
    telemetry.clear_events()
    telemetry.set_level(prev)


def test_replacing_assign_to_cluster_alters_what_a_fused_fit_stores(label_spans, monkeypatch):
    from heat_tpu.cluster import _kcluster
    from heat_tpu.core.dndarray import DNDarray

    xn, k = _far_blobs(640, 20, 4), 4
    x, init = ht.array(xn, split=0), ht.array(xn[:k], split=None)
    fit = lambda: ht.cluster.KMeans(n_clusters=k, init=init, max_iter=4).fit(x)  # noqa: E731
    clean = fit().labels_.numpy()
    assign = _kcluster._KCluster._assign_to_cluster

    def altered(self, x, *a, **kw):
        lab = assign(self, x, *a, **kw)
        arr = lab.larray.at[0, 0].set((lab.larray[0, 0] + 1) % self.n_clusters)
        return DNDarray(arr, lab.gshape, lab.dtype, lab.split, lab.device, lab.comm)

    monkeypatch.setattr(_kcluster._KCluster, "_assign_to_cluster", altered)
    est = fit()
    got = est.labels_.numpy()
    assert label_spans() == ["fused", "fused"]
    assert got[0, 0] == (clean[0, 0] + 1) % k and (got[1:] == clean[1:]).all()
    assert est.predict(x).numpy()[0, 0] == got[0, 0]
    assert "_assign_to_cluster" not in vars(ht.cluster.KMeans)  # one entry, the base class's


def test_the_lloyd_loop_and_step_keep_the_shape_faults_are_planted_in(label_spans, monkeypatch):
    import inspect

    import jax.numpy as jnp
    from heat_tpu.cluster import kmeans

    loop = inspect.signature(kmeans._lloyd_loop.__wrapped__)
    assert list(loop.parameters)[:5] == ["x", "centers", "k", "max_iter", "tol"]
    assert all(p.default is not p.empty for p in list(loop.parameters.values())[5:])
    step = inspect.signature(kmeans._lloyd_step.__wrapped__)
    assert list(step.parameters)[:3] == ["x", "centers", "k"]
    assert all(p.default is not p.empty for p in list(step.parameters.values())[3:])
    xn, k = _far_blobs(640, 20, 4), 4
    x, init = ht.array(xn, split=0), ht.array(xn[:k] + 0.5, split=None)
    fused = kmeans._fused_rows(x, k)
    assert fused is not None
    assert len(kmeans._lloyd_loop(x.parray, init.larray, k, 2, 0.0, fused=fused)) == 4
    # a loop that returns its centres unchanged: the labels are the
    # assignment to THOSE centres, made after the loop and not by it
    stuck = lambda x, centers, k, max_iter, tol, **how: (  # noqa: E731
        centers, jnp.float32(0), jnp.float32(0), jnp.int32(max_iter))
    monkeypatch.setattr(kmeans, "_lloyd_loop", stuck)
    est = ht.cluster.KMeans(n_clusters=k, init=init, max_iter=3).fit(x)
    np.testing.assert_array_equal(est.cluster_centers_.numpy(), xn[:k] + 0.5)
    np.testing.assert_array_equal(est.labels_.numpy().ravel(), np.arange(640) % k)
    assert est.n_iter_ == 3 and label_spans() == ["fused"]


@pytest.mark.parametrize("which", ["kmedians", "kmedoids", "kmeans_width_128"])
def test_what_the_kernel_does_not_take_is_assigned_lazily(which, label_spans):
    """Another metric (no hook) or a width the rule declines: the lazy
    distances and argmin, as before, though the Pallas tier is on."""
    from heat_tpu.core.fusion import LazyDNDarray

    f, k = (128, 4) if which == "kmeans_width_128" else (20, 4)
    xn = _far_blobs(320, f, k)
    x, init = ht.array(xn, split=0), ht.array(xn[:k], split=None)
    make = {"kmedians": ht.cluster.KMedians, "kmedoids": ht.cluster.KMedoids,
            "kmeans_width_128": ht.cluster.KMeans}[which]
    est = make(n_clusters=k, init=init, max_iter=5).fit(x)
    again = est.predict(x)
    assert label_spans() == ["classic", "classic"]
    assert isinstance(again, LazyDNDarray) and again.shape == (320, 1) and again.split == 0
    centres = est.cluster_centers_.numpy().astype(np.float64)
    apart = xn[:, None, :].astype(np.float64) - centres[None, :, :]
    want = (np.abs(apart).sum(-1) if which != "kmeans_width_128" else (apart ** 2).sum(-1)).argmin(1)
    np.testing.assert_array_equal(est.labels_.numpy().ravel(), want)
    np.testing.assert_array_equal(again.numpy().ravel(), want)
    np.testing.assert_array_equal(want, np.arange(320) % k)
