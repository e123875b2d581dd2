"""Sparse compute tier (ISSUE 19): SpMV/SpMM behind the autotune
plane, sparse Lanczos end-to-end, k-NN-graph serving.

Laws under test, at every mesh size (``scripts/ci.sh`` stage 22 re-runs
this file at ``HEAT_TEST_DEVICES=1/4/8``):

- **bit-parity**: on exactly-representable data the ``gather`` arm
  reproduces the ``todense()`` reference matmul bit-for-bit — including
  a ragged last shard and a shard of all-zero rows;
- **explore returns dense**: the first tuned call runs every arm but
  always answers with the dense reference result, bitwise;
- **static dispatch**: ``HEAT_TPU_AUTOTUNE=off`` restores today's
  env-knob dispatch bit-for-bit with ZERO tuning-table decisions;
- **warm start**: spmv arm entries survive a ``save``/``load``
  round-trip and are consumed by the Lanczos chain consult;
- **sparse Lanczos**: the recurrence over the tuned SpMV program agrees
  with the dense-operand recurrence (same v0) — eigenvector parity;
- **serving**: the k-NN-graph workload (graph → Laplacian → embedding
  per request) obeys the no-retrace law under mixed concurrent traffic.
"""

import os
import tempfile
import unittest
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu import serving
from heat_tpu.core import autotune, telemetry, types
from heat_tpu.core.dndarray import DNDarray
from heat_tpu.core.linalg import solver
from heat_tpu.graph import laplacian_sparse
from heat_tpu.sparse import knn_graph
# NOTE: `import heat_tpu.sparse.matmul as spmm` would bind the matmul
# FUNCTION (the package re-export shadows the module attribute); the
# from-import form resolves through sys.modules
from heat_tpu.sparse.matmul import matvec_program
import heat_tpu.sparse.manipulations as sp_manip

from .base import TestCase, scripted_clock

_RNG = np.random.default_rng(1900)
_MULTI = len(jax.local_devices()) > 1


class _Tuned:
    """Scoped tuning plane (the test_kernels idiom): enabled via API,
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


class _Env:
    """Scoped environment variable (restores the prior value)."""

    def __init__(self, name, value):
        self.name, self.value = name, value

    def __enter__(self):
        self.prev = os.environ.get(self.name)
        if self.value is None:
            os.environ.pop(self.name, None)
        else:
            os.environ[self.name] = self.value
        return self

    def __exit__(self, *exc):
        if self.prev is None:
            os.environ.pop(self.name, None)
        else:
            os.environ[self.name] = self.prev
        return False


def _spmv_rows():
    """Tuning-table rows carrying the spmv arm sets."""
    return [
        (k[0], e.get("winner"), tuple(e["arms"]),
         {a: len(s) for a, s in e["arms"].items()})
        for k, e in autotune._TABLE.items()
        if set(e["arms"]) >= {"dense", "gather"}
    ]


def _int_csr(n, m, density=0.08, seed=0, zero_rows=()):
    """Random CSR with small-integer f32 values: every product and sum
    in an SpMV is exactly representable, so arm parity is BITWISE."""
    rng = np.random.default_rng(seed)
    mat = scipy.sparse.random(
        n, m, density=density, random_state=rng, format="csr", dtype=np.float32
    )
    mat.data = (np.abs(mat.data * 900).astype(np.int64) % 7 + 1).astype(np.float32)
    if zero_rows:
        lil = mat.tolil()
        for r in zero_rows:
            lil.rows[r] = []
            lil.data[r] = []
        mat = lil.tocsr()
    return mat


def _int_vec(m, k=None, seed=1):
    rng = np.random.default_rng(seed)
    shape = (m,) if k is None else (m, k)
    return rng.integers(-4, 5, size=shape).astype(np.float32)


class TestArmBitParity(TestCase):
    """gather vs the todense() reference — bitwise
    on exact data, including ragged last shard + all-zero-rows shard."""

    # 37 rows: ragged last shard on any mesh size; the trailing rows
    # zeroed so the LAST shard is all-zero on the 8-way mesh too
    _CASES = [
        dict(n=37, m=52, seed=5, zero_rows=tuple(range(33, 37))),
        dict(n=64, m=64, seed=6, zero_rows=(0, 1, 31)),
        dict(n=16, m=200, seed=7, zero_rows=()),
    ]

    def _check(self, arm, split):
        for case in self._CASES:
            sp = _int_csr(case["n"], case["m"], seed=case["seed"],
                          zero_rows=case["zero_rows"])
            A = ht.sparse.sparse_csr_matrix(sp, split=split)
            for k in (None, 3):
                x = _int_vec(case["m"], k, seed=case["seed"] + 10)
                with _Env("HEAT_TPU_SPMV", "dense"):
                    ref = ht.sparse.matmul(A, x)  # the authoritative arm
                with _Env("HEAT_TPU_SPMV", arm):
                    got = A @ ht.array(x)
                self.assertEqual(got.split, 0 if split == 0 else None)
                np.testing.assert_array_equal(got.numpy(), ref.numpy())
                np.testing.assert_array_equal(ref.numpy(), sp @ x)

    def test_gather_bitwise_split0(self):
        self._check("gather", 0)

    def test_gather_bitwise_replicated(self):
        self._check("gather", None)

    def test_matmul_validates(self):
        A = ht.sparse.sparse_csr_matrix(_int_csr(8, 8, seed=8), split=0)
        with self.assertRaisesRegex(ValueError, "dimension mismatch"):
            ht.sparse.matmul(A, np.ones(9, np.float32))
        with self.assertRaisesRegex(ValueError, "1-D or 2-D"):
            ht.sparse.matmul(A, np.ones((8, 1, 1), np.float32))
        with self.assertRaisesRegex(TypeError, "DCSR_matrix"):
            ht.sparse.matmul(np.eye(3), np.ones(3))

    def test_out_and_dtype_promotion(self):
        sp = _int_csr(12, 10, seed=9)
        A = ht.sparse.sparse_csr_matrix(sp, split=0)
        x = ht.array(_int_vec(10, seed=12).astype(np.int32))
        y = ht.sparse.matmul(A, x)  # int rhs promotes to f32
        self.assertEqual(np.asarray(y.larray).dtype, np.float32)
        out = ht.zeros(12, split=0)
        y2 = ht.sparse.matmul(A, x, out=out)
        self.assertIs(y2, out)
        np.testing.assert_array_equal(out.numpy(), y.numpy())


class TestStaticDispatch(TestCase):
    """HEAT_TPU_AUTOTUNE=off is today's dispatch bit-for-bit: zero table
    decisions, zero table entries; the env knob rules."""

    def test_off_is_bitwise_with_zero_decisions(self):
        sp = _int_csr(37, 40, seed=13, zero_rows=(36,))
        A = ht.sparse.sparse_csr_matrix(sp, split=0)
        x = _int_vec(40, 2, seed=14)
        autotune.reset()
        before = autotune.stats()["decisions"]
        y1 = (A @ ht.array(x)).numpy()
        y2 = (A @ ht.array(x)).numpy()
        np.testing.assert_array_equal(y1, y2)
        np.testing.assert_array_equal(y1, sp @ x)
        self.assertEqual(autotune.stats()["decisions"], before)
        self.assertEqual(autotune.table_size(), 0)

    def test_env_knob_malformed_raises(self):
        A = ht.sparse.sparse_csr_matrix(_int_csr(8, 8, seed=15), split=0)
        with _Env("HEAT_TPU_SPMV", "fast"):
            with self.assertRaisesRegex(ValueError, "HEAT_TPU_SPMV"):
                ht.sparse.matmul(A, np.ones(8, np.float32))

class TestSpmvArms(TestCase):
    """The tuned two-arm consult: explore-then-sticky, the round-15
    explore contract, and the save/load warm start."""

    def _problem(self, seed=20):
        sp = _int_csr(40, 40, density=0.12, seed=seed)
        A = ht.sparse.sparse_csr_matrix(sp, split=0)
        return A, sp, _int_vec(40, seed=seed + 1)

    def test_explore_returns_dense_bitwise(self):
        A, sp, x = self._problem()
        with _Env("HEAT_TPU_SPMV", "dense"):
            ref = ht.sparse.matmul(A, x).numpy()  # autotune off: pure dense
        with _Tuned():
            got = ht.sparse.matmul(A, x).numpy()  # first call: explore round
        np.testing.assert_array_equal(got, ref)

    def test_explore_then_sticky_two_arms(self):
        A, sp, x = self._problem(seed=22)
        with _Tuned(), scripted_clock(dense=0.002, gather=0.001):
            for _ in range(7):
                y = ht.sparse.matmul(A, x)
            rows = _spmv_rows()
            self.assertTrue(rows)
            self.assertEqual(rows[0][2], ("dense", "gather"))
            self.assertEqual(rows[0][3], {"dense": 3, "gather": 3})
            self.assertEqual(rows[0][1], "gather")
            np.testing.assert_array_equal(y.numpy(), sp @ x)
            # each arm owns a cost-ledger row
            kinds = {p["kind"] for p in telemetry.programs()}
            self.assertLessEqual({"spmv_dense", "spmv_gather"}, kinds)

    def test_save_load_roundtrip_of_spmv_entries(self):
        A, sp, x = self._problem(seed=24)
        with _Tuned(), scripted_clock(dense=0.002, gather=0.001):
            for _ in range(7):
                ht.sparse.matmul(A, x)
            table = {k: e for k, e in autotune.table().items()
                     if set(e["arms"]) == {"dense", "gather"}}
            self.assertTrue(table)
            (key, entry), = table.items()
            self.assertIsNotNone(entry["winner"])
            with tempfile.TemporaryDirectory() as tmp:
                path = os.path.join(tmp, "tuning.json")
                self.assertGreaterEqual(autotune.save(path), 1)
                autotune.reset()
                self.assertEqual(autotune.winner(key), None)
                self.assertGreaterEqual(autotune.load(path), 1)
            loaded = autotune.table()[key]
            self.assertEqual(loaded["winner"], entry["winner"])
            self.assertTrue(loaded["loaded"])
            self.assertEqual(
                {a: len(d) for a, d in loaded["arms"].items()},
                {a: len(d) for a, d in entry["arms"].items()},
            )
            # the warmed winner serves without a single new explore
            explores = autotune.stats()["explores"]
            y = ht.sparse.matmul(A, x)
            np.testing.assert_array_equal(y.numpy(), sp @ x)
            self.assertEqual(autotune.stats()["explores"], explores)


class TestSparseLanczos(TestCase):
    """The fused recurrence over the tuned SpMV program vs the dense
    operand — same v0, eigenvector parity, zero densifications."""

    def _laplacian(self, n=48, seed=26):
        rng = np.random.default_rng(seed)
        pts = np.concatenate([
            rng.normal(0.0, 0.25, size=(n // 2, 4)),
            rng.normal(3.0, 0.25, size=(n - n // 2, 4)),
        ]).astype(np.float32)
        G = knn_graph(ht.array(pts, split=0), 6, weights="rbf", sigma=1.0)
        return laplacian_sparse(G, definition="norm_sym")

    def test_sparse_vs_dense_eigenvector_parity(self):
        L = self._laplacian()
        n = L.shape[0]
        m = 12
        raw = jnp.sin(jnp.arange(1, n + 1, dtype=jnp.float32))
        v0 = DNDarray(raw, (n,), types.float32, None, L.device, L.comm)
        Ld = sp_manip.todense(L)
        telemetry_level = telemetry.set_level("events")
        try:
            telemetry.clear_events()
            Vs, Ts = solver.lanczos(L, m, v0=v0)
            # the sparse solve NEVER densified the operand
            self.assertEqual(len(telemetry.events(kind="sparse_densify")), 0)
        finally:
            telemetry.set_level(telemetry_level)
        Vd, Td = solver.lanczos(Ld, m, v0=v0)
        np.testing.assert_allclose(
            np.asarray(Ts.larray), np.asarray(Td.larray), atol=1e-4
        )
        es, Us = np.linalg.eigh(np.asarray(Ts.larray))
        ed, Ud = np.linalg.eigh(np.asarray(Td.larray))
        np.testing.assert_allclose(es, ed, atol=1e-4)
        # eigenVECTOR parity as principal angles of the leading Ritz
        # subspace (per-vector signs/degeneracies are not identifiable)
        Qs = np.asarray(Vs.larray) @ Us[:, :2]
        Qd = np.asarray(Vd.larray) @ Ud[:, :2]
        Qs, _ = np.linalg.qr(Qs)
        Qd, _ = np.linalg.qr(Qd)
        sv = np.linalg.svd(Qs.T @ Qd, compute_uv=False)
        self.assertGreater(float(sv.min()), 0.999)

    def test_chain_consult_consumes_the_winner(self):
        sp = _int_csr(32, 32, density=0.15, seed=28)
        sym = sp.maximum(sp.T).tocsr()
        A = ht.sparse.sparse_csr_matrix(sym, split=0)
        x = _int_vec(32, seed=29)
        with _Tuned(), scripted_clock(dense=0.002, gather=0.001):
            for _ in range(7):
                ht.sparse.matmul(A, x)  # resolve the (k=1) winner
            rows = _spmv_rows()
            self.assertIsNotNone(rows[0][1])
            hits = autotune.stats()["cache_hits"]
            fn, operands = matvec_program(A)
            y = fn(operands, jnp.asarray(x))
            np.testing.assert_array_equal(np.asarray(y), sym @ x)
            # a resolved gather winner is a served chain decision
            self.assertEqual(rows[0][1], "gather")
            self.assertGreater(autotune.stats()["cache_hits"], hits)


class TestServingKnnGraph(TestCase):
    """The k-NN-graph workload behind the serving front door: graph →
    sparse Laplacian → Lanczos embedding per request, and STILL the
    no-retrace law — zero fusion misses, zero step compiles, zero
    densifications under mixed concurrent traffic."""

    def test_no_retrace_under_mixed_concurrent_requests(self):
        rng = np.random.default_rng(30)
        n, f = 64, 8
        X = np.concatenate([
            rng.normal(0.0, 0.3, size=(n // 2, f)),
            rng.normal(3.0, 0.3, size=(n - n // 2, f)),
        ]).astype(np.float32)
        spec = ht.cluster.Spectral(
            n_clusters=2, gamma=1.0, affinity="knn", n_neighbors=6, n_lanczos=12
        )
        spec.fit(ht.array(X, split=0))
        self.assertEqual(int(spec.labels_.shape[0]), n)

        telemetry.reset_group("serving")
        prev_level = telemetry.set_level("events")
        eng = serving.ServingEngine()
        try:
            ep = eng.register(
                "knn_embed", spec, feature_dim=f, min_bucket=16,
                max_batch=64, max_delay_s=0.002, warm=True,
            )
            self.assertEqual(ep.buckets, (16, 32, 64))
            sizes = [1, 5, 16, 9, 33, 64, 3, 17, 2] * 2
            payloads = [
                rng.normal(1.5, 1.5, size=(s, f)).astype(np.float32)
                for s in sizes
            ]
            for p in payloads[: len(ep.buckets)]:
                eng.predict("knn_embed", p, timeout=120)

            telemetry.clear_events()
            fusion_before = telemetry.snapshot_group("fusion").get("misses", 0)
            steps_before = eng.stats()["step_compiles"]

            with ThreadPoolExecutor(max_workers=8) as pool:
                futures = list(
                    pool.map(lambda p: eng.submit("knn_embed", p), payloads)
                )
                results = [fut.result(120) for fut in futures]
            for p, r in zip(payloads, results):
                self.assertEqual(np.asarray(r).shape[0], p.shape[0])

            self.assertEqual(
                telemetry.snapshot_group("fusion").get("misses", 0),
                fusion_before,
                "sparse serving traffic must not MISS the fusion cache",
            )
            self.assertEqual(
                eng.stats()["step_compiles"], steps_before,
                "every bucket was compiled during warmup",
            )
            # the graph pipeline ran per request ... sparsely
            self.assertGreaterEqual(len(telemetry.events(kind="knn_graph")), 1)
            self.assertEqual(len(telemetry.events(kind="sparse_densify")), 0)
        finally:
            eng.close()
            telemetry.set_level(prev_level)


def tearDownModule():
    # This module compiles many one-off executables (three spmv arms x
    # several geometries x three mesh sizes in CI).  Alphabetically it runs
    # late in the suite, where the process already carries thousands of
    # cached XLA programs; dropping ours keeps the remaining modules clear
    # of the CPU JIT's accumulated-state cliff.
    jax.clear_caches()


if __name__ == "__main__":
    unittest.main()
