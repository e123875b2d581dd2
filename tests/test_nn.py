"""NN/optim layer tests (reference models: heat/nn/tests/test_data_parallel.py,
heat/optim/tests/, heat/utils/data/ tests)."""

import numpy as np

import heat_tpu as ht
from .base import TestCase


class TestDataParallel(TestCase):
    def _toy_problem(self, n=256, f=8, classes=3, seed=0):
        rng = np.random.default_rng(seed)
        X = rng.standard_normal((n, f)).astype(np.float32)
        W = rng.standard_normal((f, classes)).astype(np.float32)
        y = (X @ W).argmax(axis=1)
        return X, y

    def test_mlp_training_reduces_loss(self):
        import optax

        X, y = self._toy_problem()
        model = ht.nn.DataParallel(
            ht.models.MLP(features=(32, 3)),
            optimizer=ht.optim.DataParallelOptimizer(optax.adam(1e-2)),
        )
        model.init(0, X[:8])
        data = ht.array(X, split=0)
        labels = ht.array(y, split=0)
        # read each loss back: 60 steps queued ahead of a loaded CPU mesh
        # starve XLA's in-process all-reduce of a thread (rendezvous abort)
        losses = [float(model.train_step(data, labels)) for _ in range(60)]
        self.assertLess(losses[-1], losses[0] * 0.3)
        # forward through the wrapper returns a split DNDarray
        out = model(data)
        self.assertEqual(out.shape, (X.shape[0], 3))
        self.assertEqual(out.split, 0)
        acc = (out.numpy().argmax(axis=1) == y).mean()
        self.assertGreater(acc, 0.9)

    def test_resnet_train_step_runs(self):
        """ResNet-18 with BatchNorm: batch_stats must update, loss finite."""
        import optax

        rng = np.random.default_rng(1)
        X = rng.standard_normal((16, 16, 16, 3)).astype(np.float32)
        y = rng.integers(0, 4, 16)
        model = ht.nn.DataParallel(
            ht.models.ResNet18(num_classes=4),
            optimizer=ht.optim.DataParallelOptimizer(optax.sgd(1e-2)),
        )
        model.init(0, X[:2])
        before = model.variables["batch_stats"]
        loss1 = model.train_step(ht.array(X, split=0), ht.array(y, split=0))
        self.assertTrue(np.isfinite(loss1))
        after = model.variables["batch_stats"]
        import jax

        changed = jax.tree.reduce(
            lambda acc, pair: acc or pair,
            jax.tree.map(lambda a, b: bool((np.asarray(a) != np.asarray(b)).any()), before, after),
        )
        self.assertTrue(changed)

    def test_train_before_init_raises(self):
        import optax

        model = ht.nn.DataParallel(
            ht.models.MLP(features=(4, 2)),
            optimizer=ht.optim.DataParallelOptimizer(optax.sgd(0.1)),
        )
        with self.assertRaises(RuntimeError):
            model.train_step(ht.ones((4, 4)), ht.zeros((4,), dtype=ht.int32))

    def test_nn_fallthrough(self):
        self.assertTrue(hasattr(ht.nn, "Dense"))
        self.assertTrue(hasattr(ht.nn, "Conv"))
        self.assertTrue(callable(ht.nn.functional.relu))
        with self.assertRaises(AttributeError):
            ht.nn.DefinitelyNotALayer


class TestOptim(TestCase):
    def test_optim_fallthrough(self):
        self.assertTrue(callable(ht.optim.SGD))
        self.assertTrue(callable(ht.optim.Adam))
        self.assertTrue(callable(ht.optim.adamw))

    def test_detect_metric_plateau(self):
        det = ht.optim.DetectMetricPlateau(patience=2, threshold=1e-3)
        improving = [1.0, 0.8, 0.6, 0.4]
        for v in improving:
            self.assertFalse(det.test_if_improving(v))
        # now stall: patience 2 → third stalled epoch trips
        self.assertFalse(det.test_if_improving(0.4))
        self.assertFalse(det.test_if_improving(0.4))
        self.assertTrue(det.test_if_improving(0.4))
        # state roundtrip
        state = det.get_state()
        det2 = ht.optim.DetectMetricPlateau()
        det2.set_state(state)
        self.assertEqual(det2.best, det.best)

    def test_daso_skip_logic(self):
        import optax

        daso = ht.optim.DASO(
            ht.optim.DataParallelOptimizer(optax.sgd(0.1)),
            total_epochs=20, warmup_epochs=2, cooldown_epochs=2,
        )
        self.assertEqual(daso.phase, "warmup")
        daso.next_epoch(1.0)
        daso.next_epoch(0.99)
        self.assertEqual(daso.phase, "cycling")
        # stable loss → skips grow
        daso.next_epoch(0.989)
        skip_after_stable = daso.global_skip
        self.assertGreaterEqual(skip_after_stable, 1)
        daso.next_epoch(0.5)  # big improvement → skips shrink
        self.assertLessEqual(daso.global_skip, max(skip_after_stable, 1))
        daso.epoch = 19
        self.assertEqual(daso.phase, "cooldown")

    def test_lr_schedules(self):
        sched = ht.optim.lr_scheduler.StepLR(0.1, step_size=10, gamma=0.5)
        self.assertAlmostEqual(float(sched(0)), 0.1, places=6)
        self.assertAlmostEqual(float(sched(10)), 0.05, places=6)
        cos = ht.optim.lr_scheduler.CosineAnnealingLR(0.1, T_max=100)
        self.assertLess(float(cos(100)), 1e-6)


class TestDataTools(TestCase):
    def test_dataloader_batches(self):
        X = np.arange(40, dtype=np.float32).reshape(20, 2)
        y = np.arange(20)
        ds = ht.utils.data.Dataset(ht.array(X, split=0), ht.array(y, split=0))
        dl = ht.utils.data.DataLoader(ds, batch_size=4)
        batches = list(dl)
        self.assertEqual(len(batches), 5)
        bx, by = batches[0]
        self.assertEqual(tuple(bx.shape), (4, 2))
        np.testing.assert_array_equal(np.asarray(by), np.arange(4))

    def test_dataloader_shuffle_preserves_pairs(self):
        X = np.arange(32, dtype=np.float32).reshape(16, 2)
        y = np.arange(16)
        ds = ht.utils.data.Dataset(ht.array(X, split=0), ht.array(y, split=0))
        ht.random.seed(4)
        dl = ht.utils.data.DataLoader(ds, batch_size=16, shuffle=True)
        (bx, by) = next(iter(dl))
        bx, by = np.asarray(bx), np.asarray(by)
        # pairing preserved under the global shuffle
        np.testing.assert_array_equal(bx[:, 0], 2 * by)
        # actually shuffled
        self.assertFalse((by == np.arange(16)).all())

    def test_partial_h5_dataset(self):
        import h5py, tempfile, os

        data = np.arange(100, dtype=np.float32).reshape(50, 2)
        labels = np.arange(50, dtype=np.int64)
        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "stream.h5")
            with h5py.File(path, "w") as f:
                f.create_dataset("data", data=data)
                f.create_dataset("labels", data=labels)
            ds = ht.utils.data.PartialH5Dataset(
                path, dataset_names=["data", "labels"], initial_load=20
            )
            self.assertEqual(len(ds), 50)
            seen = []
            for bx, by in ds:
                self.assertEqual(bx.split, 0)
                seen.append(np.asarray(by.larray))
            np.testing.assert_array_equal(np.concatenate(seen), labels)


class TestDASOTwoTier(TestCase):
    """End-to-end hierarchical DP: 2 DCN slices × 4 ICI devices."""

    def _two_tier(self):
        import jax
        from jax.sharding import Mesh
        from heat_tpu.parallel.mesh import MeshComm

        devices = np.array(jax.devices()[:8]).reshape(2, 4)
        mesh = Mesh(devices, ("dcn", "ici"))
        return mesh, MeshComm(mesh, split_axis="ici")

    def test_daso_training_converges_and_slices_diverge(self):
        import jax
        import optax

        mesh, comm = self._two_tier()
        daso = ht.optim.DASO(
            ht.optim.DataParallelOptimizer(optax.sgd(0.05)),
            mesh=mesh, comm=comm,
            total_epochs=10, warmup_epochs=0, cooldown_epochs=0,
        )
        self.assertEqual(daso.n_slices, 2)
        model = ht.nn.DataParallelMultiGPU(
            ht.models.MLP(features=(16, 3)), comm=comm, optimizer=daso
        )
        rng = np.random.default_rng(0)
        X = rng.standard_normal((64, 8)).astype(np.float32)
        W = rng.standard_normal((8, 3)).astype(np.float32)
        y = (X @ W).argmax(axis=1)
        model.init(0, X[:8])
        # params are slice-stacked: every leaf has leading dim 2
        leaf = jax.tree.leaves(model.params)[0]
        self.assertEqual(leaf.shape[0], 2)

        daso.global_skip = 4  # skip window: slices free-run between syncs
        losses = []
        diverged = False
        for i in range(24):
            losses.append(model.train_step(ht.array(X), ht.array(y)))
            w = np.asarray(jax.tree.leaves(model.params)[0])
            if not daso.should_sync_globally() and not np.allclose(w[0], w[1]):
                diverged = True
        self.assertLess(losses[-1], losses[0])
        # identical per-slice batches here; divergence comes only from
        # different data — so after each sync slices agree again
        daso.global_skip = 1
        model.train_step(ht.array(X), ht.array(y))
        w = np.asarray(jax.tree.leaves(model.params)[0])
        np.testing.assert_allclose(w[0], w[1], rtol=1e-5)

    def test_daso_slices_see_different_data(self):
        import jax
        import optax

        mesh, comm = self._two_tier()
        daso = ht.optim.DASO(
            ht.optim.DataParallelOptimizer(optax.sgd(0.1)),
            mesh=mesh, comm=comm,
            total_epochs=10, warmup_epochs=0, cooldown_epochs=0,
        )
        model = ht.nn.DataParallelMultiGPU(
            ht.models.MLP(features=(8, 2)), comm=comm, optimizer=daso
        )
        rng = np.random.default_rng(1)
        X = rng.standard_normal((32, 4)).astype(np.float32)
        y = rng.integers(0, 2, 32)
        model.init(0, X[:4])
        daso.global_skip = 100  # never sync inside this loop
        daso.batches_seen = 1  # avoid the step-0 sync
        for _ in range(3):
            model.train_step(ht.array(X), ht.array(y))
        w = np.asarray(jax.tree.leaves(model.params)[0])
        # slices trained on different halves of the batch → diverged params
        self.assertFalse(np.allclose(w[0], w[1]))


class TestDASOSyncSchedule(TestCase):
    """VERDICT r1 #7: on a real (dcn=2, ici=4) mesh, parameters must agree
    across slices exactly at scheduled global syncs and diverge between
    them; plateau adaptation must widen the skip window; cooldown must
    return to per-step sync (reference: dp_optimizer.py:336-730)."""

    def _setup(self, warmup, cooldown, total):
        import jax
        import optax
        from jax.sharding import Mesh
        from heat_tpu.parallel.mesh import MeshComm

        devices = np.array(jax.devices()[:8]).reshape(2, 4)
        mesh = Mesh(devices, ("dcn", "ici"))
        comm = MeshComm(mesh, split_axis="ici")
        daso = ht.optim.DASO(
            ht.optim.DataParallelOptimizer(optax.sgd(0.1)),
            mesh=mesh, comm=comm,
            total_epochs=total, warmup_epochs=warmup, cooldown_epochs=cooldown,
        )
        model = ht.nn.DataParallelMultiGPU(
            ht.models.MLP(features=(8, 2)), comm=comm, optimizer=daso
        )
        rng = np.random.default_rng(5)
        X = rng.standard_normal((32, 4)).astype(np.float32)
        y = rng.integers(0, 2, 32)
        model.init(0, X[:4])
        return daso, model, X, y

    def _slices_agree(self, model):
        import jax

        w = np.asarray(jax.tree.leaves(model.params)[0])
        return np.allclose(w[0], w[1], rtol=1e-6, atol=1e-7)

    def test_params_change_only_at_scheduled_syncs(self):
        daso, model, X, y = self._setup(warmup=0, cooldown=0, total=10)
        daso.global_skip = 3
        daso.batches_seen = 1  # step counter mid-stream, no step-0 sync
        for step in range(2, 14):
            was_sync = (step % 3) == 0  # batches_seen hits a multiple of 3
            model.train_step(ht.array(X), ht.array(y))
            self.assertEqual(daso.batches_seen, step)
            self.assertEqual(
                self._slices_agree(model), was_sync,
                f"step {step}: agree={self._slices_agree(model)} expected sync={was_sync}",
            )

    def test_warmup_and_cooldown_sync_every_step(self):
        daso, model, X, y = self._setup(warmup=2, cooldown=2, total=6)
        self.assertEqual(daso.phase, "warmup")
        daso.global_skip = 8  # must be ignored during warmup
        for _ in range(3):
            model.train_step(ht.array(X), ht.array(y))
            self.assertTrue(self._slices_agree(model), "warmup must sync per step")
        daso.epoch = 5  # jump to cooldown
        self.assertEqual(daso.phase, "cooldown")
        daso.global_skip = 8  # must be ignored during cooldown too
        for _ in range(3):
            model.train_step(ht.array(X), ht.array(y))
            self.assertTrue(self._slices_agree(model), "cooldown must sync per step")

    def test_plateau_widens_skip_worsening_narrows(self):
        daso, model, X, y = self._setup(warmup=0, cooldown=0, total=20)
        daso.epoch = 1  # cycling
        daso.global_skip = 2
        daso._last_losses = [1.0]
        daso.epoch_loss_logic(0.999)  # plateau: relative improvement < 5%
        self.assertEqual(daso.global_skip, 4)
        daso.epoch_loss_logic(0.998)  # still plateaued (tiny improvement)
        self.assertEqual(daso.global_skip, 8)
        daso.epoch_loss_logic(1.5)  # worsening → halve
        self.assertEqual(daso.global_skip, 4)


class TestNNReviewRegressions(TestCase):
    """Regressions for the NN-layer review findings."""

    def test_partial_h5_reader_error_propagates(self):
        import h5py, tempfile, os

        with tempfile.TemporaryDirectory() as d:
            path = os.path.join(d, "stream.h5")
            with h5py.File(path, "w") as f:
                f.create_dataset("data", data=np.zeros((10, 2)))
            ds = ht.utils.data.PartialH5Dataset(
                path, dataset_names=["data", "missing"], initial_load=5
            )
            with self.assertRaises(RuntimeError):
                list(ds)

    def test_daso_sync_actually_averages(self):
        import jax.numpy as jnp
        import optax

        daso = ht.optim.DASO(
            ht.optim.DataParallelOptimizer(optax.sgd(0.0)),
            total_epochs=10, warmup_epochs=0, cooldown_epochs=0,
        )
        daso.dcn_axis = "dcn"  # two-tier layout: leading dim = slices
        diverged = {"w": jnp.stack([jnp.ones(4), 3 * jnp.ones(4)])}
        daso.local_optimizer.init(diverged)
        daso.global_skip = 1  # sync every step
        synced = daso.step({"w": jnp.zeros_like(diverged["w"])}, diverged)
        np.testing.assert_allclose(np.asarray(synced["w"]), 2.0)

    def test_daso_worsening_loss_syncs_more(self):
        import optax

        daso = ht.optim.DASO(
            ht.optim.DataParallelOptimizer(optax.sgd(0.1)),
            total_epochs=30, warmup_epochs=0, cooldown_epochs=0,
        )
        daso.global_skip = 8
        daso._last_losses = [1.0]
        daso.epoch_loss_logic(2.0)  # diverging
        self.assertLess(daso.global_skip, 8)

    def test_dataloader_keeps_tail_by_default(self):
        X = np.arange(10, dtype=np.float32).reshape(10, 1)
        dl = ht.utils.data.DataLoader(ht.array(X, split=0), batch_size=4)
        batches = list(dl)
        self.assertEqual(len(batches), 3)
        self.assertEqual(batches[-1].shape[0], 2)

    def test_sparse_todense_out_validation(self):
        import scipy.sparse

        sp = scipy.sparse.eye(4, format="csr", dtype=np.float32)
        d = ht.sparse.sparse_csr_matrix(sp, split=0)
        bad = ht.zeros((3, 3))
        with self.assertRaises(ValueError):
            d.todense(out=bad)

    def test_base_import_without_nn(self):
        import subprocess, sys

        code = (
            "import jax; jax.config.update('jax_platforms','cpu');"
            "import sys; sys.modules['flax']=None; sys.modules['optax']=None;"
            "import heat_tpu as ht; print(ht.arange(3).numpy().tolist())"
        )
        # one retry: the subprocess competes with the suite's own compiles
        # for CPU and has been seen to die under load
        for attempt in range(2):
            r = subprocess.run(
                [sys.executable, "-c", code], capture_output=True, text=True,
                timeout=120,
            )
            if "[0, 1, 2]" in r.stdout:
                return
        self.assertIn("[0, 1, 2]", r.stdout, r.stderr)


class TestDASOMultiSlice(TestCase):
    """VERDICT r2 weak #6: grow the virtual-mesh DASO evidence — 4-slice
    (dcn=4, ici=2) and 8x1 schedules.  (Uneven slice sizes are not
    representable: a jax Mesh is rectangular by construction, so every
    dcn slice owns the same ici extent.)"""

    def _mesh(self, dcn, ici):
        import jax
        from jax.sharding import Mesh

        from heat_tpu.parallel.mesh import MeshComm

        devices = np.array(jax.devices()[: dcn * ici]).reshape(dcn, ici)
        mesh = Mesh(devices, ("dcn", "ici"))
        return mesh, MeshComm(mesh, split_axis="ici")

    def test_four_slices_sync_and_diverge(self):
        import jax
        import optax

        mesh, comm = self._mesh(4, 2)
        daso = ht.optim.DASO(
            ht.optim.DataParallelOptimizer(optax.sgd(0.05)),
            mesh=mesh, comm=comm,
            total_epochs=10, warmup_epochs=0, cooldown_epochs=0,
        )
        self.assertEqual(daso.n_slices, 4)
        model = ht.nn.DataParallelMultiGPU(
            ht.models.MLP(features=(8, 2)), comm=comm, optimizer=daso
        )
        rng = np.random.default_rng(0)
        X = rng.standard_normal((32, 4)).astype(np.float32)
        y = rng.integers(0, 2, 32)
        model.init(0, X[:4])
        leaf = jax.tree.leaves(model.params)[0]
        self.assertEqual(leaf.shape[0], 4)  # one param copy per slice
        daso.global_skip = 100
        daso.batches_seen = 1
        for _ in range(3):
            model.train_step(ht.array(X), ht.array(y))
        w = np.asarray(jax.tree.leaves(model.params)[0])
        # four slices on four data shards: pairwise divergence
        for a in range(4):
            for b in range(a + 1, 4):
                self.assertFalse(np.allclose(w[a], w[b]), (a, b))
        # one forced sync: all four agree again
        daso.global_skip = 1
        model.train_step(ht.array(X), ht.array(y))
        w = np.asarray(jax.tree.leaves(model.params)[0])
        for a in range(1, 4):
            np.testing.assert_allclose(w[0], w[a], rtol=1e-5)

    def test_eight_slices_single_device_each(self):
        import optax

        mesh, comm = self._mesh(8, 1)
        daso = ht.optim.DASO(
            ht.optim.DataParallelOptimizer(optax.sgd(0.05)),
            mesh=mesh, comm=comm,
            total_epochs=4, warmup_epochs=1, cooldown_epochs=1,
        )
        self.assertEqual(daso.n_slices, 8)
        model = ht.nn.DataParallelMultiGPU(
            ht.models.MLP(features=(4, 2)), comm=comm, optimizer=daso
        )
        rng = np.random.default_rng(1)
        X = rng.standard_normal((16, 4)).astype(np.float32)
        y = rng.integers(0, 2, 16)
        model.init(0, X[:2])
        loss = model.train_step(ht.array(X), ht.array(y))
        self.assertTrue(np.isfinite(float(loss)))
