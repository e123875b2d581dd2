"""PRNG case matrix (reference model: heat/core/tests/test_random.py —
the reference proves its Threefry counter sequence gives identical global
streams for any rank count, correct moments, and stateful get/set
semantics; this is the same contract over jax's partitionable Threefry
plus the round-4 cached-sampler layer).
"""

import numpy as np

import heat_tpu as ht
from .base import TestCase


class TestStreamContracts(TestCase):
    def test_seed_reproducibility_per_sampler(self):
        for fn, args, kw in [
            (ht.random.rand, (9, 5), {}),
            (ht.random.randn, (9, 5), {}),
            (ht.random.randint, (0, 100), {"size": (9, 5)}),
            (ht.random.randperm, (37,), {}),
        ]:
            with self.subTest(fn=fn.__name__):
                ht.random.seed(999)
                a = fn(*args, **kw).numpy()
                ht.random.seed(999)
                b = fn(*args, **kw).numpy()
                np.testing.assert_array_equal(a, b)

    def test_split_invariance_matrix(self):
        # the core RNG contract: same seed -> same GLOBAL numbers for any
        # split (the reference's any-rank-count invariant)
        for splits in [(None, 0), (None, 1), (0, 1)]:
            with self.subTest(splits=splits):
                ht.random.seed(1234)
                a = ht.random.rand(13, 7, split=splits[0]).numpy()
                ht.random.seed(1234)
                b = ht.random.rand(13, 7, split=splits[1]).numpy()
                np.testing.assert_array_equal(a, b)

    def test_counter_advances_between_calls(self):
        ht.random.seed(7)
        a = ht.random.rand(50).numpy()
        b = ht.random.rand(50).numpy()
        self.assertFalse(np.array_equal(a, b))

    def test_get_set_state_roundtrip(self):
        ht.random.seed(42)
        ht.random.rand(10)
        state = ht.random.get_state()
        self.assertEqual(state[0], "Threefry")
        a = ht.random.rand(20).numpy()
        ht.random.set_state(state)
        b = ht.random.rand(20).numpy()
        np.testing.assert_array_equal(a, b)

    def test_set_state_validates(self):
        with self.assertRaises(ValueError):
            ht.random.set_state(("Mersenne", 0, 0))
        with self.assertRaises(ValueError):
            ht.random.set_state("not-a-tuple")


class TestSamplerDomains(TestCase):
    def test_rand_in_unit_interval(self):
        for dtype in (ht.float32, ht.float64, ht.bfloat16):
            with self.subTest(dtype=dtype):
                x = ht.random.rand(1000, dtype=dtype, split=0).numpy().astype(np.float64)
                self.assertGreaterEqual(x.min(), 0.0)
                self.assertLess(x.max(), 1.0)

    def test_randn_moments(self):
        x = ht.random.randn(200_000, split=0).numpy()
        self.assertLess(abs(x.mean()), 0.02)
        self.assertLess(abs(x.std() - 1.0), 0.02)

    def test_normal_loc_scale(self):
        x = ht.random.normal(3.0, 0.5, (100_000,), split=0).numpy()
        self.assertLess(abs(x.mean() - 3.0), 0.02)
        self.assertLess(abs(x.std() - 0.5), 0.02)

    def test_randint_bounds_matrix(self):
        for low, high in [(0, 2), (-5, 5), (100, 101), (0, 256)]:
            with self.subTest(low=low, high=high):
                x = ht.random.randint(low, high, size=(5000,), split=0).numpy()
                self.assertGreaterEqual(int(x.min()), low)
                self.assertLess(int(x.max()), high)
        # one-arg form: [0, high)
        x = ht.random.randint(7, size=(1000,)).numpy()
        self.assertGreaterEqual(int(x.min()), 0)
        self.assertLess(int(x.max()), 7)

    def test_randint_covers_small_range(self):
        x = ht.random.randint(0, 4, size=(4000,), split=0).numpy()
        self.assertEqual(set(np.unique(x).tolist()), {0, 1, 2, 3})

    def test_randint_dtype(self):
        self.assertEqual(
            ht.random.randint(0, 10, size=(5,), dtype=ht.int64).dtype, ht.int64
        )

    def test_scalar_shapes(self):
        s = ht.random.rand()
        self.assertEqual(tuple(s.shape), ())
        s2 = ht.random.randn()
        self.assertEqual(tuple(s2.shape), ())


class TestPermutations(TestCase):
    def test_randperm_is_permutation_sizes(self):
        for n in (1, 2, 13, 100, 1000):
            with self.subTest(n=n):
                p = ht.random.randperm(n).numpy()
                self.assertEqual(sorted(p.tolist()), list(range(n)))

    def test_sharded_randperm_is_permutation(self):
        p = ht.random.randperm(257, split=0)
        self.assertEqual(p.split, 0)
        self.assertEqual(sorted(p.numpy().tolist()), list(range(257)))

    def test_sharded_randperm_not_identity(self):
        p = ht.random.randperm(1000, split=0).numpy()
        self.assertGreater((p != np.arange(1000)).sum(), 900)

    def test_permutation_of_array_shuffles_rows(self):
        host = np.arange(40, dtype=np.float32).reshape(20, 2)
        x = ht.array(host, split=0)
        shuffled = ht.random.permutation(x)
        got = shuffled.numpy()
        self.assertEqual(got.shape, (20, 2))
        # rows preserved as units
        np.testing.assert_array_equal(
            np.sort(got[:, 0]), host[:, 0]
        )
        np.testing.assert_array_equal(got[:, 1] - got[:, 0], np.ones(20))

    def test_permutation_int_arg(self):
        p = ht.random.permutation(29)
        self.assertEqual(sorted(p.numpy().tolist()), list(range(29)))

    def test_shuffle_rows_shared_permutation(self):
        host_a = np.arange(60, dtype=np.float32).reshape(30, 2)
        host_b = np.arange(30, dtype=np.float32)[:, None]
        a = ht.array(host_a, split=0)
        b = ht.array(host_b, split=0)
        sa, sb = ht.random.shuffle_rows([a, b])
        ga, gb = sa.numpy(), sb.numpy()
        # the SAME permutation applied to both arrays
        np.testing.assert_array_equal(ga[:, 0] / 2.0, gb[:, 0])
        np.testing.assert_array_equal(np.sort(gb[:, 0]), host_b[:, 0])


class TestChunkedBigSampler(TestCase):
    def test_chunked_path_determinism_and_shape(self):
        # force the chunked generator (sub-f32 dtype + size over threshold is
        # impractical in a unit test; instead exercise the wrapper directly)
        from heat_tpu.core.random import _chunk_sampler, _base_uniform
        import jax
        import jax.numpy as jnp

        # patch the threshold locally by calling the builder with a shape
        # whose f32 intermediate exceeds a tiny budget
        import heat_tpu.core.random as rnd

        old = rnd._CHUNK_F32_BYTES
        rnd._CHUNK_F32_BYTES = 1024
        try:
            chunked = _chunk_sampler(_base_uniform, (300, 4), jnp.bfloat16)
            self.assertIsNotNone(chunked)
            key = jax.random.PRNGKey(0)
            a = np.asarray(chunked(key, (300, 4), jnp.bfloat16).astype(jnp.float32))
            b = np.asarray(chunked(key, (300, 4), jnp.bfloat16).astype(jnp.float32))
            np.testing.assert_array_equal(a, b)
            self.assertEqual(a.shape, (300, 4))
            self.assertGreaterEqual(a.min(), 0.0)
            self.assertLess(a.max(), 1.0)
            # all rows populated (no zero block left from the fori_loop)
            self.assertTrue((a.max(axis=1) > 0).all())
        finally:
            rnd._CHUNK_F32_BYTES = old


class TestSamplerCache(TestCase):
    def test_jit_cache_reuses_programs(self):
        # the round-4 fix: repeated calls must HIT the sampler cache (a
        # fresh jit per call recompiled every ht.random.* — the round-3
        # "lanczos" cost)
        from heat_tpu.core.random import _sampler_jit

        before = _sampler_jit.cache_info()
        ht.random.rand(64, 3, split=0)
        ht.random.rand(64, 3, split=0)
        ht.random.rand(64, 3, split=0)
        after = _sampler_jit.cache_info()
        self.assertGreaterEqual(after.hits - before.hits, 2)

    def test_factory_cache_reuses_programs(self):
        from heat_tpu.core.factories import _factory_jit

        before = _factory_jit.cache_info()
        ht.zeros((32, 4), split=0)
        ht.zeros((32, 4), split=0)
        ht.full((32, 4), 7.0, split=0)
        ht.full((32, 4), 9.0, split=0)  # different value, SAME program
        after = _factory_jit.cache_info()
        self.assertGreaterEqual(after.hits - before.hits, 2)

    def test_full_value_rides_as_operand(self):
        np.testing.assert_array_equal(
            ht.full((5,), 3, dtype=ht.int32).numpy(), np.full(5, 3, np.int32)
        )
        np.testing.assert_array_equal(
            ht.full((5,), True, dtype=ht.bool).numpy(), np.full(5, True)
        )
        np.testing.assert_allclose(
            ht.full((5,), 2.5, dtype=ht.bfloat16).numpy().astype(np.float32),
            np.full(5, 2.5, np.float32),
        )
