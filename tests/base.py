"""Shared test utilities (reference: heat/core/tests/test_suites/basic_test.py).

``TestCase.assert_array_equal`` follows the reference's oracle (:67-141):
check global shape/dtype, compare the global result against the NumPy
expectation, and compare **each device shard** against the corresponding NumPy
slice computed by ``comm.chunk`` — so sharding layout bugs cannot hide behind
a correct gather.
"""

import contextlib
import math
import unittest
from unittest import mock

import numpy as np

import heat_tpu as ht
from heat_tpu.core import autotune


@contextlib.contextmanager
def scripted_clock(**arm_seconds):
    """Inside, every wall clock the tuning plane folds into its table for
    the named arms reads as scripted.  Explore times and the sampled
    degradation watch both enter through ``autotune.observe``, so under
    this clock a winner, and whether it is sent back to explore, never
    depend on how loaded the machine is.  A forfeit (``inf``) stays one."""
    real = autotune.observe

    def observe(key, arm, dur_s):
        if math.isfinite(dur_s):
            dur_s = arm_seconds.get(arm, dur_s)
        real(key, arm, dur_s)

    with mock.patch.object(autotune, "observe", observe):
        yield


class TestCase(unittest.TestCase):
    @classmethod
    def setUpClass(cls):
        cls.comm = ht.parallel.get_comm()
        cls.device = ht.get_device()

    def get_rank(self):
        return self.comm.rank

    def get_size(self):
        return self.comm.size

    def assert_array_equal(self, heat_array, expected_array, rtol=1e-5, atol=1e-8):
        """Global + per-shard comparison against a numpy oracle."""
        self.assertIsInstance(
            heat_array, ht.DNDarray, f"expected DNDarray, got {type(heat_array)}"
        )
        expected_array = np.asarray(expected_array)
        self.assertEqual(
            tuple(heat_array.shape),
            tuple(expected_array.shape),
            f"global shape mismatch: {heat_array.shape} vs {expected_array.shape}",
        )
        got = heat_array.numpy()
        if np.issubdtype(expected_array.dtype, np.floating) or np.issubdtype(
            expected_array.dtype, np.complexfloating
        ):
            np.testing.assert_allclose(
                got.astype(expected_array.dtype), expected_array, rtol=rtol, atol=atol
            )
        else:
            np.testing.assert_array_equal(got.astype(expected_array.dtype), expected_array)

        # per-shard check against comm.chunk slices
        if heat_array.split is not None:
            shards = heat_array.lshards()
            for r, shard in enumerate(shards):
                _, _, slices = heat_array.comm.chunk(
                    heat_array.shape, heat_array.split, rank=r
                )
                expected_slice = expected_array[slices]
                self.assertEqual(
                    tuple(shard.shape),
                    tuple(expected_slice.shape),
                    f"shard {r} shape mismatch",
                )
                if np.issubdtype(expected_array.dtype, np.floating):
                    np.testing.assert_allclose(
                        shard.astype(expected_array.dtype), expected_slice, rtol=rtol, atol=atol
                    )
                else:
                    np.testing.assert_array_equal(
                        shard.astype(expected_array.dtype), expected_slice
                    )

    def assert_func_equal(
        self, shape, heat_func, numpy_func, heat_args=None, numpy_args=None, low=-10, high=10, dtype=np.float32
    ):
        """Run a heat fn vs a numpy fn over a generated array for every split
        (reference: basic_test.py:143)."""
        heat_args = heat_args or {}
        numpy_args = numpy_args or {}
        rng = np.random.default_rng(42)
        if np.issubdtype(dtype, np.integer):
            data = rng.integers(low, high, size=shape).astype(dtype)
        else:
            data = ((high - low) * rng.random(size=shape) + low).astype(dtype)
        expected = numpy_func(data, **numpy_args)
        for split in [None] + list(range(len(shape))):
            x = ht.array(data, split=split)
            result = heat_func(x, **heat_args)
            self.assert_array_equal(result, expected, rtol=1e-4, atol=1e-6)
