"""Laws of the chip bring-up (ISSUE 21), checked on the CPU and fast:

- ``chip_smoke.py`` without a TPU exits nonzero, names the platform it
  found and prints no result line;
- the compile-cache helper leaves ``jax_compilation_cache_dir`` alone when
  ``JAX_COMPILATION_CACHE_DIR`` is set, and otherwise yields the same path
  inside the checkout on every call;
- ``bench.py``'s peak lookup raises for a device that is not a TPU or whose
  ``device_kind`` has no entry in the one peak table;
- the timing fences (``autotune.timed``, ``telemetry.timed_call``,
  ``utils.monitor``) propagate an exception raised at the fence instead of
  timing a poisoned result.
"""

import importlib.util
import os
import subprocess
import sys
import unittest
from unittest import mock

import jax

from heat_tpu.core import autotune, telemetry
from heat_tpu.utils import compile_cache, monitor

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


class TestChipSmokeNeedsAChip(unittest.TestCase):
    def test_cpu_run_exits_nonzero_and_names_the_platform(self):
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, os.path.join(_ROOT, "chip_smoke.py")],
            env=env, capture_output=True, text=True, timeout=120, cwd=_ROOT,
        )
        self.assertNotEqual(proc.returncode, 0)
        self.assertIn("'cpu'", proc.stderr)
        self.assertIn("needs a TPU", proc.stderr)
        self.assertNotIn('"ok"', proc.stdout)


class TestCompileCachePlacement(unittest.TestCase):
    def setUp(self):
        self.prev_dir = jax.config.jax_compilation_cache_dir
        self.prev_min = jax.config.jax_persistent_cache_min_compile_time_secs

    def tearDown(self):
        jax.config.update("jax_compilation_cache_dir", self.prev_dir)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", self.prev_min
        )

    def test_env_placement_is_left_alone(self):
        jax.config.update("jax_compilation_cache_dir", "/placed/from/outside")
        with mock.patch.dict(
            os.environ, {"JAX_COMPILATION_CACHE_DIR": "/placed/from/outside"}
        ):
            self.assertEqual(compile_cache.enable(), "/placed/from/outside")
        self.assertEqual(
            jax.config.jax_compilation_cache_dir, "/placed/from/outside"
        )

    def test_default_is_one_fixed_path_inside_the_checkout(self):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        with mock.patch.dict(os.environ, env, clear=True):
            first = compile_cache.enable()
            second = compile_cache.enable()
        self.assertEqual(first, second)
        self.assertEqual(first, os.path.join(_ROOT, ".jax_cache"))
        self.assertEqual(jax.config.jax_compilation_cache_dir, first)


class _Device:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


class TestPeakLookup(unittest.TestCase):
    @classmethod
    def setUpClass(cls):
        spec = importlib.util.spec_from_file_location(
            "bench_entry", os.path.join(_ROOT, "bench.py")
        )
        cls.bench = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(cls.bench)

    def test_known_kind_reads_the_table(self):
        self.assertEqual(
            self.bench.peak_tflops_bf16(_Device("tpu", "TPU v5 lite")), 197.0
        )

    def test_unknown_kind_raises(self):
        with self.assertRaisesRegex(RuntimeError, "no entry in the peak table"):
            self.bench.peak_tflops_bf16(_Device("tpu", "TPU v99"))

    def test_no_tpu_raises(self):
        with self.assertRaisesRegex(RuntimeError, "needs a TPU"):
            self.bench.peak_tflops_bf16(_Device("cpu", "cpu"))


class _Poisoned:
    """Stands in for an array whose asynchronous computation failed: the
    error surfaces when something blocks on it."""

    def block_until_ready(self):
        raise RuntimeError("RESOURCE_EXHAUSTED: surfaced at the fence")


class TestFencesPropagate(unittest.TestCase):
    def _patched(self):
        def fence(x):
            x.block_until_ready()
            return x

        return mock.patch.object(jax, "block_until_ready", fence)

    def test_autotune_timed(self):
        with self._patched(), self.assertRaisesRegex(RuntimeError, "at the fence"):
            autotune.timed(_Poisoned)

    def test_telemetry_timed_call(self):
        prev = telemetry.set_level("events")
        try:
            with mock.patch.object(telemetry, "timing_active", lambda: True), \
                    self._patched(), \
                    self.assertRaisesRegex(RuntimeError, "at the fence"):
                telemetry.timed_call("fp-poisoned", _Poisoned)
        finally:
            telemetry.set_level(prev)

    def test_monitor_decorator(self):
        with self._patched(), self.assertRaisesRegex(RuntimeError, "at the fence"):
            monitor.monitor("poisoned", emit=False)(_Poisoned)()


if __name__ == "__main__":
    unittest.main()
