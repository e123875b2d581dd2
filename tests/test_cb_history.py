"""Perf-regression harness (benchmarks/cb/history.py): tolerance model
unit laws plus the self-check gate replayed on a BENCH_cb_r*.json
trajectory the test writes (the repo holds no round records yet —
ROADMAP S0 — so a live run reports every row ``no-history``)."""

import importlib.util
import json
import os
import tempfile
import unittest

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def _load_history():
    # benchmarks/cb is a script directory, not a package
    path = os.path.join(_ROOT, "benchmarks", "cb", "history.py")
    spec = importlib.util.spec_from_file_location("cb_history", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


history = _load_history()

# a three-round TPU trajectory with the shape of a real one: rows appear
# over time, walls wander inside tolerance, one tiny row jitters under
# the absolute floor
_TRAJECTORY = {
    2: {"backend": "tpu", "measurements": [
        {"name": "matmul_split_0", "wall_s": 0.00680},
        {"name": "concatenate", "wall_s": 0.00090}]},
    3: {"backend": "tpu", "measurements": [
        {"name": "matmul_split_0", "wall_s": 0.00668},
        {"name": "concatenate", "wall_s": 0.00110},
        {"name": "lanczos", "wall_s": 0.0120}]},
    4: {"backend": "tpu", "measurements": [
        {"name": "matmul_split_0", "wall_s": 0.00671},
        {"name": "concatenate", "wall_s": 0.00230},
        {"name": "lanczos", "wall_s": 0.0310},
        {"name": "kmeans_lloyd_iter", "wall_s": 0.00770}]},
}


def _write_trajectory(root, rounds=_TRAJECTORY):
    for rnum, doc in rounds.items():
        with open(os.path.join(root, f"BENCH_cb_r{rnum:02d}.json"), "w") as fh:
            json.dump(doc, fh)


class TestCompare(unittest.TestCase):
    def test_regression_flagged_beyond_tolerance(self):
        best = {"matmul_split_0": {"best_wall_s": 1.0, "round": 3}}
        # limit = max(1.0 * 1.25, 1.0 + 0.002) = 1.25s
        rows, bad = history.compare(
            [{"name": "matmul_split_0", "wall_s": 1.26}], best
        )
        self.assertEqual(len(bad), 1)
        self.assertEqual(bad[0]["status"], "regression")
        self.assertEqual(bad[0]["best_round"], 3)
        rows, bad = history.compare(
            [{"name": "matmul_split_0", "wall_s": 1.24}], best
        )
        self.assertEqual(bad, [])
        self.assertEqual(rows[0]["status"], "ok")

    def test_abs_floor_suppresses_tiny_row_jitter(self):
        # a 0.5 ms row tripling is still under the 2 ms jitter floor
        best = {"concatenate": {"best_wall_s": 0.0005, "round": 2}}
        rows, bad = history.compare(
            [{"name": "concatenate", "wall_s": 0.0015}], best
        )
        self.assertEqual(bad, [])
        self.assertEqual(rows[0]["status"], "ok")
        # ... but blowing past best + floor flags even on a tiny row
        rows, bad = history.compare(
            [{"name": "concatenate", "wall_s": 0.004}], best
        )
        self.assertEqual(len(bad), 1)

    def test_per_row_override_applies(self):
        self.assertIn("lanczos", history.TOLERANCE)
        best = {"lanczos": {"best_wall_s": 0.010, "round": 4}}
        rows, bad = history.compare(
            [{"name": "lanczos", "wall_s": 0.035}], best  # 3.5x, tol 3.0
        )
        self.assertEqual(bad, [])  # limit = 0.010 * 4.0 = 0.040
        rows, bad = history.compare(
            [{"name": "lanczos", "wall_s": 0.041}], best
        )
        self.assertEqual(len(bad), 1)

    def test_no_history_row_passes(self):
        rows, bad = history.compare(
            [{"name": "brand_new_row", "wall_s": 9.9}], {}
        )
        self.assertEqual(bad, [])
        self.assertEqual(rows[0]["status"], "no-history")

    def test_rows_missing_fields_skipped(self):
        rows, bad = history.compare(
            [{"name": "x"}, {"wall_s": 1.0}, {"name": "y", "wall_s": None}],
            {},
        )
        self.assertEqual(rows, [])
        self.assertEqual(bad, [])


class TestHistoryLoading(unittest.TestCase):
    def test_best_history_is_backend_scoped_minimum(self):
        rounds = [
            (2, "r2", {"backend": "tpu", "measurements": [
                {"name": "a", "wall_s": 2.0}, {"name": "b", "wall_s": 5.0}]}),
            (3, "r3", {"backend": "tpu", "measurements": [
                {"name": "a", "wall_s": 1.0}]}),
            (4, "r4", {"backend": "cpu", "measurements": [
                {"name": "a", "wall_s": 0.1}]}),
        ]
        best = history.best_history(rounds, "tpu")
        self.assertEqual(best["a"], {"best_wall_s": 1.0, "round": 3})
        self.assertEqual(best["b"], {"best_wall_s": 5.0, "round": 2})
        # the CPU round never contaminates the TPU baseline
        windowed = history.best_history(rounds, "tpu", before_round=3)
        self.assertEqual(windowed["a"]["best_wall_s"], 2.0)

    def test_load_rounds_reads_trajectory_in_round_order(self):
        with tempfile.TemporaryDirectory() as td:
            _write_trajectory(td)
            rounds = history.load_rounds(td)
        self.assertEqual(len(rounds), 3)
        nums = [r for r, _p, _d in rounds]
        self.assertEqual(nums, sorted(nums))
        for _r, _p, doc in rounds:
            self.assertIn("backend", doc)
            self.assertIn("measurements", doc)

    def test_load_rounds_skips_malformed_file(self):
        with tempfile.TemporaryDirectory() as td:
            with open(os.path.join(td, "BENCH_cb_r01.json"), "w") as fh:
                fh.write("{not json")
            with open(os.path.join(td, "BENCH_cb_r02.json"), "w") as fh:
                json.dump({"backend": "tpu", "measurements": []}, fh)
            rounds = history.load_rounds(td)
        self.assertEqual([r for r, _p, _d in rounds], [2])


class TestGate(unittest.TestCase):
    def test_self_check_passes_on_a_steady_trajectory(self):
        # the CI gate itself: latest round vs best of the earlier ones
        with tempfile.TemporaryDirectory() as td:
            _write_trajectory(td)
            self.assertEqual(history.self_check(td), [])

    def test_self_check_bites_on_a_planted_regression(self):
        doctored = json.loads(json.dumps(_TRAJECTORY))  # deep copy
        doctored = {int(k): v for k, v in doctored.items()}
        for m in doctored[4]["measurements"]:
            m["wall_s"] = m["wall_s"] * 10.0
        with tempfile.TemporaryDirectory() as td:
            _write_trajectory(td, doctored)
            bad = history.self_check(td)
        self.assertTrue(bad)  # 10x everywhere must trip the gate

    def test_check_attaches_delta_table_to_doc(self):
        doc = {"backend": "tpu", "measurements": [
            {"name": "matmul_split_0", "wall_s": 1e9}]}
        with tempfile.TemporaryDirectory() as td:
            _write_trajectory(td)
            bad = history.check(doc, root=td)
        self.assertEqual(len(bad), 1)
        reg = doc["regression"]
        self.assertEqual(reg["backend"], "tpu")
        self.assertEqual(reg["regressions"], ["matmul_split_0"])
        self.assertEqual(reg["rows"][0]["status"], "regression")
        self.assertTrue(reg["baseline_rounds"])

    def test_check_cpu_run_passes_as_no_history(self):
        # a dev-machine CPU run is never judged against the TPU trajectory
        doc = {"backend": "cpu", "measurements": [
            {"name": "matmul_split_0", "wall_s": 1e9}]}
        with tempfile.TemporaryDirectory() as td:
            _write_trajectory(td)
            bad = history.check(doc, root=td)
        self.assertEqual(bad, [])
        self.assertEqual(doc["regression"]["rows"][0]["status"], "no-history")

    def test_repo_without_records_reports_no_history(self):
        # the truth today: no round record is checked in
        doc = {"backend": "tpu", "measurements": [
            {"name": "matmul_split_0", "wall_s": 1e9}]}
        self.assertEqual(history.check(doc, root=_ROOT), [])
        self.assertEqual(doc["regression"]["rows"][0]["status"], "no-history")
        self.assertEqual(doc["regression"]["baseline_rounds"], [])


if __name__ == "__main__":
    unittest.main()
