"""BENCHMARK.json and the files under perf/ agree, and keep to the contract's
limits on names, units and sizes."""

import json
import os
import re

import pytest

from perf import manifest as mf

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return mf.load_manifest()


def test_top_level_keys(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads",
                             "end_to_end", "per_layer"}
    assert manifest["paths"] == ["perf"]
    assert 1 <= manifest["run_seconds"] <= 51
    path = os.path.join(mf.ROOT, "BENCHMARK.json")
    assert os.path.getsize(path) <= 64 * 1024


def test_names_and_units(manifest):
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]), m["name"]
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in manifest["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
    assert "setup_s" in {m["name"] for m in manifest["end_to_end"]}


def test_cells_and_their_files(manifest):
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = manifest["workloads"]
    assert len({c["name"] for c in cells}) == len(cells)
    assert len({(c["config"], c["traffic"]) for c in cells}) == len(cells)
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)
    assert {c["config"] for c in cells} == set(configs)
    for c in cells:
        for key in ("name", "config", "traffic"):
            assert NAME.match(c[key]), c[key]
        assert c["chips"] in (1, 4) and len(c["why"]) <= 200
        cell = mf.load_cell(manifest, c["name"], rehearse=False)
        wl, cfg = cell["workload"], cell["config"]
        assert cfg["chips"] == c["chips"]
        assert os.path.isfile(os.path.join(mf.PERF_DIR, "traffic", c["traffic"] + ".json"))
        for kind, name in (("drivers", wl["driver"]), ("work_models", wl["work_model"]),
                           ("generators", cfg["data"]["generator"])):
            assert os.path.isfile(os.path.join(mf.PERF_DIR, kind, name + ".py")), (kind, name)
        # every cell reports set-up, another end-to-end metric and a per-layer one
        e2e = {m["name"] for m in cell["end_to_end"]}
        assert "setup_s" in e2e and len(e2e) >= 2 and cell["per_layer"]
        for m in cell["per_layer"]:
            assert os.path.isfile(os.path.join(mf.PERF_DIR, "layer_metrics", m["name"] + ".py"))
        assert wl["check"]["limits"], "a cell compares at least one number"


def test_configs(manifest):
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    sources = [c["source"] for c in manifest["configs"]]
    assert len(sources) == len(set(sources))
    for c in manifest["configs"]:
        assert NAME.match(c["name"]) and c["file"].startswith("perf/")
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
        with open(os.path.join(mf.ROOT, c["file"])) as fh:
            cfg = json.load(fh)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert NAME.match(key) and not key.endswith(("_dim", "_rank"))
            assert key not in ("features", "cols"), "a width is never cut"


def test_metric_workload_lists_name_cells(manifest):
    cells = {c["name"] for c in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
    for m in manifest["per_layer"]:
        moved = e2e[m["moves"]]
        if "workloads" in m and "workloads" in moved:
            assert set(m["workloads"]) <= set(moved["workloads"])


def test_file_names_under_paths():
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, files in os.walk(mf.PERF_DIR):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", "out")]
        for f in files:
            rel = os.path.relpath(os.path.join(base, f), mf.ROOT)
            assert allowed.match(rel), rel


def test_no_topology_call_at_import_time():
    """``get_topology_desc`` loads libtpu, which one process at a time may
    hold: nothing under perf/ calls it while a module is imported."""
    for base, dirs, files in os.walk(mf.PERF_DIR):
        dirs[:] = [d for d in dirs if d not in ("__pycache__", "out")]
        for f in files:
            if f.endswith(".py") and f != os.path.basename(__file__):
                with open(os.path.join(base, f)) as fh:
                    assert "get_topology_desc" not in fh.read(), f
