"""``--rehearse-cpu`` drives every cell's driver at toy size, labels the
platform ``cpu`` and prints no device metric; without it a CPU is refused."""

import pytest

from perf import manifest as mf
from perf.tests._util import run_cell

MANIFEST = mf.load_manifest()
CELLS = [(c["name"], c["chips"]) for c in MANIFEST["workloads"]]
DEVICE_METRICS = {m["name"] for m in MANIFEST["per_layer"] if m["source"] == "device_trace"}


@pytest.mark.parametrize("name,chips", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_the_cell(name, chips, trace):
    rc, result, err = run_cell(name, chips=chips, trace=trace)
    assert rc == 0, err[-2000:]
    assert result["correct"] is True, result["check"]
    assert result["rehearsal"] is True
    assert result["device"]["platform"] == "cpu" and result["device"]["count"] == chips
    assert result["failed"] == 0 and result["attempted"] >= 1
    assert list(result)[-1] == "check" and result["check"]
    assert not DEVICE_METRICS & set(result["metrics"]), "a CPU run printed a device metric"
    assert "busy_s" not in result["device"] and "breakdown" not in result
    cell = mf.load_cell(MANIFEST, name, True)
    if trace:
        counters = {m["name"] for m in cell["per_layer"] if m["source"] == "program_counter"
                    and m["name"].startswith(("explores_in_window", "compiles_in_window"))}
        assert len(counters) == 2 and counters <= set(result["metrics"])
        assert all(result["metrics"][name]["value"] == 0 for name in counters)
    else:
        assert set(result["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    # every number compared is printed beside its limit, last on standard error
    tail = err.strip().splitlines()[-(len(result["check"]) + 1):]
    assert tail[-1] == "correct: True"
    for line, name_ in zip(tail, result["check"]):
        assert line.startswith(f"check {name_}: ")


def test_cpu_without_rehearsal_is_refused():
    rc, result, err = run_cell("kmeans_fit", rehearse=False)
    assert rc == 2 and result is None
    assert "needs a TPU" in err and "'cpu'" in err


def test_wrong_device_count_is_refused():
    # the backend is up with two devices before the harness asks for one
    rc, result, err = run_cell("kmeans_fit", chips=2, patch="import heat_tpu")
    assert rc == 2 and result is None and "1 chip(s)" in err


def test_unknown_workload_is_refused():
    rc, result, _ = run_cell("no_such_cell")
    assert rc != 0 and result is None
