"""Every cell's control comes out as not correct: the reference in the
program's place one precision lower (or the program's own lower-precision
path) reads above a limit, on three seeds, at a size a test run can hold;
and the program itself reads under every limit on the same seeds."""

import json

import pytest

from perf import control
from perf import manifest as mf

MANIFEST = mf.load_manifest()
SEEDS = "11,2147483659,4000000007"


@pytest.mark.parametrize("name", [c["name"] for c in MANIFEST["workloads"]])
def test_control_is_not_correct(name, capsys):
    rc = control.main(["--workload", name, "--seeds", SEEDS, "--control-seeds", SEEDS,
                       "--calls", "1", "--rehearse-cpu"])
    assert rc == 0
    readings = [json.loads(ln) for ln in capsys.readouterr().out.splitlines() if ln.startswith("{")]
    verdict = readings.pop()
    assert verdict["separated"] is True and verdict["control_correct"] == "0/3"
    assert len(readings) == 6
    for r in readings:
        assert r["correct"] is (r["who"] == "program"), (r["seed"], r["who"], r["numbers"])
        assert bool(r["over"]) is not r["correct"]


def test_a_control_that_passes_is_reported(capsys, monkeypatch):
    """Where the control reads like the program (here: the program's own
    default path named as its control), ``control.py`` says so and exits 1."""
    monkeypatch.setattr(mf, "load_cell", _with_control(mf.load_cell, {}))
    rc = control.main(["--workload", "qr_tall", "--seeds", "11", "--control-seeds", "11",
                       "--calls", "1", "--rehearse-cpu"])
    assert rc == 1
    verdict = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert verdict["separated"] is False and verdict["control_correct"] == "1/1"


def _with_control(load_cell, kwargs):
    def patched(*a, **kw):
        cell = load_cell(*a, **kw)
        cell["workload"]["check"]["control"] = kwargs
        return cell
    return patched
