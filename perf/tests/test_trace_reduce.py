"""The reduction from a trace to numbers, on a hand-made trace whose answers
can be worked out on paper and on a recorded one cross-checked by brute force."""

import glob
import json
import os

import pytest

from perf import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def hand_made():
    """Window 0..1000 ns, two calls.  Device 0: ops cover 100..300 (two
    overlapping), 400..450 (an all-gather) and 900..1100 (cut at the window's
    end).  Device 1 is busy 100..200 only."""
    host = {"name": "python3", "events": [
        [tr.WINDOW, 0.0, 1000.0],
        [tr.CALL, 50.0, 400.0], ["kmeans.fit", 60.0, 300.0], ["inner", 310.0, 100.0],
        [tr.CALL, 500.0, 480.0], ["readback", 600.0, 300.0],
    ]}
    dev0 = {"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": [
            ["%fusion.1", 100.0, 150.0], ["%fusion.2", 200.0, 100.0],
            ["%all-gather.3 = f32[8]", 400.0, 50.0], ["%fusion.1", 900.0, 200.0],
            ["%outside", 2000.0, 10.0]]},
        {"name": tr.MODULES_LINE, "events": [
            ["jit_a(1)", 100.0, 200.0], ["jit_b(2)", 400.0, 50.0], ["jit_a(1)", 900.0, 200.0]]},
    ]}
    dev1 = {"name": "/device:TPU:1", "lines": [
        {"name": tr.OPS_LINE, "events": [["%fusion.1", 100.0, 100.0]]},
        {"name": tr.MODULES_LINE, "events": [["jit_a(1)", 100.0, 100.0]]},
    ]}
    return {"planes": [dev0, dev1, {"name": "/host:CPU", "lines": [host]}]}


def test_hand_made_trace():
    r = tr.reduce(hand_made())
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["calls"] == 2 and r["fullest"] == 0
    d0, d1 = r["devices"]
    assert d0["busy_s"] == pytest.approx((200 + 50 + 100) * 1e-9)
    assert d1["busy_s"] == pytest.approx(100e-9)
    assert d0["programs"] == 3 and d1["programs"] == 1
    assert d0["collective_s"] == pytest.approx(50e-9) and d1["collective_s"] == 0
    assert d0["ops"]["%fusion.1"] == pytest.approx(250e-9)   # 150 + the 100 inside
    assert "%outside" not in d0["ops"]
    # idle: 0..100 (mid 50: the call, just started), 300..400 (mid 350: inner),
    # 450..900 (mid 675: readback)
    gaps = r["idle_gaps"]
    assert gaps[tr.CALL] == pytest.approx(100e-9)
    assert gaps["inner"] == pytest.approx(100e-9)
    assert gaps["readback"] == pytest.approx(450e-9)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - d0["busy_s"])
    assert tr.top(gaps, 1) == [["readback", pytest.approx(450e-9)]]


def test_readers_on_hand_made_trace():
    from perf import manifest as mf

    run = {"trace": tr.reduce(hand_made()), "calls": 2, "floor_s": 35e-9, "chips": 2,
           "counters": {"autotune_explores": 0, "jax_compiles": 1, "fusion_misses": 2},
           "memory_peak_bytes": 5_000_000_000}

    def read(name):
        return mf.load_module("layer_metrics", name).read(run)

    assert read("device_idle_pct") == pytest.approx(65.0)
    assert read("host_ms_per_call") == pytest.approx(650e-9 / 2 * 1e3)
    assert read("programs_per_call") == pytest.approx(1.5)
    assert read("kernel_roofline") == pytest.approx(10.0)
    assert read("compiles_in_window") == 3.0 and read("explores_in_window") == 0.0
    assert read("peak_hbm_gb") == pytest.approx(5.0)
    # nothing to read: nothing returned, never a 0 for a share
    empty = dict(run, trace=None, memory_peak_bytes=0)
    for name in ("device_idle_pct", "host_ms_per_call", "programs_per_call",
                 "kernel_roofline", "peak_hbm_gb"):
        assert mf.load_module("layer_metrics", name).read(empty) is None


def _brute_busy(events, lo, hi):
    """Union length by sweeping the sorted end points."""
    points = []
    for _, s, d in events:
        s, e = max(s, lo), min(s + d, hi)
        if e > s:
            points += [(s, 1), (e, -1)]
    busy, depth, last = 0.0, 0, None
    for t, step in sorted(points):
        if depth > 0:
            busy += t - last
        depth, last = depth + step, t
    return busy


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(DATA, "trace_*.json"))))
def test_recorded_trace(path):
    with open(path) as fh:
        trace = json.load(fh)
    r = tr.reduce(trace)
    lo, hi, _ = tr._window(trace)
    devices = [p for p in trace["planes"] if p["name"].startswith("/device:")]
    assert len(r["devices"]) == len(devices) and r["calls"] >= 1
    for plane, got in zip(devices, r["devices"]):
        ops = next(ln["events"] for ln in plane["lines"] if ln["name"] == tr.OPS_LINE)
        assert got["busy_s"] == pytest.approx(_brute_busy(ops, lo, hi) * 1e-9, rel=1e-9)
        assert 0 < got["busy_s"] <= r["window_s"]
        assert sum(got["ops"].values()) >= got["busy_s"] * (1 - 1e-9)
        collective = sum(min(s + d, hi) - max(s, lo) for n, s, d in ops
                         if tr.COLLECTIVE.match(n) and min(s + d, hi) > max(s, lo))
        assert got["collective_s"] == pytest.approx(collective * 1e-9)
    idle = r["window_s"] - r["devices"][r["fullest"]]["busy_s"]
    assert sum(r["idle_gaps"].values()) == pytest.approx(idle, rel=1e-6)
    if "allgather" in path:
        # 4 ms of a four-chip TSQR call around its all-gather, which the
        # chip's trace names "async-collective-start"
        assert len(devices) == 4 and all(d["collective_s"] > 0 for d in r["devices"])
    else:
        assert all(d["programs"] >= r["calls"] for d in r["devices"])
