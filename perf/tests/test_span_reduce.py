"""The reduction from a trace to the program's own spans and scopes, on
hand-made traces whose answers can be worked out on paper and on a recorded
one (a v5e ``kmeans_fit`` trace cut down by ``make_span_sample.py``)
cross-checked against ``trace_reduce``."""

import json
import os

import pytest

from perf import span_reduce as sr
from perf import trace_reduce as tr
from perf.layer_metrics import (
    autotune_self_ms_per_call,
    fusion_self_ms_per_call,
    host_syncs_per_call,
    lloyd_ms_per_call,
    sync_idle_ms_per_call,
)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = (host_syncs_per_call, sync_idle_ms_per_call, fusion_self_ms_per_call,
           autotune_self_ms_per_call, lloyd_ms_per_call)


def hand_made():
    """Window 0..1000 ns, two calls.  The device runs a Lloyd ``while`` over
    100..400 whose body's two operations lie inside it, a fused operation over
    450..500, and in the second call a ``while`` over 600..700.  The host waits
    in a sync span over 300..440 (with the runtime's own readback event inside
    it) and in another over 720..900."""
    lloyd = "jit(_lloyd_loop)/jit(main)/ht.kmeans.lloyd/while"
    body = lloyd + "/body/closed_call/jit(_lloyd_step)/"
    host = {"name": "python3", "events": [
        [tr.WINDOW, 0.0, 1000.0],
        [tr.CALL, 50.0, 450.0],
        ["ht:kmeans.fit", 60.0, 400.0],            # 60..460
        ["ht:kmeans.init", 70.0, 20.0],            # 70..90, sibling of the next two
        ["PjitFunction(_lloyd_loop)", 95.0, 10.0],  # the runtime's, not a program span
        ["ht:sync:kmeans.n_iter", 300.0, 140.0],   # 300..440
        ["np.asarray(jax.Array)", 310.0, 125.0],   # the runtime's event inside it
        ["ht:kmeans.labels", 440.0, 15.0],         # 440..455
        ["ht:fusion.materialize", 470.0, 25.0],    # 470..495, outside kmeans.fit
        ["ht:sync:guard.flag", 480.0, 10.0],       # 480..490, child of materialize
        [tr.CALL, 550.0, 400.0],
        ["ht:kmeans.fit", 560.0, 380.0],           # 560..940
        ["ht:sync:kmeans.n_iter", 720.0, 180.0],   # 720..900
        ["ht:sync:late", 990.0, 50.0],             # closes after the window
    ]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": tr.OPS_LINE, "events": [
            ["%while.2", 100.0, 300.0, lloyd],
            ["%fusion.15", 110.0, 100.0, body + "ht.kmeans.assign/ht.cdist/dot_general"],
            ["%fusion.6", 220.0, 150.0, body + "ht.kmeans.update/dot_general"],
            ["%fusion.2", 450.0, 50.0, "jit(program)/jit(main)/ht.fused/euclid_cdist/ht.cdist/sub"],
            ["%while.2", 600.0, 100.0, lloyd],
            ["%copy.1", 960.0, 20.0, ""],
        ]},
        {"name": tr.MODULES_LINE, "events": [["jit__lloyd_loop(1)", 100.0, 300.0]]},
    ]}
    return {"planes": [dev, {"name": "/host:CPU", "lines": [host]}]}


def three(trace):
    """The trace as ``trace_reduce`` reads it: three fields an event."""
    return {"planes": [
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": [ev[:3] for ev in ln["events"]]}
            for ln in p["lines"]]}
        for p in trace["planes"]]}


def test_self_time_with_nested_and_sibling_spans():
    got = sr.reduce(hand_made())
    assert got["calls"] == 2 and got["window_s"] == pytest.approx(1000e-9)
    fit = got["spans"]["ht:kmeans.fit"]
    assert fit["count"] == 2 and fit["total_s"] == pytest.approx(780e-9)
    # first: 400 - init 20 - sync 140 - labels 15 = 225; second: 380 - sync 180 = 200
    assert fit["self_s"] == pytest.approx(425e-9)
    assert got["spans"]["ht:kmeans.init"]["self_s"] == pytest.approx(20e-9)
    mat = got["spans"]["ht:fusion.materialize"]
    assert mat["total_s"] == pytest.approx(25e-9) and mat["self_s"] == pytest.approx(15e-9)
    # the runtime's events are no program spans
    assert not [k for k in got["spans"] if not k.startswith(sr.PREFIX)]
    # a span cut by the window's end counts what lies inside: 990..1000
    assert got["spans"]["ht:sync:late"]["total_s"] == pytest.approx(10e-9)


def test_idle_goes_to_the_innermost_program_span():
    trace = hand_made()
    got = sr.reduce(trace)
    # busy: 100..400, 450..500, 600..700, 960..980; idle: 0..100, 400..450,
    # 500..600, 700..960, 980..1000
    assert got["busy_s"] == pytest.approx(470e-9) and got["idle_s"] == pytest.approx(530e-9)
    spans = got["spans"]
    # 400..440 lies in the sync span, although the runtime's readback event
    # inside it ended at 435 and is the innermost event of any kind before
    assert spans["ht:sync:kmeans.n_iter"]["idle_s"] == pytest.approx((40 + 180) * 1e-9)
    assert spans["ht:sync:kmeans.n_iter"]["max_gap_s"] == pytest.approx(180e-9)
    assert spans["ht:kmeans.labels"]["idle_s"] == pytest.approx(10e-9)   # 440..450
    # kmeans.fit's own: 60..70, 90..100, then 560..600, 700..720, 900..940
    assert spans["ht:kmeans.fit"]["idle_s"] == pytest.approx(120e-9)
    assert spans["ht:sync:late"]["idle_s"] == pytest.approx(10e-9)
    assert got["sync_idle_s"] == pytest.approx(230e-9)
    # outside any program span: 0..60, 500..560, 940..960, 980..990
    assert got["idle_outside_s"] == pytest.approx(150e-9)
    total = sum(v["idle_s"] for v in spans.values()) + got["idle_outside_s"]
    assert total == pytest.approx(got["idle_s"], abs=1e-15)
    # trace_reduce gives the same idle time, and gives the first sync's part
    # to the runtime's event inside it
    outside = tr.reduce(three(trace))
    assert outside["window_s"] - outside["devices"][0]["busy_s"] == pytest.approx(got["idle_s"])
    assert "np.asarray(jax.Array)" in outside["idle_gaps"]


def test_sync_spans_are_counted_where_they_close():
    got = sr.reduce(hand_made())
    assert got["syncs"] == 3            # ht:sync:late closes after the window
    run = {"trace": {"calls": 2}, "span_reduce": got}
    assert host_syncs_per_call.read(run) == pytest.approx(1.5)
    assert sync_idle_ms_per_call.read(run) == pytest.approx(230e-6 / 2)
    assert fusion_self_ms_per_call.read(run) == pytest.approx(15e-6 / 2)
    assert autotune_self_ms_per_call.read(run) == 0.0   # never entered: 0, not None


def test_union_under_a_scope_with_a_while_and_its_body():
    got = sr.reduce(hand_made())
    scopes = got["scopes"]
    # the while operations cover 100..400 and 600..700; the body's are inside
    assert scopes["ht.kmeans.lloyd"] == pytest.approx(400e-9)
    assert scopes["ht.kmeans.assign"] == pytest.approx(100e-9)
    assert scopes["ht.kmeans.update"] == pytest.approx(150e-9)
    # ht.cdist: once under the Lloyd step, once under the fused program
    assert scopes["ht.cdist"] == pytest.approx(150e-9)
    assert scopes["ht.fused"] == scopes["ht.fused/euclid_cdist"] == pytest.approx(50e-9)
    assert lloyd_ms_per_call.read({"trace": {"calls": 2}, "span_reduce": got}) == \
        pytest.approx(400e-6 / 2)
    assert sr.scopes_of("jit(f)/jit(main)/while/body/add") == []


def test_no_scope_path_no_lloyd_metric():
    got = sr.reduce(three(hand_made()))
    assert got["scopes"] == {} and got["syncs"] == 3
    assert lloyd_ms_per_call.read({"trace": {"calls": 2}, "span_reduce": got}) is None


def write_trace(tmp_path, monkeypatch, trace):
    """Lay ``trace`` where a run would have written it; ``load_xplane`` reads
    the plain data back."""
    where = tmp_path / "trace" / "cell" / "plugins" / "profile" / "t0"
    where.mkdir(parents=True)
    path = where / "vm.xplane.pb"
    path.write_text(json.dumps(trace))
    monkeypatch.setattr(sr, "newest_xplane", lambda root=None: str(path))
    monkeypatch.setattr(sr, "load_xplane", lambda p: json.load(open(p)))


def test_none_when_the_trace_is_not_this_runs(tmp_path, monkeypatch):
    write_trace(tmp_path, monkeypatch, hand_made())
    assert sr.for_run({"trace": None}) is None                 # the CPU rehearsal
    assert sr.for_run({"trace": {"calls": 3}}) is None         # another run's file
    run = {"trace": {"calls": 2}}
    assert sr.for_run(run)["syncs"] == 3
    assert run["span_reduce"] is sr.for_run(run)               # reduced once a run
    for reader in READERS:
        assert reader.read({"trace": None}) is None
        assert reader.read({"trace": {"calls": 3}}) is None
        assert reader.read({"trace": {"calls": 2}}) is not None


def test_none_without_a_window_or_without_program_spans(tmp_path, monkeypatch):
    trace = hand_made()
    host = trace["planes"][1]["lines"][0]
    # a program from before PR 25: its spans have no prefix
    before = [[ev[0].replace(sr.PREFIX, "", 1), *ev[1:]] for ev in host["events"]]
    trace["planes"][1]["lines"][0] = {"name": "python3", "events": before}
    write_trace(tmp_path, monkeypatch, trace)
    for reader in READERS:
        assert reader.read({"trace": {"calls": 2}}) is None
    host["events"] = [ev for ev in host["events"] if ev[0] != tr.WINDOW]
    trace["planes"][1]["lines"][0] = host
    (tmp_path / "again").mkdir()
    write_trace(tmp_path / "again", monkeypatch, trace)
    assert sr.for_run({"trace": {"calls": 2}}) is None


def test_no_trace_directory(tmp_path):
    assert sr.newest_xplane(str(tmp_path)) is None


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    """A protobuf message from ``(number, int | bytes | str)`` fields."""
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += _varint(number << 3) + _varint(value)
        else:
            data = value.encode() if isinstance(value, str) else value
            out += _varint(number << 3 | 2) + _varint(len(data)) + data
    return out


def test_scope_paths_from_the_files_own_bytes(tmp_path, monkeypatch):
    """A hand-encoded XSpace: the scope path is a stat of an event's metadata,
    given as a string or as a reference to a stat's name."""
    stats_md = [_msg((1, 7), (2, _msg((1, 7), (2, "tf_op")))),
                _msg((1, 8), (2, _msg((1, 8), (2, "jit(f)/ht.b/mul:")))),
                _msg((1, 9), (2, _msg((1, 9), (2, "flops"))))]
    events_md = [
        _msg((1, 3), (2, _msg((1, 3), (2, "%fusion.1"), (5, _msg((1, 9), (3, 12))),
                              (5, _msg((1, 7), (5, "jit(f)/ht.a/add:")))))),
        _msg((1, 4), (2, _msg((1, 4), (2, "%copy.2")))),
        _msg((1, 300), (2, _msg((1, 300), (2, "%fusion.3"), (5, _msg((1, 7), (7, 8)))))),
    ]
    ops = _msg((1, 1), (2, tr.OPS_LINE), (3, 1000),
               (4, _msg((1, 3), (2, 500), (3, 2000))), (4, _msg((1, 4), (2, 3000), (3, 10))),
               (4, _msg((1, 300), (2, 4000), (3, 10))), (4, _msg((1, 3), (2, 5000), (3, 2000))))
    steps = _msg((1, 2), (2, "Steps"), (4, _msg((1, 4), (2, 0), (3, 1))))
    device = _msg((1, 1), (2, "/device:TPU:0"), (3, steps), (3, ops),
                  *[(4, m) for m in events_md], *[(5, m) for m in stats_md])
    host = _msg((1, 2), (2, "/host:CPU"), (3, _msg((2, "python3"))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, device), (1, host)))
    want = [("%fusion.1", "jit(f)/ht.a/add:"), ("%copy.2", ""),
            ("%fusion.3", "jit(f)/ht.b/mul:"), ("%fusion.1", "jit(f)/ht.a/add:")]
    assert sr.scope_paths(str(path)) == {"/device:TPU:0": want}

    def plain(names):
        return {"planes": [{"name": "/device:TPU:0", "lines": [
            {"name": tr.OPS_LINE, "events": [[n, 0.0, 1.0] for n in names]}]}]}

    monkeypatch.setattr(tr, "load_xplane", lambda p: plain([n for n, _ in want]))
    events = sr.load_xplane(str(path))["planes"][0]["lines"][0]["events"]
    assert [ev[3] for ev in events] == ["jit(f)/ht.a/add", "", "jit(f)/ht.b/mul", "jit(f)/ht.a/add"]
    # events that do not pair up with the file's by name keep three fields
    monkeypatch.setattr(tr, "load_xplane", lambda p: plain(["%a", "%b", "%c", "%d"]))
    assert all(len(ev) == 3 for ev in sr.load_xplane(str(path))["planes"][0]["lines"][0]["events"])


SYNC_COUNTER = """
from perf import run as _run
_run.PERF_DIR = {tmp!r}   # the trace goes there; the manifest keeps its own
_read = _run.Counters.read
def _with_syncs(self):
    from heat_tpu.core import telemetry
    return dict(_read(self), sync_count=telemetry.snapshot_group("sync")["count"])
_run.Counters.read = _with_syncs
"""


@pytest.mark.parametrize("cell", ["kmeans_fit", "qr_tall"])
def test_sync_spans_equal_the_programs_sync_counter(cell, tmp_path):
    """A traced rehearsal: as many ``ht:sync:*`` spans close inside the window
    as the program's ``sync`` group counted over it, and the program's spans
    nest under the call's."""
    from perf.tests._util import run_cell

    rc, result, err = run_cell(cell, trace=1, patch=SYNC_COUNTER.format(tmp=str(tmp_path)))
    assert rc == 0, err[-2000:]
    path = sr.newest_xplane(str(tmp_path / "out" / "trace"))
    got = sr.reduce(sr.load_xplane(path))
    assert got["calls"] == result["window"]["calls"] >= 1
    assert got["syncs"] == result["window"]["counters"]["sync_count"] >= got["calls"]
    top = "ht:kmeans.fit" if cell == "kmeans_fit" else "ht:linalg.qr"
    assert got["spans"][top]["count"] == got["calls"]
    assert got["scopes"] == {} and got["busy_s"] == 0.0   # no device plane on the CPU
    assert got["idle_s"] == pytest.approx(got["window_s"])


SAMPLE = os.path.join(DATA, "spans_kmeans_fit.json")


@pytest.mark.skipif(not os.path.exists(SAMPLE), reason="no recorded sample")
def test_recorded_kmeans_fit_sample():
    with open(SAMPLE) as fh:
        trace = json.load(fh)
    got = sr.reduce(trace)
    outside = tr.reduce(three(trace))
    calls = got["calls"]
    assert calls == outside["calls"] >= 2
    busy = outside["devices"][outside["fullest"]]["busy_s"]
    assert got["busy_s"] == pytest.approx(busy, abs=1e-9)
    total = sum(v["idle_s"] for v in got["spans"].values()) + got["idle_outside_s"]
    assert total == pytest.approx(outside["window_s"] - busy, abs=1e-6)
    sites = {k[len(sr.SYNC):] for k in got["spans"] if k.startswith(sr.SYNC)}
    assert {"kmeans.n_iter", "kmeans.inertia", "guard.flag"} <= sites
    assert got["syncs"] >= 3 * calls
    # self times of one thread's spans and the time outside them fill the window
    selfs = sum(v["self_s"] for v in got["spans"].values())
    assert selfs <= got["window_s"]
    fit = got["spans"]["ht:kmeans.fit"]
    assert fit["count"] == calls and fit["self_s"] < fit["total_s"]
    # the Lloyd loop is most of the device's time, and contains both stages
    lloyd = got["scopes"]["ht.kmeans.lloyd"]
    assert 0.5 * busy < lloyd <= busy
    assert got["scopes"]["ht.kmeans.assign"] + got["scopes"]["ht.kmeans.update"] <= lloyd * 1.001
