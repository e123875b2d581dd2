"""The block-diffusion decode cell of SDAR-30B-A3B-Chat: its work model by
hand, the rehearsal of the cell on the CPU, and the control and the planted
faults that must make ``correct`` false at the rehearsal's toy size (float32
there, limits 1e-4: the configuration's ``rehearse_why``)."""

import json
import os
import subprocess
import sys

import pytest

from perf import manifest as mf
from perf.tests._util import ROOT, run_cell
from perf.work_models import floor_seconds, sdar_decode

CELL = "sdar30b_bd4_decode_32k_b16"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def config():
    return mf.load_cell(mf.load_manifest(), CELL, False)["config"]


def test_parameters_by_hand():
    """The parameter arithmetic, from the configuration's file."""
    n = sdar_decode.parameters(config())
    assert n["attention"] == 2 * 2048 * 4096 + 2 * 2048 * 512 + 256 == 18_874_624
    assert n["expert"] == 3 * 2048 * 768 == 4_718_592
    assert n["layer"] == 18_874_624 + 2048 * 128 + 2 * 2048 + 128 * 4_718_592 == 623_120_640
    assert n["embed"] + n["head"] + 2048 == 622_331_904
    assert n["total"] == 3_737_935_104 and round(2 * n["total"] / 1e9, 2) == 7.48
    assert config()["memory"].startswith("weights 3,737,935,104 parameters")


def test_a_pass_and_a_call_by_hand():
    cfg = config()
    part = sdar_decode.pass_bytes(cfg)
    assert part["experts"] == 2 * 5 * 128 * 4_718_592 == pytest.approx(6.04e9, rel=1e-3)
    # five layers' streams, every session at the shortest prompt's end and its block
    assert part["kv_stream"] == 5 * 16 * (30720 + 4) * 2048
    assert part["head"] == 2 * 151936 * 2048 == pytest.approx(0.622e9, rel=1e-3)
    assert part["rest"] == pytest.approx(0.19e9, rel=2e-2)
    step = sum(part.values())
    assert part["experts"] / step == pytest.approx(0.51, abs=0.01)
    commit = sdar_decode.commit_bytes(cfg)
    assert "head" not in commit and commit["experts"] == part["experts"] * 4 / 5
    w = sdar_decode.work(cfg, {}, 1)
    shapes = sdar_decode.of_config(cfg)
    seen = sum(30720 + 4 * j for j in range(1, 7))
    assert shapes == {"kv_rows_streamed": (2 * 5 + 4) * 16 * seen, "row_bytes": 2048}
    streams = 6 * (2 * part["kv_stream"] + commit["kv_stream"])
    assert w["bytes"] == pytest.approx(6 * (2 * step + sum(commit.values())), rel=1e-3)
    assert w["bytes"] - 6 * (2 * step + sum(commit.values())) == pytest.approx(
        sdar_decode.kv_stream(shapes)["bytes"] - streams)
    secs, bound = floor_seconds(w, PEAKS)
    # a block, two passes and a commit, some 34 GB: 42 ms at the HBM peak
    assert bound == "hbm" and secs / 6 == pytest.approx(0.041, rel=0.05)
    assert w["flops"] / 197e12 < secs / 4


def test_the_cell_unharmed_is_correct_with_room():
    rc, result, err = run_cell(CELL, trace=1)
    assert rc == 0, err[-2000:]
    assert result["correct"] is True
    for name in ("logits_err", "logits_err_mean", "confidence_err", "kv_cache_err"):
        value, limit = result["check"][name]
        assert value < limit / 10
    for name in ("tokens_not_argmax", "unmask_not_topk", "prompt_changed", "positions_off",
                 "cache_bytes_off"):
        assert result["check"][name] == [0.0, 0]
    info = result["window"]["info"]
    # 3 sessions x 64 positions held x 2 layers x a key and a value of 2 x 16 lanes x 4 B
    assert info["cache"] == {"bytes": {"shared": 3 * 64 * 2 * 256}, "capacity": 64,
                             "snapshot_bytes": 0}
    assert all(40 <= n <= 52 for n in info["prompt_lengths"])
    assert [p - (n - n % 4) for p, n in zip(info["positions"], info["prompt_lengths"])] == [12] * 3


def control_readings(operands):
    code = ("import sys; sys.path.insert(0, %r); from perf import control; "
            "sys.exit(control.main(['--workload', %r, '--seeds', '21', '--control-seeds', '21', "
            "'--control-operands', %r, '--rehearse-cpu']))" % (ROOT, CELL, operands))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, cwd=ROOT,
                          env=env, timeout=900)
    lines = [json.loads(ln) for ln in proc.stdout.splitlines() if ln.startswith("{")]
    return proc.returncode, lines, proc.stderr


@pytest.mark.parametrize("operands,over", [
    ("weights_fp8", {"logits_err", "confidence_err", "kv_cache_err"}),  # the stated control
    ("causal_in_block", {"logits_err", "confidence_err", "kv_cache_err"}),
    ("no_commit", {"logits_err", "kv_cache_err"}),
])
def test_control_separates(operands, over):
    rc, lines, err = control_readings(operands)
    assert rc == 0, err[-2000:]
    program, control, verdict = lines
    assert program["who"] == "program" and program["correct"] is True
    assert control["who"] == "control" and control["correct"] is False
    assert over <= set(control["over"]), control
    assert verdict["separated"] is True


def test_the_stream_reader_takes_its_rows_from_the_runs_own_decode_span(monkeypatch):
    """No cell's name in the reader: the newest ``lm.decode`` span that
    carries a block model's rows says them; a program without the layer
    (another model's span, or none) reads nothing and does not raise."""
    from heat_tpu.core import telemetry
    from perf.layer_metrics import block_attn_roofline

    monkeypatch.setattr(block_attn_roofline.mf, "load_peaks", lambda kind: PEAKS)
    run = {"trace": {"calls": 2}, "span_reduce": {"calls": 2, "scopes": {
        "ht.lm.block_attn": 0.5}}}
    with telemetry.telemetry_level("events"):
        telemetry.clear_events()
        assert block_attn_roofline.read(dict(run)) is None
        with telemetry.span("lm.decode", batch=16, steps=12, latent_bytes=1152):
            pass                                   # the drafting model's span: no key/value rows
        assert block_attn_roofline.read(dict(run)) is None
        with telemetry.span("lm.decode", batch=16, blocks=6, row_bytes=2048,
                            kv_rows_streamed=1_000_000):
            pass
        want = 100 * 1_000_000 * 2048 / 819e9 * 2 / 0.5
        assert block_attn_roofline.read(dict(run)) == pytest.approx(want)
        bare = {"trace": {"calls": 2}, "span_reduce": {"calls": 2, "scopes": {}}}
        assert block_attn_roofline.read(dict(bare)) is None


def test_scope_readers_read_their_scopes():
    from perf.layer_metrics import block_attn_ms_per_call, commit_ms_per_call

    run = {"trace": {"calls": 4}, "span_reduce": {"calls": 4, "scopes": {
        "ht.lm.block_attn": 0.2, "ht.lm.commit": 0.1}}}
    assert block_attn_ms_per_call.read(dict(run)) == pytest.approx(50.0)
    assert commit_ms_per_call.read(dict(run)) == pytest.approx(25.0)
    bare = {"trace": {"calls": 4}, "span_reduce": {"calls": 4, "scopes": {"ht.lm.mlp": 0.1}}}
    for reader in (block_attn_ms_per_call, commit_ms_per_call):
        assert reader.read(dict(bare)) is None
