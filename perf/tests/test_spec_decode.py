"""The drafting decode cell of GLM-4.7-Flash: its work model by hand, the
rehearsal of the cell on the CPU, and the control and the planted faults that
must make ``correct`` false at the rehearsal's toy size (float32 there, limits
1e-4: the configuration's ``rehearse_why``)."""

import json
import os
import subprocess
import sys

import pytest

from perf import manifest as mf
from perf.tests._util import ROOT, run_cell
from perf.work_models import floor_seconds, glm_decode

CELL = "glm47flash_mtp_decode_32k_b16"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def config():
    return mf.load_cell(mf.load_manifest(), CELL, False)["config"]


def test_parameters_by_hand():
    """The parameter arithmetic, from the configuration's file."""
    n = glm_decode.parameters(config())
    assert n["attention"] == (2048 * 768 + 768 * 20 * 256 + 2048 * 576 + 512 * 20 * 448
                              + 5120 * 2048 + 768 + 512) == 21_759_232
    assert n["expert"] == 3 * 2048 * 1536 == 9_437_184
    assert n["dense_layer"] == 84_677_888 and n["expert_layer"] == 635_311_424
    assert n["embed"] + n["head"] == 634_388_480
    assert n["mtp"] == 8_388_608 + 635_311_424 + 3 * 2048 == 643_706_176
    assert n["total"] == 3_904_020_288 and round(2 * n["total"] / 1e9, 2) == 7.81
    assert config()["memory"].startswith("weights 3,904,020,288 parameters")


def test_a_step_by_hand():
    cfg = config()
    part = glm_decode.step_bytes(cfg)
    assert part["experts"] == 2 * 5 * 65 * 9_437_184 == pytest.approx(6.13e9, rel=1e-3)
    # six layers' streams, every session at the shortest prompt's end and its two positions
    assert part["latent_stream"] == 6 * 16 * (30720 + 2) * 1152
    assert part["head"] == 2 * 2 * 154880 * 2048 == pytest.approx(1.27e9, rel=1e-3)
    assert part["rest"] == pytest.approx(0.405e9, rel=1e-2)
    step = sum(part.values())
    assert part["experts"] / step == pytest.approx(0.55, abs=0.01)
    w = glm_decode.work(cfg, {}, 1)
    assert w["bytes"] == 12 * step
    secs, bound = floor_seconds(w, PEAKS)
    assert bound == "hbm" and secs / 12 == pytest.approx(13.7e-3, rel=1e-2)
    # bound by bytes, not products: the full read's 40 query rows are 76 FLOP a byte of a row
    assert w["flops"] / 197e12 < secs / 4
    shapes = {"latent_rows_streamed": 12 * 6 * 16 * 32000, "latent_bytes": 1152}
    assert glm_decode.latent_stream(shapes)["bytes"] == 12 * 6 * 16 * 32000 * 1152


def test_the_cell_unharmed_is_correct_with_room():
    rc, result, err = run_cell(CELL, trace=1)
    assert rc == 0, err[-2000:]
    assert result["correct"] is True
    for name in ("logits_err", "draft_logits_err", "latent_cache_err", "mtp_latent_cache_err"):
        value, limit = result["check"][name]
        assert value < limit / 10
    for name in ("tokens_not_argmax", "positions_off", "cache_bytes_off"):
        assert result["check"][name] == [0.0, 0]
    info = result["window"]["info"]
    # 3 sessions x 256 positions held x 4 cached layers x (32 + 8) numbers x 4 B; no state to copy
    assert info["cache"] == {"bytes": {"shared": 3 * 256 * 4 * 40 * 4}, "capacity": 256,
                             "snapshot_bytes": 0}
    assert info["positions_distinct"] >= 2 and 0.0 <= info["acceptance"] <= 1.0
    assert all(20 <= n <= 40 for n in info["prompt_lengths"])


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
    ("weights_fp8", {"logits_err", "draft_logits_err", "latent_cache_err"}),  # the stated control
    ("mtp_fed_previous_h", {"draft_logits_err", "mtp_latent_cache_err"}),
    ("every_draft_accepted", {"logits_err", "tokens_not_argmax"}),
    ("stream_drops_newest", {"logits_err", "draft_logits_err"}),
    ("stale_draft_row", {"logits_err", "latent_cache_err"}),
])
def test_control_separates(operands, over):
    rc, lines, err = control_readings(operands)
    assert rc == 0, err[-2000:]
    program, control, verdict = lines
    assert program["who"] == "program" and program["correct"] is True
    assert control["who"] == "control" and control["correct"] is False
    assert over <= set(control["over"]), control
    assert verdict["separated"] is True


def test_the_stream_reader_takes_its_bytes_from_the_runs_own_decode_span(monkeypatch):
    """No cell's name in the reader: the newest ``lm.decode`` span that
    streamed says the rows; a program without the layer (another model's
    span, or none) reads nothing and does not raise."""
    from heat_tpu.core import telemetry
    from perf.layer_metrics import latent_stream_roofline

    monkeypatch.setattr(latent_stream_roofline.mf, "load_peaks", lambda kind: PEAKS)
    run = {"trace": {"calls": 2}, "span_reduce": {"calls": 2, "scopes": {
        "ht.lm.latent_stream": 0.5}}}
    with telemetry.telemetry_level("events"):
        telemetry.clear_events()
        assert latent_stream_roofline.read(dict(run)) is None
        with telemetry.span("lm.decode", batch=16, context=32768, steps=12, layers=5,
                            latent_bytes=1152, index_bytes=256):
            pass                                   # the sparse model's span: nothing streamed
        assert latent_stream_roofline.read(dict(run)) is None
        with telemetry.span("lm.decode", batch=16, steps=12, latent_bytes=1152) as span:
            span.note(latent_rows_streamed=1_000_000)
        want = 100 * 1_000_000 * 1152 / 819e9 * 2 / 0.5
        assert latent_stream_roofline.read(dict(run)) == pytest.approx(want)
        bare = {"trace": {"calls": 2}, "span_reduce": {"calls": 2, "scopes": {}}}
        assert latent_stream_roofline.read(dict(bare)) is None


def test_scope_readers_read_their_scopes():
    from perf.layer_metrics import latent_stream_ms_per_call, mtp_ms_per_call

    run = {"trace": {"calls": 4}, "span_reduce": {"calls": 4, "scopes": {
        "ht.lm.mtp": 0.2, "ht.lm.latent_stream": 0.1}}}
    assert mtp_ms_per_call.read(dict(run)) == pytest.approx(50.0)
    assert latent_stream_ms_per_call.read(dict(run)) == pytest.approx(25.0)
    bare = {"trace": {"calls": 4}, "span_reduce": {"calls": 4, "scopes": {"ht.lm.mlp": 0.1}}}
    for reader in (mtp_ms_per_call, latent_stream_ms_per_call):
        assert reader.read(dict(bare)) is None
