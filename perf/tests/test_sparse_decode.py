"""The decode cell of the DeepSeek-V3.2 share: its work models by hand, the
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
from perf.work_models import deepseek_decode, floor_seconds, sparse_read

CELL = "dsv32_decode_32k_b16"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def config():
    return mf.load_cell(mf.load_manifest(), CELL, False)["config"]


def test_parameters_by_hand():
    """ISSUE 34's arithmetic from the configuration's file."""
    n = deepseek_decode.parameters(config())
    assert n["mla"] == (7168 * 1536 + 1536 * 24576 + 7168 * 576 + 512 * 32768 + 16384 * 7168
                        + 1536 + 512) == 187_107_328
    assert n["indexer"] == 1536 * 8192 + 7168 * 128 + 7168 * 64 + 256 == 13_959_424
    assert n["attention"] == 201_066_752 and n["expert"] == 3 * 7168 * 2048 == 44_040_192
    assert n["dense_layer"] == 201_066_752 + 3 * 7168 * 18432 + 2 * 7168
    assert n["expert_layer"] == 201_066_752 + 2 * 7168 + 7168 * 256 + 256 + 17 * 44_040_192
    assert round(n["expert_layer"] / 1e6, 1) == 951.6
    assert round(n["expert_layer_uncut"] / 1e9, 2) == 11.52            # no chip holds one
    assert n["embed"] == n["head"] == 16160 * 7168
    assert n["total"] == 4_635_518_208 and round(2 * n["total"] / 1e9, 2) == 9.27
    assert config()["memory"].startswith("weights 4,635,518,208 parameters")


def test_caches_and_a_step_by_hand():
    cfg = config()
    assert deepseek_decode.cache_token_bytes(cfg) == 5 * (576 + 128) * 2 == 7040
    assert 16 * 32768 * 7040 == pytest.approx(3.69e9, rel=1e-3)
    shapes = sparse_read.of_config(cfg)
    assert set(shapes) == set(sparse_read.SHAPES)
    one = dict(shapes, steps=1)
    assert sparse_read.index_scan(one)["bytes"] == 16 * 5 * 32768 * 256
    assert sparse_read.index_scan(one)["bytes"] == pytest.approx(0.671e9, rel=1e-3)
    assert sparse_read.latent_rows(one)["bytes"] == 16 * 5 * 2048 * 1152
    assert sparse_read.latent_rows(one)["bytes"] == pytest.approx(0.189e9, rel=2e-3)
    assert sparse_read.expert_weights(one)["bytes"] == 4 * 16 * 88_080_384   # every held expert
    assert sparse_read.expert_weights(one)["bytes"] == pytest.approx(5.64e9, rel=1e-3)
    # a context shorter than the selection reads all of it
    assert sparse_read.latent_rows(dict(one, context=100))["bytes"] == 16 * 5 * 100 * 1152
    n = deepseek_decode.parameters(cfg)
    step = (2 * (n["total"] - n["embed"]) + 2 * 16 * 7168
            + 16 * 5 * 32768 * 256 + 16 * 5 * 2048 * 1152)
    assert deepseek_decode.step_bytes(cfg) == step
    assert step == pytest.approx(9.90e9, rel=1e-3)                       # ISSUE 34
    assert 4 * 16 * 88_080_384 / step == pytest.approx(0.57, abs=0.005)  # the held experts
    w = deepseek_decode.work(cfg, {}, 1)
    assert w["bytes"] == 12 * step
    secs, bound = floor_seconds(w, PEAKS)
    assert bound == "hbm" and secs / 12 == pytest.approx(12.1e-3, rel=5e-3)
    # the routed experts' products at the expected 1/16 of pairs: 0.5 expert a token and layer
    weights = n["total"] - n["embed"] - 64 * n["expert"] + 4 * 0.5 * n["expert"]
    caches = 5 * 32768 * 64 * 128 + 5 * 2048 * 128 * (2 * 512 + 64)
    assert w["flops"] == 12 * 16 * 2.0 * (weights + caches)
    assert w["flops"] / 197e12 < secs / 3                                # bound by bytes, not products


def test_the_cell_unharmed_is_correct_with_room():
    rc, result, err = run_cell(CELL, trace=1)
    assert rc == 0, err[-2000:]
    assert result["correct"] is True
    for name in ("logits_err", "latent_cache_err", "index_cache_err"):
        value, limit = result["check"][name]
        assert value < limit / 10
    assert result["check"]["selection_missed"] == [0.0, 0.0]
    assert result["check"]["cache_bytes_off"] == [0.0, 0]
    assert result["check"]["experts_held_off"] == [0.0, 0]
    info = result["window"]["info"]
    # 2 sessions x 256 positions held x 3 layers x (32 + 8 + 16) numbers x 4 B; no state to copy
    assert info["cache"] == {"bytes": {"shared": 2 * 256 * 3 * 56 * 4}, "capacity": 256,
                             "snapshot_bytes": 0}
    assert sum(len(v) for v in info["logits_err_by_session"].values()) == 6   # 2 sessions x 3 steps
    assert "check_s" in result["window"]


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
    ("weights_fp8", {"logits_err", "latent_cache_err", "index_cache_err"}),  # the stated control
    ("reads_newest", {"logits_err", "selection_missed"}),
    ("reads_all", {"logits_err"}),
    ("index_stale", {"logits_err", "selection_missed"}),
    ("bias_in_weights", {"logits_err"}),
    ("absent_experts_counted", {"logits_err"}),
])
def test_control_separates(operands, over):
    rc, lines, err = control_readings(operands)
    assert rc == 0, err[-2000:]
    program, control, verdict = lines
    assert program["who"] == "program" and program["correct"] is True
    assert control["who"] == "control" and control["correct"] is False
    assert over <= set(control["over"]), control
    assert verdict["separated"] is True
    # the exact numbers still hold: the fault is in the mathematics
    assert control["numbers"]["n_steps_off"] == 0 and control["numbers"]["tokens_not_argmax"] == 0


REWIND_KEEPS_THE_TOKEN = """
from heat_tpu.models import session
def _stays(self, snapshot):
    self.position = snapshot.position
session.DecodeSession.rewind = _stays
"""

ROPE_KEY_OF_THE_NEIGHBOUR = """
from heat_tpu.models import deepseek
from heat_tpu.ops import latent_attention as la
_rows = la.rope_rows
la.rope_rows = lambda cache, slots: _rows(cache, slots ^ 1)
"""

FAULTS = {
    "a_rewind_that_keeps_the_token_it_had": (REWIND_KEEPS_THE_TOKEN, ("logits_err",)),
    "the_rotary_key_of_the_neighbouring_position": (ROPE_KEY_OF_THE_NEIGHBOUR, ("logits_err",)),
}


@pytest.mark.parametrize("patch,over", list(FAULTS.values()), ids=list(FAULTS))
def test_fault_is_caught(patch, over):
    rc, result, err = run_cell(CELL, patch="import heat_tpu\n" + patch)
    assert rc == 0, err[-2000:]
    assert result["correct"] is False
    for number in over:
        value, limit = result["check"][number]
        assert not value <= limit, result["check"]


def test_roofline_readers_take_their_bytes_from_the_runs_own_decode_span(monkeypatch):
    """No cell's name in the readers: the newest ``lm.decode`` span says the
    shapes; a program without the layer (another model's span, or none) reads
    nothing and does not raise."""
    from heat_tpu.core import telemetry
    from perf.layer_metrics import (_sparse, latent_read_roofline, moe_experts_roofline,
                                    sparse_select_roofline)

    monkeypatch.setattr(_sparse.mf, "load_peaks", lambda kind: PEAKS)
    scopes = {"ht.lm.sparse_select": 0.5, "ht.lm.latent_read": 0.25, "ht.lm.moe_experts": 1.0}
    run = {"trace": {"calls": 2}, "span_reduce": {"calls": 2, "scopes": scopes}}
    readers = (sparse_select_roofline, latent_read_roofline, moe_experts_roofline)
    with telemetry.telemetry_level("events"):
        telemetry.clear_events()
        assert _sparse.decode_shapes() is None
        assert [r.read(dict(run)) for r in readers] == [None] * 3
        with telemetry.span("lm.decode", batch=16, context=8192, steps=16, layers=5,
                            state_bytes=1, kv_heads=8, head_dim=128):
            pass                                   # the retention model's span
        assert [r.read(dict(run)) for r in readers] == [None] * 3
        for batch in (16, 4):
            with telemetry.span("lm.decode", batch=batch, context=32768, steps=12, layers=5,
                                moe_layers=4, selected=2048, latent_bytes=1152, index_bytes=256,
                                experts_held=16, expert_bytes=88_080_384):
                pass
            floors = (12 * 5 * batch * 32768 * 256 / 819e9, 12 * 5 * batch * 2048 * 1152 / 819e9,
                      12 * 4 * 16 * 88_080_384 / 819e9)
            for reader, floor, secs in zip(readers, floors, scopes.values()):
                assert reader.read(dict(run)) == pytest.approx(100 * floor * 2 / secs)
        bare = {"trace": {"calls": 2}, "span_reduce": {"calls": 2, "scopes": {}}}
        assert [r.read(dict(bare)) for r in readers] == [None] * 3


def test_scope_readers_read_their_scopes():
    from perf.layer_metrics import (latent_attn_ms_per_call, moe_ms_per_call,
                                    sparse_select_ms_per_call)

    run = {"trace": {"calls": 4}, "span_reduce": {"calls": 4, "scopes": {
        "ht.lm.latent_attn": 0.4, "ht.lm.sparse_select": 0.1, "ht.lm.moe": 0.6}}}
    assert latent_attn_ms_per_call.read(dict(run)) == pytest.approx(100.0)
    assert sparse_select_ms_per_call.read(dict(run)) == pytest.approx(25.0)
    assert moe_ms_per_call.read(dict(run)) == pytest.approx(150.0)
    bare = {"trace": {"calls": 4}, "span_reduce": {"calls": 4, "scopes": {"ht.lm.mlp": 0.1}}}
    for reader in (latent_attn_ms_per_call, sparse_select_ms_per_call, moe_ms_per_call):
        assert reader.read(dict(bare)) is None
