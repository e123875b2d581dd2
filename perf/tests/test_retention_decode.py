"""The decode cell of the power-retention model: its work models by hand, the
rehearsal of the cell on the CPU, and the control and planted faults that must
make ``correct`` false at the rehearsal's toy size (float32 there, limits
1e-4: the configuration's ``rehearse_why``)."""

import json
import os
import subprocess
import sys

import pytest

from perf import manifest as mf
from perf.tests._util import ROOT, run_cell
from perf.work_models import brumby_decode, floor_seconds, retention_state

CELL = "brumby_decode_8k_b16"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def config():
    return mf.load_cell(mf.load_manifest(), CELL, False)["config"]


def test_parameters_by_hand():
    """ISSUE 32's arithmetic: the published config and the assumed gate."""
    n = brumby_decode.parameters(config())
    assert n["mixer"] == 2 * 5120 * 5120 + 2 * 5120 * 1024 + 5120 * 8 + 8 + 256
    assert n["layer"] == n["mixer"] + 3 * 5120 * 17408 + 2 * 5120 == 330_352_904
    assert n["embed"] == n["head"] == 151936 * 5120
    assert n["total"] == 5 * n["layer"] + 2 * 151936 * 5120 + 5120 == 3_207_594_280
    assert round(2 * n["total"] / 1e9, 3) == 6.415
    whole = brumby_decode.parameters(dict(config(), num_hidden_layers=40))["total"]
    assert whole == 14_769_945_920                                       # 14.77 B


def test_state_by_hand():
    cfg = config()
    head = retention_state.head_state_bytes(128)
    assert head == 8256 * 129 * 4                      # 128*129/2 features x (128 values + z) x 4 B
    assert 8 * head == pytest.approx(34.08e6, rel=1e-3)                  # a layer and session
    assert brumby_decode.state_bytes(cfg) == 16 * 5 * 8 * head
    assert brumby_decode.state_bytes(cfg) == pytest.approx(2.726e9, rel=1e-3)
    # what the program's layout holds is stated by the configuration, not counted
    held = 16 * 5 * 8 * cfg["assumed"]["sizes"]["state_rows"] * 129 * 4
    assert held == pytest.approx(2.748e9, rel=1e-3) and held > brumby_decode.state_bytes(cfg)
    w = retention_state.work(cfg, {}, 1)
    assert w["bytes"] == 16 * 2 * brumby_decode.state_bytes(cfg)         # 16 steps, read and written
    said = dict(steps=16, layers=5, batch=16, kv_heads=8, head_dim=128)
    assert set(said) == set(retention_state.SHAPES)
    assert retention_state.step_work(**said)["bytes"] == w["bytes"]
    assert retention_state.step_work(**dict(said, batch=8, steps=32))["bytes"] == w["bytes"]
    assert floor_seconds(w, PEAKS) == (pytest.approx(w["bytes"] / 819e9), "hbm")


def test_decode_call_by_hand():
    cfg = config()
    n = brumby_decode.parameters(cfg)
    step = 2 * (n["total"] - n["embed"]) + 2 * 16 * 5120 + 2 * brumby_decode.state_bytes(cfg)
    assert brumby_decode.step_bytes(cfg) == step
    assert step == pytest.approx(10.31e9, rel=1e-3)                      # ISSUE 32
    assert 2 * brumby_decode.state_bytes(cfg) / step == pytest.approx(0.53, abs=0.005)
    assert 2 * n["head"] / step == pytest.approx(0.15, abs=0.005)        # the head, at five layers
    w = brumby_decode.work(cfg, {}, 1)
    assert w["bytes"] == 16 * step + 2 * brumby_decode.state_bytes(cfg)  # and the rewind's copy
    secs, bound = floor_seconds(w, PEAKS)
    assert bound == "hbm" and secs == pytest.approx(0.2081, rel=1e-3)


def test_the_cell_unharmed_is_correct_with_room():
    rc, result, err = run_cell(CELL, trace=1)
    assert rc == 0, err[-2000:]
    assert result["correct"] is True
    for name in ("logits_err", "state_err", "stepped_state_err"):
        value, limit = result["check"][name]
        assert value < limit / 10
    assert result["check"]["state_bytes_off"] == [0.0, 0]
    # 2 sessions x 3 layers x 2 heads x 144 rows held x 17 x 4 B, and the snapshot as much
    cache = result["window"]["info"]["cache"]
    assert cache["bytes"]["state"] == cache["snapshot_bytes"] == 2 * 3 * 2 * 144 * 17 * 4


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
    ("state_bf16", {"state_bf16_share"}),        # the workload's stated control
    ("weights_fp8", {"logits_err"}),
    ("state_stale", {"logits_err", "state_err", "stepped_state_err"}),
])
def test_control_separates(operands, over):
    rc, lines, err = control_readings(operands)
    assert rc == 0, err[-2000:]
    program, control, verdict = lines
    assert program["who"] == "program" and program["correct"] is True
    assert control["who"] == "control" and control["correct"] is False
    assert over <= set(control["over"]), control
    assert verdict["separated"] is True


PREFILL_CHUNK_DROPPED = """
import jax, jax.numpy as jnp
from heat_tpu.models import brumby
_chunk = brumby._prefill_chunk
def _skips_the_second(cfg, params, state, tokens, pos0, **kw):
    if int(pos0) == brumby.PREFILL_CHUNK:
        return state, _chunk(cfg, params, jax.tree.map(jnp.copy, state), tokens, pos0, **kw)[1]
    return _chunk(cfg, params, state, tokens, pos0, **kw)
brumby.PREFILL_CHUNK = 16
brumby._prefill_chunk = _skips_the_second
"""

REWIND_RESTORES_NOTHING = """
from heat_tpu.models import session
def _stays(self, snapshot):
    self.position = snapshot.position
session.DecodeSession.rewind = _stays
"""

NEWEST_KEY_NOT_WRITTEN = """
import jax.numpy as jnp
from heat_tpu.models import brumby
_step = brumby.retention_step
brumby.retention_step = lambda S, z, q, k, *a, **kw: _step(S, z, q, jnp.zeros_like(k), *a, **kw)
"""

NORMALISER_NOT_CARRIED = """
import jax.numpy as jnp
from heat_tpu.models import brumby
_step = brumby.retention_step
brumby.retention_step = lambda S, z, *a, **kw: _step(S, jnp.zeros_like(z), *a, **kw)
"""

STEP_KEEPS_BF16 = """
import jax
from heat_tpu.models import brumby
_step = brumby.retention_step
def _rounds(*a, **kw):
    y, S, z = _step(*a, **kw)
    return y, jax.lax.reduce_precision(S, 8, 7), jax.lax.reduce_precision(z, 8, 7)
brumby.retention_step = _rounds
"""

# the numbers that refuse each fault at the cell's size too (PERF.md section 2:
# at the published widths the logits see the state too faintly to refuse any)
FAULTS = {
    "a_prefill_chunk_dropped": (PREFILL_CHUNK_DROPPED, ("state_err", "stepped_state_err")),
    "rewind_restores_nothing": (REWIND_RESTORES_NOTHING, ("stepped_state_err",)),
    "decode_steps_do_not_write_their_key": (NEWEST_KEY_NOT_WRITTEN, ("stepped_state_err",)),
    "normaliser_not_carried_between_steps": (NORMALISER_NOT_CARRIED, ("stepped_state_err",)),
    "the_step_keeps_its_state_in_bfloat16": (STEP_KEEPS_BF16, ("stepped_state_bf16_share",)),
}


@pytest.mark.parametrize("patch,over", list(FAULTS.values()), ids=list(FAULTS))
def test_fault_is_caught(patch, over):
    rc, result, err = run_cell(CELL, patch="import heat_tpu\n" + patch)
    assert rc == 0, err[-2000:]
    assert result["correct"] is False
    for number in over:
        value, limit = result["check"][number]
        assert not value <= limit, result["check"]
    # the exact numbers still hold: the fault is in the mathematics
    assert result["check"]["n_steps_off"][0] == 0 and result["check"]["tokens_not_argmax"][0] == 0


def test_roofline_reader_takes_its_bytes_from_the_runs_own_decode_span(monkeypatch):
    """No cell's name in the reader: the newest ``lm.decode`` span says the
    shapes; a program without the layer (another model's span, or none) reads
    nothing and does not raise."""
    from heat_tpu.core import telemetry
    from perf.layer_metrics import retention_state_roofline as reader

    monkeypatch.setattr(reader.mf, "load_peaks", lambda kind: PEAKS)
    run = {"trace": {"calls": 2}, "span_reduce": {"calls": 2, "scopes": {reader.SCOPE: 0.5}}}
    with telemetry.telemetry_level("events"):
        telemetry.clear_events()
        assert reader.decode_shapes() is None and reader.read(dict(run)) is None
        with telemetry.span("lm.decode", batch=8, context=32768, steps=8, readers=8,
                            token_bytes=5120):
            pass                                   # SambaY's span: no state to step
        assert reader.read(dict(run)) is None
        for batch in (16, 4):
            with telemetry.span("lm.decode", batch=batch, context=8192, steps=16, layers=5,
                                state_bytes=1, kv_heads=8, head_dim=128):
                pass
            floor = 16 * 5 * batch * 8 * 2 * 8256 * 129 * 4 / 819e9
            assert reader.read(dict(run)) == pytest.approx(100 * floor * 2 / 0.5)
        assert reader.read({"trace": {"calls": 2}, "span_reduce": {"calls": 2, "scopes": {}}}) is None


def test_scope_readers_read_their_scopes():
    from perf.layer_metrics import retention_ms_per_call, state_copy_ms_per_call

    run = {"trace": {"calls": 4}, "span_reduce": {"calls": 4, "scopes": {
        "ht.lm.retention": 0.4, "ht.lm.retention_state": 0.3, "ht.lm.state_copy": 0.02}}}
    assert retention_ms_per_call.read(dict(run)) == pytest.approx(100.0)
    assert state_copy_ms_per_call.read(dict(run)) == pytest.approx(5.0)
    bare = {"trace": {"calls": 4}, "span_reduce": {"calls": 4, "scopes": {"ht.lm.mlp": 0.1}}}
    assert retention_ms_per_call.read(dict(bare)) is None
    assert state_copy_ms_per_call.read(dict(bare)) is None
