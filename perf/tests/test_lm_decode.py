"""The decode cell of the SambaY language model: its work models by hand, and
planted faults that must make ``correct`` false at the rehearsal's toy size
(float32 there, limits 1e-4: the configuration's ``rehearse_why``)."""

import pytest

from perf import manifest as mf
from perf.tests._util import run_cell
from perf.work_models import floor_seconds, sambay_decode, shared_kv_read

CELL = "phi4flash_decode_32k"
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def config():
    return mf.load_cell(mf.load_manifest(), CELL, False)["config"]


def test_parameters_by_hand():
    """ISSUE 27's table: the published config and the assumed Mamba sizes."""
    cfg = config()
    n = sambay_decode.parameters(cfg)
    assert sambay_decode.layer_kinds(cfg) == (["mamba", "window"] * 8 + ["mamba", "full"]
                                              + ["gmu", "cross"] * 7)
    assert n["mlp"] == 3 * 2560 * 10240                                   # 78.6M
    norms = 4 * 2560
    assert n["window"] == n["full"] == 2560 * 5120 + 2560 * 2560 + 384 + n["mlp"] + norms
    assert n["cross"] == 2 * 2560 * 2560 + 384 + n["mlp"] + norms
    assert n["gmu"] == 2 * 2560 * 5120 + n["mlp"] + norms
    assert n["mamba"] == (2560 * 10240 + 5120 * 2560 + 5120 * 192 + 160 * 5120 + 5120
                          + 16 * 5120 + 4 * 5120 + 5120 + 5120 + n["mlp"] + norms)
    assert n["embed"] == 200064 * 2560
    assert n["total"] == (9 * n["mamba"] + 8 * n["window"] + n["full"] + 7 * n["gmu"]
                          + 7 * n["cross"] + n["embed"] + 2 * 2560)
    assert round(2 * n["total"] / 1e9, 2) == 7.70


def test_shared_cache_read_by_hand():
    cfg = config()
    assert shared_kv_read.readers(cfg) == 8            # layer 17 and the seven cross layers
    assert shared_kv_read.token_bytes(cfg) == 5120     # 20 heads x 64 x (k, v) x 2 B, stored once
    w = shared_kv_read.work(cfg, {}, 1)
    assert w["bytes"] == 8 * 8 * 8 * 32768 * 5120      # steps x readers x sessions x context x B
    # the same bytes from the shapes a run's decode span says, whatever the cell
    said = dict(steps=8, readers=8, batch=8, context=32768, token_bytes=5120)
    assert set(said) == set(shared_kv_read.SHAPES)
    assert shared_kv_read.read_work(**said)["bytes"] == w["bytes"]
    assert shared_kv_read.read_work(**dict(said, context=65536, batch=4))["bytes"] == w["bytes"]
    assert w["bytes"] / 8 == pytest.approx(10.74e9, rel=1e-3)
    assert floor_seconds(w, PEAKS) == (pytest.approx(w["bytes"] / 819e9), "hbm")


def test_decode_step_by_hand():
    cfg = config()
    w = sambay_decode.work(cfg, {}, 1)
    weights = 2 * sambay_decode.parameters(cfg)["total"]
    windows = 8 * 8 * 512 * 5120                                        # 0.17 GB
    states = 9 * 8 * (16 * 5120 * 4 + 3 * 5120 * 2)                     # read and written
    step = weights + 8 * 8 * 32768 * 5120 + windows + 2 * states
    assert w["bytes"] == 8 * step
    assert step == pytest.approx(18.65e9, rel=2e-3)                     # ISSUE 27, Tentpole 5
    secs, bound = floor_seconds(w, PEAKS)
    assert bound == "hbm" and secs == pytest.approx(0.182, rel=5e-3)
    assert 8 * 8 * 32768 * 5120 / step == pytest.approx(0.58, abs=0.01)  # the shared reads' share


WINDOW_SEES_EVERYTHING = """
from heat_tpu.models import sambay
_masked = sambay.masked_attention
sambay.masked_attention = lambda *a, **kw: _masked(*a, **dict(kw, window=None))
"""

CROSS_MISSES_NEWEST = """
from heat_tpu.models import sambay
_attention = sambay._attention
def _stale(cfg, kind, layer, p, h, pos0, shared, ring, block):
    return _attention(cfg, kind, layer, p, h, pos0 - (kind == "cross"), shared, ring, block)
sambay._attention = _stale
"""

STATE_NOT_CARRIED = """
import jax.numpy as jnp
from heat_tpu.models import sambay
_step = sambay.selective_step
sambay.selective_step = lambda u, dt, a, b, c, d, state: _step(u, dt, a, b, c, d, jnp.zeros_like(state))
"""

LAMBDA_DROPPED = """
from heat_tpu.models import sambay
sambay._lambda = lambda cfg, p, layer: 0.0
"""

MEMORY_ONE_STEP_LATE = """
import jax.numpy as jnp
from heat_tpu.models import sambay
_init = sambay.DecodeSession.__init__
def _with_last(self, model, batch, *a, **kw):
    _init(self, model, batch, *a, **kw)
    self._state["last_y"] = jnp.zeros((self.batch, 1, model.cfg.d_inner), jnp.float32)
sambay.DecodeSession.__init__ = _with_last
_run = sambay._run_layers
def _late(cfg, params, first, last, x, pos0, shared, state, memory, **kw):
    state = dict(state)
    kept = state.pop("last_y", None)
    if kept is None:
        return _run(cfg, params, first, last, x, pos0, shared, state, memory, **kw)
    if last <= cfg.n_self:                      # a prefill chunk: remember its last position's
        x, shared, state, memory = _run(cfg, params, first, last, x, pos0, shared, state, memory, **kw)
        return x, shared, dict(state, last_y=memory[:, -1:]), memory
    # a decode step: the memory units read what the step before left
    x, shared, state, fresh = _run(cfg, params, first, cfg.n_self, x, pos0, shared, state, None, **kw)
    x, shared, state, _ = _run(cfg, params, cfg.n_self, last, x, pos0, shared, state, kept, **kw)
    return x, shared, dict(state, last_y=fresh), fresh
sambay._run_layers = _late
"""

FAULTS = {
    "window_layers_attend_the_whole_context": WINDOW_SEES_EVERYTHING,
    "cross_layers_miss_the_newest_token": CROSS_MISSES_NEWEST,
    "mamba_state_not_carried_between_steps": STATE_NOT_CARRIED,
    "lambda_term_dropped": LAMBDA_DROPPED,
    "memory_taken_from_the_step_before": MEMORY_ONE_STEP_LATE,
}


@pytest.mark.parametrize("patch", list(FAULTS.values()), ids=list(FAULTS))
def test_fault_is_caught(patch):
    rc, result, err = run_cell(CELL, patch="import heat_tpu\n" + patch)
    assert rc == 0, err[-2000:]
    assert result["correct"] is False
    value, limit = result["check"]["logits_err"]
    assert not value <= limit, result["check"]
    # the exact numbers still hold: the fault is in the mathematics
    assert result["check"]["n_steps_off"][0] == 0 and result["check"]["tokens_not_argmax"][0] == 0


def test_the_cell_unharmed_is_correct_with_room():
    rc, result, err = run_cell(CELL)
    assert rc == 0, err[-2000:]
    assert result["correct"] is True
    value, limit = result["check"]["logits_err"]
    assert value < limit / 10
    # 43 positions in one block of 64: sessions x slots x (k, v) x heads x head x float32
    cache = result["window"]["info"]["cache"]
    assert cache["capacity"] == 64 and cache["bytes"]["shared"] == 2 * 64 * 2 * 4 * 8 * 4
    assert result["check"]["shared_cache_err"][0] < 1e-5


def test_roofline_reader_takes_its_bytes_from_the_runs_own_decode_span(monkeypatch):
    """No cell's name in the reader: the newest ``lm.decode`` span says the
    shapes, so a cell of another context or batch is held to its own bytes."""
    from heat_tpu.core import telemetry
    from perf.layer_metrics import shared_kv_attn_roofline as reader

    monkeypatch.setattr(reader.mf, "load_peaks", lambda kind: PEAKS)
    run = {"trace": {"calls": 2}, "span_reduce": {"calls": 2, "scopes": {reader.SCOPE: 0.5}}}
    with telemetry.telemetry_level("events"):
        telemetry.clear_events()
        assert reader.decode_shapes() is None and reader.read(dict(run)) is None
        with telemetry.span("lm.decode", batch=8, context=32768, steps=8):
            pass                                   # a program whose span lacks the cache's shapes
        assert reader.read(dict(run)) is None
        for context in (32768, 65536):
            with telemetry.span("lm.decode", batch=8, context=context, steps=8, readers=8,
                                token_bytes=5120):
                pass
            floor = 8 * 8 * 8 * context * 5120 / 819e9
            assert reader.read(dict(run)) == pytest.approx(100 * floor * 2 / 0.5)
        assert reader.read({"trace": {"calls": 2}, "span_reduce": {"calls": 2, "scopes": {}}}) is None
