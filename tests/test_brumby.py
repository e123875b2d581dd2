"""Brumby (power-retention layers) held to its plain reference
(``perf/reference/brumby.py``: attention form only, float32) at toy sizes on
the CPU, seeded random weights: the feature map, the recurrent form, the
chunked prefill, the Pallas step kernel through the interpreter, prefill then
decode through a session, save and rewind, the state's bytes, the published
parameter counts, and the precision the configuration states.

Where a test runs the recurrent form against the attention form it draws each
query near its own key (as trained heads put weight on the newest token): the
recurrent form's normaliser is a sum of signed feature products, and with one
live position and ``q . k`` near zero it cancels in float32 whatever computes
it; a served session never decodes from an empty state.
"""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import heat_tpu as ht  # noqa: E402
from heat_tpu.core import telemetry  # noqa: E402
from heat_tpu.models import brumby, sambay, session as lm_session  # noqa: E402
from heat_tpu.ops import power_retention as pr  # noqa: E402
from perf.reference import brumby as ref  # noqa: E402

F32_TOL = 1e-5
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu", "hidden_size": 5120,
    "intermediate_size": 17408, "max_position_embeddings": 32768, "max_window_layers": 40,
    "model_type": "brumby", "num_attention_heads": 40, "num_hidden_layers": 40,
    "num_key_value_heads": 8, "rms_norm_eps": 1e-06, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}


def toy(dtype="float32", **over):
    sizes = dict(vocab_size=96, hidden_size=64, intermediate_size=128, num_attention_heads=6,
                 num_key_value_heads=2, head_dim=16, num_hidden_layers=3,
                 max_position_embeddings=256, dtype=dtype)
    sizes.update(over)
    return brumby.BrumbyConfig(**sizes)


def as_reference(cfg):
    return {k: getattr(cfg, k) for k in ref.SIZES}


def rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)))


def prompts(cfg, batch, length, seed=0):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, (batch, length)).astype(np.int32)


@pytest.fixture
def pallas(monkeypatch):
    """``pallas("interpret")``: the step runs the Pallas kernel through the
    interpreter, as on a TPU; the tests' default is the ``jax.numpy`` body."""
    def set_mode(value):
        monkeypatch.setenv("HEAT_TPU_PALLAS", value)
        brumby._decode.clear_cache()
    yield set_mode
    brumby._decode.clear_cache()


@pytest.fixture
def chunks(monkeypatch):
    def set_chunk(prefill):
        monkeypatch.setattr(brumby, "PREFILL_CHUNK", prefill)
    return set_chunk


@pytest.fixture(scope="module")
def model():
    return brumby.Brumby(toy(), seed=3)


def sequence(seed, batch=2, seq=37, heads=2, group=3, d=16, gate=3.0):
    """Queries, keys, values and log-gates of a toy layer; each query tied to
    its own key."""
    ks = jax.random.split(jax.random.key(seed), 5)
    k = jax.random.normal(ks[0], (batch, seq, heads, d), jnp.float32)
    q = k[:, :, :, None, :] + 0.3 * jax.random.normal(
        ks[1], (batch, seq, heads, group, d), jnp.float32)
    v = jax.random.normal(ks[2], (batch, seq, heads, d), jnp.float32)
    log_g = jax.nn.log_sigmoid(gate + jax.random.normal(ks[3], (batch, seq, heads), jnp.float32))
    return q, k, v, log_g


@jax.jit
def attention_form(q, k, v, log_g):
    """The reference's attention form, a sequence at a time."""
    batch, seq, heads, group, d = q.shape
    cfg = {"retention_eps": pr.EPS}
    out = [ref.retention(cfg, q[b].reshape(seq, heads * group, d), k[b], v[b], log_g[b], 16)
           for b in range(batch)]
    return jnp.stack(out).reshape(q.shape)


def empty_state(batch, heads, d):
    return (jnp.zeros((batch, heads, d, pr.state_rows(d)), jnp.float32),
            jnp.zeros((batch, heads, pr.feature_blocks(d), d), jnp.float32))


def stepped(q, k, v, log_g, S, z):
    """Position by position through ``retention_step`` (traced anew each call:
    the mode is read while tracing)."""
    def step(state, xs):
        y, S, z = pr.retention_step(*state, *xs)
        return (S, z), y

    along = lambda a: jnp.moveaxis(a, 1, 0)  # noqa: E731
    (S, z), ys = jax.jit(lambda state, xs: jax.lax.scan(step, state, xs))(
        (S, z), tuple(along(a) for a in (q, k, v, log_g)))
    return jnp.moveaxis(ys, 0, 1), S, z


# ---- the feature map

@pytest.mark.parametrize("d", [2, 8, 16, 128])
def test_features_give_the_squared_product(d):
    x, y = jax.random.normal(jax.random.key(d), (2, 5, d), jnp.float32)
    fx, fy = pr.features(x), pr.features(y)
    assert fx.shape == (5, d // 2 + 1, d) == (5, pr.feature_blocks(d), d)
    got = jnp.sum(fx * fy, axis=(-2, -1))
    want = jnp.sum(x * y, axis=-1) ** 2 / d
    assert float(jnp.max(jnp.abs(got - want) / (jnp.sum(x * x, -1) * jnp.sum(y * y, -1) / d))) < 1e-6
    # d (d + 1) / 2 features; the rest of the last block stays zero
    live = np.asarray(pr.feature_table(d)) != 0
    assert int(live.sum()) == pr.feature_count(d) == d * (d + 1) // 2
    assert pr.state_rows(d) - pr.feature_count(d) == d // 2
    assert int(jnp.sum(fx[0] != 0)) <= pr.feature_count(d)


def test_features_are_the_references_in_another_order():
    """The judge's map (``perf/drivers/retention_decode.py:held_rows``) from
    the program's blocks to the reference's order."""
    from perf.drivers.retention_decode import held_rows
    d = 16
    x = jax.random.normal(jax.random.key(1), (d,), jnp.float32)
    rows, spare = held_rows(d)
    flat = pr.features(x).reshape(-1)
    assert np.allclose(np.asarray(flat[rows]), np.asarray(ref.sym_square(x, d)), rtol=1e-6, atol=0)
    assert len(spare) == d // 2 and not np.any(np.asarray(flat[spare]))
    assert sorted(np.concatenate([rows, spare]).tolist()) == list(range(pr.state_rows(d)))


# ---- the recurrent form, the chunks, the kernel

@pytest.mark.parametrize("how", ["off", "interpret"])
@pytest.mark.parametrize("gate", [8.0, 3.0, -12.0], ids=["near_one", "mixed", "near_zero"])
def test_recurrent_form_equals_attention_form(gate, how, pallas):
    pallas(how)
    q, k, v, log_g = sequence(11, gate=gate)
    got, _, _ = stepped(q, k, v, log_g, *empty_state(2, 2, 16))
    assert float(jnp.max(jnp.abs(got - attention_form(q, k, v, log_g)))) < F32_TOL


@pytest.mark.parametrize("gate", [8.0, -12.0], ids=["near_one", "near_zero"])
@pytest.mark.parametrize("chunk", [1, 4, 7, 37, 64])
def test_chunked_equals_reference(chunk, gate):
    q, k, v, log_g = sequence(12, gate=gate)
    got, S, z = pr.retention_chunked(q, k, v, log_g, *empty_state(2, 2, 16), chunk)
    assert float(jnp.max(jnp.abs(got - attention_form(q, k, v, log_g)))) < F32_TOL
    # and it leaves the state that stepping leaves
    _, S_step, z_step = stepped(q, k, v, log_g, *empty_state(2, 2, 16))
    assert float(jnp.max(jnp.abs(S - S_step)) / jnp.max(jnp.abs(S_step))) < F32_TOL
    assert float(jnp.max(jnp.abs(z - z_step)) / jnp.max(jnp.abs(z_step))) < F32_TOL


@pytest.mark.parametrize("first,chunk", [(20, 6), (5, 8), (36, 3)])
def test_chunked_from_a_state_that_is_not_empty(first, chunk):
    q, k, v, log_g = sequence(13)
    want = attention_form(q, k, v, log_g)
    cut = lambda a, lo, hi: a[:, lo:hi]  # noqa: E731
    y1, S, z = pr.retention_chunked(*(cut(a, 0, first) for a in (q, k, v, log_g)),
                                    *empty_state(2, 2, 16), 8)
    y2, _, _ = pr.retention_chunked(*(cut(a, first, 37) for a in (q, k, v, log_g)), S, z, chunk)
    assert float(jnp.max(jnp.abs(jnp.concatenate([y1, y2], axis=1) - want))) < F32_TOL


@pytest.mark.parametrize("d,group,batch,heads", [(16, 2, 2, 2), (16, 3, 2, 2), (8, 5, 1, 3),
                                                 (128, 5, 1, 1)])
def test_kernel_through_the_interpreter_equals_the_jnp_body(d, group, batch, heads, pallas):
    q, k, v, log_g = sequence(14, batch=batch, seq=3, heads=heads, group=group, d=d)
    _, S, z = pr.retention_chunked(q, k, v, log_g, *empty_state(batch, heads, d), 3)
    args = (S, z, q[:, 2], k[:, 2], v[:, 2], log_g[:, 2])
    pallas("off")
    want = pr.retention_step(*args)
    pallas("interpret")
    got = pr.retention_step(*args)
    # the state is the same arithmetic in the same order (to the last place
    # where the backend contracts a product and a sum); the query's sums run in
    # another order
    for have, expect in zip(got, want):
        assert float(jnp.max(jnp.abs(have - expect)) / jnp.max(jnp.abs(expect))) < 1e-6
    assert not np.any(np.asarray(got[1])[..., pr.feature_count(d) - pr.state_rows(d):])


def test_widths_the_chip_cannot_tile_run_the_jnp_body(monkeypatch):
    monkeypatch.setenv("HEAT_TPU_PALLAS", "tpu")
    assert pr._kernel_takes(128, "tpu") and pr._kernel_takes(256, "tpu")
    assert not pr._kernel_takes(16, "tpu") and pr._kernel_takes(16, "interpret")
    assert not pr._kernel_takes(128, "off")


def test_the_step_kernel_lowers_for_tpu_at_the_published_widths():
    """The Pallas -> Mosaic lowering runs here for platform ``tpu`` and raises
    on an illegal block, a 64-bit constant or a primitive it lacks (compiled
    for a described v5e in ``tests/test_lloyd_pass.py``, on the chip by
    ``chip_smoke.py``)."""
    batch, heads, group, d = 16, 8, 5, 128
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    with jax.enable_x64(False):
        text = jax.jit(lambda *a: pr._step_pallas(*a, interpret=False)).trace(
            f32(batch, heads, d, pr.state_rows(d)), f32(batch, heads, pr.feature_blocks(d), d),
            f32(batch, heads, group, d), f32(batch, heads, d), f32(batch, heads, d),
            f32(batch, heads)).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text and "ht_power_retention_step" in text


# ---- the session

def served_against_reference(mdl, tokens, steps, session):
    first = session.prefill(ht.array(tokens))
    chosen, logits = (np.asarray(v.larray) for v in session.decode(steps))
    first_token = np.asarray(jnp.argmax(first.larray, -1))
    worst = 0.0
    for b in range(tokens.shape[0]):
        seq = jnp.asarray(np.concatenate([tokens[b], [first_token[b]], chosen[b, :-1]]))
        want = ref.logits_at_end(as_reference(mdl.cfg), mdl.params, seq, steps)
        worst = max(worst, rel_err(logits[b], want))
        alone = ref.logits_at_end(as_reference(mdl.cfg), mdl.params, jnp.asarray(tokens[b]), 1)
        worst = max(worst, rel_err(np.asarray(first.larray)[b], alone[0]))
    return worst


@pytest.mark.parametrize("how", ["off", "interpret"])
@pytest.mark.parametrize("prefill,length", [(8, 19), (64, 19), (6, 18), (4, 2)])
def test_prefill_then_decode_equals_the_full_forward_pass(model, chunks, pallas, how, prefill,
                                                          length):
    pallas(how)
    chunks(prefill)
    session = model.session(2, 64)
    assert served_against_reference(model, prompts(model.cfg, 2, length), 4, session) < F32_TOL


def test_a_prompt_in_two_calls_is_the_prompt_in_one(model, chunks):
    chunks(8)
    tokens = prompts(model.cfg, 2, 21, seed=4)
    whole, halves = model.session(2, 64), model.session(2, 64)
    want = whole.prefill(ht.array(tokens))
    halves.prefill(ht.array(tokens[:, :9]))
    got = halves.prefill(ht.array(tokens[:, 9:]))
    assert rel_err(got.larray, want.larray) < F32_TOL and halves.position == whole.position == 21


def test_rewind_returns_to_the_saved_position(model, chunks):
    chunks(6)
    session = model.session(2, 40)
    session.prefill(ht.array(prompts(model.cfg, 2, 19)))
    saved = session.save()
    before = jax.tree.map(np.asarray, (session._token, session._state))
    tokens, logits = (np.asarray(v.larray) for v in session.decode(6))
    assert session.position == 25
    session.rewind(saved)
    assert session.position == 19
    # the state, the pending token and the position, exactly
    after = jax.tree.map(np.asarray, (session._token, session._state))
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before)))
    again_tokens, again_logits = (np.asarray(v.larray) for v in session.decode(6))
    assert np.array_equal(tokens, again_tokens) and np.array_equal(logits, again_logits)
    # the snapshot survives its use, and holds all of the state
    session.rewind(saved)
    session.decode(2)
    more_tokens, more_logits = session.decode(4)
    assert np.array_equal(np.asarray(more_tokens.larray), tokens[:, 2:])
    assert rel_err(np.asarray(more_logits.larray), logits[:, 2:]) < F32_TOL
    assert lm_session.tree_bytes(saved.state) == session.cache_bytes()["state"]


def test_rewind_writes_into_the_live_states_buffers(model, monkeypatch, pallas):
    """Two copies, never three: the live state is given up to the program
    that copies the saved one, and a large leaf of the copy is written by one
    DMA into the buffer of the leaf it replaces (here every leaf counts as
    large, and the kernel runs through the interpreter, which copies without
    aliasing; ``tests/test_lloyd_pass.py`` compiles the program for a v5e and
    reads the aliased bytes)."""
    monkeypatch.setattr(lm_session, "DMA_BYTES", 0)
    lm_session._restored.clear_cache()
    try:
        pallas("interpret")
        session = model.session(2, 40)
        session.prefill(ht.array(prompts(model.cfg, 2, 9)))
        saved = session.save()
        session.decode(2)
        session.rewind(saved)
        assert not any(leaf.is_deleted() for leaf in jax.tree.leaves(saved.state))
        for got, want in zip(jax.tree.leaves(session._state), jax.tree.leaves(saved.state)):
            assert np.array_equal(np.asarray(got), np.asarray(want))
        # for the chip: every leaf of the live state is donated and aliased to
        # an output, and the copy is the kernel under its scope
        pallas("tpu")
        state = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                             (session._token, session._state))
        args = (state, state)
        with jax.enable_x64(False):
            text = lm_session._restored.trace(*args).lower(lowering_platforms=("tpu",)).as_text(
                debug_info=True)
        leaves = len(jax.tree.leaves(session._state)) + 1
        assert text.count("tf.aliasing_output") == leaves
        assert text.count("tpu_custom_call") == leaves and "ht.lm.state_copy" in text
    finally:
        lm_session._restored.clear_cache()


@pytest.mark.parametrize("how", ["off", "interpret"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32], ids=["f32", "bf16", "i32"])
def test_device_copy_copies(dtype, how, monkeypatch):
    from heat_tpu.ops._pallas_common import device_copy
    monkeypatch.setenv("HEAT_TPU_PALLAS", how)
    src = (jax.random.normal(jax.random.key(0), (3, 2, 8, 16)) * 9).astype(dtype)
    assert np.array_equal(np.asarray(jax.jit(device_copy)(src)), np.asarray(src))
    got = jax.jit(device_copy, donate_argnums=1)(src, jnp.zeros_like(src))
    assert np.array_equal(np.asarray(got), np.asarray(src)) and not src.is_deleted()


@pytest.mark.parametrize("batch", [1, 3])
def test_the_states_bytes_are_what_cache_bytes_says(model, batch):
    cfg = model.cfg
    session = model.session(batch, 50)
    assert session.capacity == cfg.max_position_embeddings
    d, heads = cfg.head_dim, cfg.num_key_value_heads
    held = cfg.num_hidden_layers * batch * heads * pr.state_rows(d) * (d + 1) * 4
    assert session.cache_bytes() == {"state": held} and session._shared == ()
    counted = telemetry.snapshot_group("lm")["cache_bytes"]
    assert counted == {"shared": 0, "window": 0, "state": held}
    assert sum(int(x.nbytes) for x in jax.tree.leaves(session._state)) == held


def test_session_refuses_what_it_cannot_hold(model):
    with pytest.raises(ValueError):
        model.session(2, model.cfg.max_position_embeddings + 1)
    session = model.session(2, 16)
    with pytest.raises(ValueError):
        session.decode(1)                                   # no prompt yet
    with pytest.raises(ValueError):
        session.save()
    with pytest.raises(ValueError):
        session.prefill(ht.array(prompts(model.cfg, 3, 4)))  # another batch
    session.prefill(ht.array(prompts(model.cfg, 2, 5)))
    tokens, logits = session.decode(1)
    assert tokens.shape == (2, 1) and logits.shape == (2, 1, model.cfg.vocab_size)
    assert tokens.split is None and math.isfinite(float(jnp.max(jnp.abs(logits.larray))))


def test_spans_counters_and_one_sync_a_decode(model, chunks):
    chunks(8)
    session = model.session(2, 32)
    held = session.cache_bytes()["state"]
    before = telemetry.snapshot()
    with telemetry.telemetry_level("events"):
        telemetry.clear_events()
        session.prefill(ht.array(prompts(model.cfg, 2, 11)))
        saved = session.save()
        session.decode(3)
        session.rewind(saved)
        session.decode(2)
        begun = telemetry.events("span_begin")
    after = telemetry.snapshot()
    assert [e["name"] for e in begun] == ["lm.prefill", "lm.decode", "sync:lm.tokens", "lm.rewind",
                                         "lm.decode", "sync:lm.tokens"]
    spans = {e["name"]: e for e in begun}
    assert spans["lm.prefill"]["tokens"] == 22 and spans["lm.prefill"]["chunk"] == 8
    decode = spans["lm.decode"]
    assert (decode["batch"], decode["context"], decode["steps"], decode["layers"]) == (2, 11, 2, 3)
    assert decode["state_bytes"] == held and decode["kv_heads"] == 2 and decode["head_dim"] == 16
    lm = {k: after["lm"][k] - before["lm"][k] for k in
          ("decode_steps", "prefill_tokens", "state_bytes_stepped", "state_bytes_copied",
           "cache_keys_visible", "cache_keys_fetched")}
    assert lm == {"decode_steps": 5, "prefill_tokens": 22, "state_bytes_stepped": 2 * 5 * held,
                  "state_bytes_copied": 2 * held, "cache_keys_visible": 0, "cache_keys_fetched": 0}
    assert after["sync"]["count"] - before["sync"]["count"] == 2
    assert session.tokens.shape == (2, 2) and session.position == 13


# ---- the published sizes, the generator, the precision

def test_published_parameter_counts():
    """ISSUE 32's arithmetic, from the catalog row's ``config`` and the assumed
    gate with its offset."""
    published = PUBLISHED
    if os.path.isfile(CATALOG):
        rows = [json.loads(line) for line in open(CATALOG, encoding="utf-8")]
        (row,) = [r for r in rows if r["name"] == "Brumby-14B-Base"]
        assert row["config"] == PUBLISHED
        published = row["config"]
    cfg = brumby.BrumbyConfig.from_dict(published, retention_degree=2)
    assert cfg == brumby.BrumbyConfig()
    n = brumby.param_count(cfg)
    q_and_o, k_and_v = 2 * 5120 * 5120, 2 * 5120 * 1024
    assert n["layer"] == (q_and_o + k_and_v + 5120 * 8 + 8 + 256 + 3 * 5120 * 17408 + 2 * 5120
                          ) == 330_352_904
    assert n["embed"] == n["head"] == 151936 * 5120
    assert n["total"] == 40 * n["layer"] + 2 * 151936 * 5120 + 5120 == 14_769_945_920
    five = brumby.param_count(brumby.BrumbyConfig.from_dict(dict(published, num_hidden_layers=5)))
    assert five["total"] == 3_207_594_280 and round(2 * five["total"] / 1e9, 3) == 6.415
    assert 8 * pr.state_rows(128) * 129 * 4 == 34_344_960  # a layer and session as held; 8,256 rows are features
    assert (cfg.group, cfg.q_width, cfg.kv_width) == (5, 5120, 1024)


@pytest.mark.parametrize("bad", [{"retention_degree": 3}, {"tie_word_embeddings": True},
                                 {"attention_bias": True}, {"num_key_value_heads": 7},
                                 {"head_dim": 127}], ids=lambda v: next(iter(v)))
def test_config_refuses_what_the_layer_is_not(bad):
    with pytest.raises(ValueError):
        brumby.BrumbyConfig(**bad)


def test_generators_tree_is_the_programs():
    """``perf/generators/brumby_weights.py`` draws the benchmark's weights
    without importing the program; its tree has to be the one ``param_spec``
    describes, and the model has to serve from it."""
    from perf.generators import brumby_weights
    cfg = toy()
    config = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 6,
              "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 3,
              "vocab_size": 96, "dtype": "float32",
              "assumed": {"init": {"residual_scale": 0.4, "embed_std": 1.0, "gate_bias": 8.0,
                                   "gate_std": 1.0}}}
    drawn = brumby_weights.weights(config, jax.random.key(2), lambda leaf: leaf)
    own = brumby.init_params(cfg, jax.random.key(1))
    assert jax.tree.structure(drawn) == jax.tree.structure(own)
    shapes = lambda tree: [(x.shape, x.dtype) for x in jax.tree.leaves(tree)]  # noqa: E731
    assert shapes(drawn) == shapes(own)
    # the gates remember: a median half-life of at least 1,024 positions
    gate = np.asarray(drawn["layers"][0]["mixer"]["b_g"])
    assert np.all(math.log(2) / np.log1p(np.exp(-gate)) >= 1024)
    assert np.array_equal(np.asarray(own["layers"][1]["mixer"]["b_g"]), gate)
    session = brumby.Brumby(cfg, drawn).session(1, 16)
    assert session.prefill(ht.array(prompts(cfg, 1, 5))).shape == (1, 96)


def test_limits_lie_between_the_stated_precision_and_the_one_below(chunks):
    """The float32 model passes ``F32_TOL`` and fails it with its state rounded
    to bfloat16; the bfloat16 model (bfloat16 weights and product operands,
    float32 state) stays within 3e-2 of the float32 reference."""
    def served(mdl, rounding=None):
        tokens = prompts(mdl.cfg, 2, 30, seed=9)
        session = mdl.session(2, 40)
        first = session.prefill(ht.array(tokens))
        if rounding:
            session._state = jax.tree.map(lambda x: jax.lax.reduce_precision(x, *rounding),
                                          session._state)
        chosen, logits = (np.asarray(v.larray) for v in session.decode(4))
        first_token = np.asarray(jnp.argmax(first.larray, -1))
        worst = 0.0
        for b in range(2):
            seq = jnp.asarray(np.concatenate([tokens[b], [first_token[b]], chosen[b, :-1]]))
            want = ref.logits_at_end(as_reference(mdl.cfg), mdl.params, seq, 4)
            worst = max(worst, rel_err(logits[b], want))
        return worst

    chunks(8)
    exact = brumby.Brumby(toy(), seed=13)
    assert served(exact) < F32_TOL < served(exact, rounding=(8, 7))
    half = brumby.Brumby(toy(dtype="bfloat16"), seed=13)
    assert half.params["layers"][0]["mixer"]["w_qkv"].dtype == jnp.bfloat16
    assert half.session(1, 8)._state["S"][0].dtype == jnp.float32
    assert 1e-4 < served(half) < 3e-2


def test_reference_returns_the_states_the_session_saved_and_stepped(model, chunks):
    """What the benchmark's judge compares: the saved state and the state the
    decode steps left against the reference's closed sums, through the
    judge's own map of the layout."""
    from perf.drivers.retention_decode import _state_error, held_rows
    chunks(8)
    tokens = prompts(model.cfg, 2, 19, seed=5)
    session = model.session(2, 40)
    first = np.asarray(jnp.argmax(session.prefill(ht.array(tokens)).larray, -1))
    saved = session.save().state
    chosen = np.asarray(session.decode(2)[0].larray)
    rows, spare = held_rows(model.cfg.head_dim)
    for b in range(2):
        seq = jnp.asarray(np.concatenate([tokens[b], [first[b]], chosen[b, :-1]]))
        _, states = ref.logits_at_end(as_reference(model.cfg), model.params, seq, 2,
                                      with_state=True)
        for layer, wanted in enumerate(states):
            for tree, want in zip((saved, session._state), wanted):
                held = tree["S"][layer][b], tree["z"][layer][b]
                err, coarse = _state_error(held, want, rows, spare)
                assert float(err) < F32_TOL and float(coarse) < 0.01
                rounded = jax.tree.map(lambda x: jax.lax.reduce_precision(x, 8, 7), held)
                err, coarse = _state_error(rounded, want, rows, spare)
                assert float(err) > 1e-4 and float(coarse) == 1.0
            # the two are states of different positions
            assert float(_state_error(held, wanted[0], rows, spare)[0]) > 1e-2


def test_one_session_class_serves_both_models():
    assert ht.models.Brumby is brumby.Brumby and ht.models.BrumbyConfig is brumby.BrumbyConfig
    assert ht.models.DecodeSession is lm_session.DecodeSession is sambay.DecodeSession
    assert type(brumby.Brumby(toy(), seed=1).session(1, 8)) is lm_session.DecodeSession
    assert sambay.Snapshot is lm_session.Snapshot


def test_quick_start_section_runs():
    """docs/quick_start.md section 19 executes as written, its Brumby example
    last (it reuses the prompt of SambaY's)."""
    import re

    text = open(os.path.join(ROOT, "docs", "quick_start.md"), encoding="utf-8").read()
    found = re.search(r"## 19\. Serve a language model from a session\n(.*?)\n## 20\.", text, re.S)
    assert found and "### A second model, the same session" in found.group(1)
    ns = {}
    for block in re.findall(r"```python\n(.*?)```", found.group(1), re.S):
        exec(compile(block, "quick_start.md[session]", "exec"), ns)
    assert isinstance(ns["session"].model, brumby.Brumby)
    assert np.array_equal(np.asarray(ns["tokens"].larray), np.asarray(ns["again"].larray))
