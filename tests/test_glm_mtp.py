"""GLM-4.7-Flash through the model that serves DeepSeek-V3.2 (latent attention
read in full, sigmoid-routed experts in one group, and the MTP module drafting
one token a step) held to its plain reference (``perf/reference/glm.py``:
per-head attention over every earlier position, the MTP module over the whole
sequence, float32) at toy sizes on the CPU, seeded random weights: prefill
then speculative decode with sessions at positions of their own, the tokens of
plain greedy decoding, weights that make every draft stand or none,
save/rewind of positions and drafts, the full read of the latent cache
(``ops/latent_stream.py``) through the interpreter, the published
configuration, the counters, and the decode program compiled for the chip."""

import json
import os
import re
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
from heat_tpu.models import deepseek  # noqa: E402
from heat_tpu.ops import latent_attention as la, latent_stream as ls  # noqa: E402
from perf.reference import glm as ref  # noqa: E402

F32_TOL = 1e-5
CATALOG = os.environ.get("HT_MODEL_CATALOG", "")   # a JSONL file of published configs, if any
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 10240,
    "max_position_embeddings": 202752, "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True, "num_attention_heads": 20, "n_group": 1,
    "topk_group": 1, "n_routed_experts": 64, "n_shared_experts": 1, "routed_scaling_factor": 1.8,
    "num_experts_per_tok": 4, "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1, "partial_rotary_factor": 1,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 1000000,
    "tie_word_embeddings": False, "q_lora_rank": 768, "kv_lora_rank": 512,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880,
}


def toy(dtype="float32", **over):
    sizes = dict(vocab_size=96, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
                 num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4,
                 q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=24, qk_rope_head_dim=8,
                 v_head_dim=16, index_topk=0, n_routed_experts=8, num_experts_per_tok=2,
                 n_group=1, topk_group=1, routed_scaling_factor=1.8, rms_norm_eps=1e-5,
                 rope_theta=1e6, rope_factor=1.0, max_position_embeddings=512,
                 num_nextn_predict_layers=1, dtype=dtype)
    sizes.update(over)
    return deepseek.DeepSeekConfig(**sizes)


def as_reference(cfg):
    out = {k: getattr(cfg, k) for k in ref.SIZES if hasattr(cfg, k)}
    out["router_experts"] = cfg.n_routed_experts
    out["experts_first"], out["n_routed_experts"] = cfg.experts_held
    return out


def rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)))


def prompts(cfg, batch, length, seed=0):
    return np.random.default_rng(seed).integers(0, cfg.vocab_held, (batch, length)).astype(np.int32)


def biased(model, seed=7, std=0.1):
    """The model with a balancing bias that is not zero."""
    layers = model.params["layers"] + [model.params["mtp"]["layer"]]
    for i, layer in enumerate(layers):
        if "moe" in layer:
            layer["moe"]["bias"] = std * jax.random.normal(
                jax.random.fold_in(jax.random.key(seed), i), layer["moe"]["bias"].shape, jnp.float32)
    return model


def steered(model, accept: bool, seed=0):
    """Weights under which the next token is a fixed permutation of the current
    one (the head's rows are the embedding's, permuted, and no block writes
    into the stream), and the MTP module sees the next token's embedding alone
    (``accept``: so every draft stands) or its negative (so none does)."""
    params = jax.tree.map(lambda x: x, model.params)
    d = model.cfg.hidden_size
    sigma = jax.random.permutation(jax.random.key(seed), model.cfg.vocab_held)
    params["head"] = params["embed"][sigma]
    for layer in params["layers"] + [params["mtp"]["layer"]]:
        layer["attn"]["w_o"] = jnp.zeros_like(layer["attn"]["w_o"])
        parts = [layer["mlp"]] if "mlp" in layer else [layer["moe"]["shared"],
                                                         layer["moe"]["experts"]]
        for mlp in parts:
            mlp["w_down"] = jnp.zeros_like(mlp["w_down"])
    eye = jnp.eye(d, dtype=params["mtp"]["eh_proj"].dtype)
    params["mtp"]["eh_proj"] = jnp.concatenate([eye if accept else -eye, jnp.zeros_like(eye)])
    return deepseek.DeepSeek(model.cfg, params)


@pytest.fixture(scope="module")
def model():
    return biased(deepseek.DeepSeek(toy(), seed=3))


@pytest.fixture
def pallas(monkeypatch):
    """Sets the Pallas mode for a test; the serving programs traced under
    another mode are dropped before the test and after it."""
    programs = (deepseek._prefill_chunk, deepseek._decode, deepseek._prefill_chunk_drafted,
                deepseek._decode_drafted)

    def set_mode(how):
        monkeypatch.setenv("HEAT_TPU_PALLAS", how)
        for program in programs:
            program.clear_cache()
    yield set_mode
    for program in programs:
        program.clear_cache()


def reference_rows(cfg, params, prompt, first_token, emitted, accepted, steps):
    """The reference over the prompt, the first token and the emitted ones:
    the logits of every position the call kept, in the order the steps
    checked them, and those of the rows each step drafted from."""
    length = len(prompt)
    sequence = jnp.asarray(np.concatenate([prompt, [first_token], emitted]).astype(np.int32))
    main_rows, mtp_rows, checked, at = [], [], [], length
    for j in range(steps):
        main_rows.append(at)
        checked.append((j, 0))
        if accepted[j]:
            main_rows.append(at + 1)
            checked.append((j, 1))
        at += 1 + int(accepted[j])
        mtp_rows.append(at - 1)
    want = ref.forward(as_reference(cfg), params, sequence, main_rows, mtp_rows, block=16)
    return want, checked


def spec_served(mdl, lengths, steps, seed=0):
    batch, n = len(lengths), max(lengths)
    tokens = prompts(mdl.cfg, batch, n, seed)
    session = mdl.session(batch, n + 2 * steps)
    first = session.prefill(ht.array(tokens), lengths=np.asarray(lengths))
    emitted, logits = (np.asarray(v.larray) for v in session.decode(steps))
    return session, tokens, np.asarray(first.larray), emitted, logits


# ---- the full read of the latent cache

def stream_inputs(dtype, batch, queries, capacity, heads=4, rank=32, rope=8, seed=0):
    rng = np.random.default_rng(seed)
    normal = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    return (jnp.asarray(normal(batch, queries, heads, rank)),
            jnp.asarray(normal(batch, queries, heads, rope)),
            jnp.asarray(normal(batch, capacity, rank), dtype),
            jnp.asarray(normal(batch, capacity // la.ROPE_PACK, la.ROPE_PACK * rope), dtype))


def kernel_and_masked(args, positions, monkeypatch, capacity=512):
    """The attention through ``ht_latent_stream`` in the interpreter and
    through the ``jax.numpy`` form, in that order."""
    got = {}
    for how, form in (("interpret", "stream_kernel"), ("off", "masked_jnp")):
        monkeypatch.setenv("HEAT_TPU_PALLAS", how)
        assert ls.stream_form(capacity, 32, 8) == form
        got[how] = ls.latent_stream_attention(*args, jnp.asarray(positions, jnp.int32), 0.3)
    return got["interpret"], got["off"]


@pytest.mark.parametrize("visible", [1, 256, 300, 512, 255, 257],
                         ids=["one_row", "block_edge", "mid_block", "capacity", "odd_last_slot",
                              "even_last_slot_past_edge"])
@pytest.mark.parametrize("queries", [1, 2])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_stream_kernel_is_the_masked_form(dtype, queries, visible, monkeypatch):
    """``ht_latent_stream`` through the interpreter against the ``jax.numpy``
    form: a session whose last query sees ``visible`` rows of a capacity of
    two key blocks (one row, a block edge, inside the second block, every
    row; a last visible slot odd and even on either side of the edge),
    beside sessions at other positions."""
    capacity = 512
    args = stream_inputs(dtype, 3, queries, capacity, seed=visible)
    # a step's last query sees its own rows at least
    positions = [max(visible - queries, 0), 0, capacity - queries]
    got, want = kernel_and_masked(args, positions, monkeypatch)
    assert got.shape == (3, queries, 4, 32) and got.dtype == jnp.float32
    assert rel_err(got, want) < (F32_TOL if dtype == "float32" else 4e-3)


@pytest.mark.parametrize("positions", [[3, 129, 201], [255, 1, 509]],
                         ids=["odd_steps_in_a_block", "odd_steps_across_the_edge"])
@pytest.mark.parametrize("rope_only", [False, True], ids=["latent_and_rope", "rope_only"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_stream_kernel_reads_each_slot_its_own_rotary_key(dtype, rope_only, positions,
                                                               monkeypatch):
    """A rope row holds two neighbouring positions' keys and the kernel keeps
    a slot's own by its parity: steps of two queries that start at odd
    positions, so that the pair of the step's first and second position
    straddles a rope row (in a block, and across the block edge at 255 and
    256), against the ``jax.numpy`` form.  With ``q_lat`` zero the rotary
    keys alone decide the weights, so a slot that took its neighbour's key
    would move the result by the weights' whole spread."""
    args = stream_inputs(dtype, 3, 2, 512, seed=sum(positions))
    if rope_only:
        args = (jnp.zeros_like(args[0]),) + args[1:]
    got, want = kernel_and_masked(args, positions, monkeypatch)
    assert rel_err(got, want) < (F32_TOL if dtype == "float32" else 4e-3)


def test_the_masked_form_is_the_per_position_softmax():
    """Query ``j`` of session ``b`` reads exactly the slots up to ``positions[b]
    + j``: against a plain softmax over those rows, the rotary keys unpacked."""
    capacity, positions = 256, np.array([5, 130])
    q_lat, q_pe, latent, rope = stream_inputs("float32", 2, 2, capacity, seed=1)
    got = ls.latent_stream_attention(q_lat, q_pe, latent, rope, jnp.asarray(positions), 0.25)
    k_pe = np.asarray(rope).reshape(2, capacity, -1)
    for b in range(2):
        for j in range(2):
            n = positions[b] + j + 1
            logit = (np.einsum("hc,sc->hs", q_lat[b, j], latent[b, :n])
                     + np.einsum("hr,sr->hs", q_pe[b, j], k_pe[b, :n])) * 0.25
            prob = np.exp(logit - logit.max(-1, keepdims=True))
            prob /= prob.sum(-1, keepdims=True)
            assert rel_err(got[b, j], prob @ np.asarray(latent[b, :n])) < F32_TOL


@pytest.mark.parametrize("how,capacity,rank,rope,form", [
    ("tpu", 33024, 512, 64, "stream_kernel"), ("tpu", 33024, 32, 8, "masked_jnp"),
    ("interpret", 512, 32, 8, "stream_kernel"), ("interpret", 200, 32, 8, "masked_jnp"),
    ("off", 33024, 512, 64, "masked_jnp")])
def test_the_stream_form_follows_the_shapes(how, capacity, rank, rope, form, monkeypatch):
    monkeypatch.setenv("HEAT_TPU_PALLAS", how)
    assert ls.stream_form(capacity, rank, rope) == form


# ---- prefill and speculative decode

@pytest.mark.parametrize("how", ["off", "interpret"], ids=["jnp", "kernels"])
@pytest.mark.parametrize("lengths,steps,prefill_rows", [((37, 30, 33), 5, 2048),
                                                        ((40, 17), 4, 16), ((9, 9), 3, 2048)])
def test_prefill_then_speculative_decode_equals_the_full_forward_pass(
        model, pallas, monkeypatch, how, lengths, steps, prefill_rows):
    """Every position a step checked and kept, and every row a step drafted
    from, against the reference over the prompt, the first token and the
    emitted tokens; the caches (the MTP layer's too) at every position before
    each session's own.  Under ``interpret`` the chunks of whole query tiles
    go through the prefill kernel (its causal form) and every step through the
    stream kernel."""
    monkeypatch.setattr(deepseek, "PREFILL_ROWS", prefill_rows)
    pallas(how)
    session, tokens, first, emitted, logits = spec_served(model, lengths, steps, seed=sum(lengths))
    assert session.positions.tolist() == [n + int(c) for n, c in zip(lengths, session.emitted)]
    drafts = np.asarray(model.last_drafts["logits"])
    for b, n in enumerate(lengths):
        count = int(session.emitted[b])
        want, checked = reference_rows(model.cfg, model.params, tokens[b, :n],
                                       int(np.argmax(first[b])), emitted[b, :count],
                                       session.accepted[b], steps)
        got = np.stack([logits[b, j, i] for j, i in checked])
        assert rel_err(got, want["logits"]) < 1e-4
        assert rel_err(drafts[b], want["draft_logits"]) < 1e-4
        end = n + count
        for layer in range(model.cfg.cached_layers):
            latent = session._shared["latent"][layer][b, :end]
            rope = session._shared["rope"][layer][b].reshape(session.capacity, -1)[:end]
            made = want["latent"][layer] if layer < model.cfg.num_hidden_layers else want["mtp_latent"]
            assert rel_err(latent, made[0][:end]) < 1e-4
            assert rel_err(rope, made[1][:end]) < 1e-4


@pytest.mark.parametrize("accept", [True, False], ids=["every_draft_stands", "none_stands"])
def test_steered_weights_advance_by_two_or_by_one(model, accept):
    """Weights under which every draft is the model's own next token advance
    every session by two a step, and weights under which none is by one; the
    emitted tokens are those of plain greedy decoding either way."""
    mdl = steered(model, accept)
    lengths, steps = (12, 7, 10), 4
    session, tokens, first, emitted, logits = spec_served(mdl, lengths, steps, seed=5)
    assert session.accepted.all() if accept else not session.accepted.any()
    assert session.emitted.tolist() == [2 * steps if accept else steps] * 3
    assert session.positions.tolist() == [n + (2 if accept else 1) * steps for n in lengths]
    assert (emitted[:, session.emitted[0]:] == -1).all()
    plain = plain_model(mdl)
    for b, n in enumerate(lengths):
        alone = plain.session(1, n + 2 * steps)
        alone.prefill(ht.array(tokens[b:b + 1, :n]))
        greedy, _ = alone.decode(int(session.emitted[b]))
        assert np.array_equal(np.asarray(greedy.larray)[0], emitted[b, :session.emitted[b]])


def plain_model(mdl):
    """The main model alone (no MTP module): one token a step, in lockstep."""
    cfg = deepseek.DeepSeekConfig(**{**mdl.cfg.__dict__, "num_nextn_predict_layers": 0})
    return deepseek.DeepSeek(cfg, {k: v for k, v in mdl.params.items() if k != "mtp"})


@pytest.mark.parametrize("how", ["off", "interpret"], ids=["jnp", "kernels"])
def test_speculative_decode_emits_plain_greedy_decodings_tokens(model, pallas, how):
    """Each session's emitted tokens are those the main model alone decodes
    greedily one a step from the same prompt, and the logits of the positions
    kept are those of the plain steps (1e-5)."""
    pallas(how)
    mdl = deepseek.DeepSeek(model.cfg, model.params)
    lengths, steps = (21, 16), 6
    session, tokens, first, emitted, logits = spec_served(mdl, lengths, steps, seed=3)
    plain = plain_model(mdl)
    for b, n in enumerate(lengths):
        count = int(session.emitted[b])
        alone = plain.session(1, n + 2 * steps)
        alone_first = alone.prefill(ht.array(tokens[b:b + 1, :n]))
        assert rel_err(alone_first.larray[0], first[b]) < F32_TOL
        greedy, greedy_logits = (np.asarray(v.larray)[0] for v in alone.decode(count))
        assert np.array_equal(greedy, emitted[b, :count])
        kept = [logits[b, j, i] for j in range(steps)
                for i in range(1 + int(session.accepted[b, j]))]
        assert rel_err(np.stack(kept), greedy_logits) < F32_TOL


def test_rewind_restores_positions_tokens_and_drafts(model):
    session = model.session(3, 64)
    session.prefill(ht.array(prompts(model.cfg, 3, 30, seed=2)), lengths=[30, 22, 27])
    saved = session.save()
    assert saved.state == () and saved.positions.tolist() == [30, 22, 27] and saved.position == 30
    token, draft = (np.asarray(x) for x in saved.token)
    tokens, logits = session.decode(4)
    session.decode(3)
    session.rewind(saved)
    assert session.positions.tolist() == [30, 22, 27]
    assert np.array_equal(np.asarray(session._token[0]), token)
    assert np.array_equal(np.asarray(session._token[1]), draft)
    again, logits_again = session.decode(4)
    assert np.array_equal(np.asarray(tokens.larray), np.asarray(again.larray))
    assert np.array_equal(np.asarray(logits.larray), np.asarray(logits_again.larray))


def test_session_refuses_what_it_cannot_hold(model):
    session = model.session(2, 24)
    with pytest.raises(ValueError):
        session.prefill(ht.array(prompts(model.cfg, 2, 8)), lengths=[9, 3])
    session.prefill(ht.array(prompts(model.cfg, 2, 8)), lengths=[8, 3])
    with pytest.raises(ValueError):
        session.prefill(ht.array(prompts(model.cfg, 2, 2)))       # the sessions stand apart
    with pytest.raises(ValueError):
        session.decode(125)                                        # 8 + 2 x 125 pass the 256 held
    plain = plain_model(model).session(2, 16)
    with pytest.raises(ValueError):
        plain.prefill(ht.array(prompts(model.cfg, 2, 8)), lengths=[8, 3])
    sparse = dict(index_topk=12, index_n_heads=16, index_head_dim=16)
    with pytest.raises(ValueError):
        deepseek.DeepSeek(toy(**sparse), seed=0).session(1, 16)


def test_spans_counters_and_one_sync_a_decode(model):
    """``lm.decode`` carries the shapes at its start and, at its end, what the
    device counted: the drafts that stood and the latent rows the stream read
    (layers and the MTP layer x sessions x visible rows, summed over steps)."""
    session = model.session(2, 64)
    before = telemetry.snapshot()
    with telemetry.telemetry_level("events"):
        telemetry.clear_events()
        session.prefill(ht.array(prompts(model.cfg, 2, 11)), lengths=[11, 6])
        at = session.positions.copy()
        session.decode(3)
        begun = {e["name"]: e for e in telemetry.events("span_begin")}["lm.decode"]
        ended = {e["name"]: e for e in telemetry.events("span_end")}["lm.decode"]
    after = telemetry.snapshot()
    assert {k: begun[k] for k in ("batch", "context", "first", "steps", "queries", "layers",
                                  "mtp_layers", "latent_bytes", "stream")} == dict(
        batch=2, context=11, first=6, steps=3, queries=2, layers=3, mtp_layers=1,
        latent_bytes=4 * 40, stream="masked_jnp")
    advanced = np.cumsum(1 + session.accepted, axis=1) - 1 - session.accepted  # before each step
    rows = 4 * int(np.sum(at[:, None] + advanced + 2))          # three layers and the MTP layer
    lm = {k: after["lm"][k] - before["lm"][k] for k in
          ("decode_steps", "drafts_made", "drafts_accepted", "latent_rows_streamed")}
    assert lm == {"decode_steps": 3, "drafts_made": 6,
                  "drafts_accepted": int(session.accepted.sum()), "latent_rows_streamed": rows}
    assert ended["latent_rows_streamed"] == rows
    assert ended["acceptance"] == pytest.approx(session.accepted.sum() / 6)
    assert after["sync"]["count"] - before["sync"]["count"] == 2   # one a prefill, one a decode


# ---- the published configuration

def test_the_published_config_loads_with_only_its_depth_cut():
    """The configuration's parameter and cache arithmetic, from the catalog
    row's ``config`` where a catalog is given."""
    published = PUBLISHED
    if os.path.isfile(CATALOG):
        rows = [json.loads(line) for line in open(CATALOG, encoding="utf-8")]
        (row,) = [r for r in rows if r["name"] == "GLM-4.7-Flash"]
        assert row["config"] == PUBLISHED
        published = row["config"]
    cfg = deepseek.DeepSeekConfig.from_dict(published, num_hidden_layers=5)
    assert not cfg.sparse and cfg.rope_factor == 1.0 and cfg.num_nextn_predict_layers == 1
    assert cfg.softmax_scale == pytest.approx(1 / 16)
    assert np.array_equal(cfg.rope_frequencies(),
                          (1e6 ** (-np.arange(0, 64, 2) / 64)).astype(np.float32))
    n = deepseek.param_count(cfg)
    assert n["attention"] == 21_759_232 and n["expert"] == 9_437_184
    assert n["dense_layer"] == 84_677_888 and n["expert_layer"] == 635_311_424
    assert n["embed"] + n["head"] == 634_388_480 and n["mtp"] == 643_706_176
    assert n["total"] == 3_904_020_288 and round(2 * n["total"] / 1e9, 2) == 7.81
    assert 6 * (512 + 64) * 2 == 6912 and round(16 * 33024 * 6912 / 1e9, 2) == 3.65


def test_generators_tree_is_the_programs():
    """``perf/generators/glm_weights.py`` draws the benchmark's weights without
    importing the program; its tree has to be the one ``param_spec``
    describes, with the ties it states."""
    from perf.generators import glm_weights
    cfg = toy()
    config = {k: getattr(cfg, k) for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
        "first_k_dense_replace", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "n_routed_experts", "vocab_size")}
    config.update(dtype="float32", assumed={"init": {
        "residual_scale": 0.4, "attention_out_scale": 1.5, "query_key_tie": 0.5, "embed_std": 1.0,
        "bias_std": 0.1, "head_tie": 0.9, "eh_tie": 0.9}})
    drawn = glm_weights.weights(config, jax.random.key(2), lambda leaf: leaf)
    own = deepseek.init_params(cfg, jax.random.key(1))
    assert jax.tree.structure(drawn) == jax.tree.structure(own)
    shapes = lambda tree: [(x.shape, x.dtype) for x in jax.tree.leaves(tree)]  # noqa: E731
    assert shapes(drawn) == shapes(own)
    attn = drawn["mtp"]["layer"]["attn"]
    assert np.array_equal(np.asarray(attn["w_dq"][:, :32]), np.asarray(attn["w_dkv"][:, :32]))
    # every head row leans on one embedding row: a permutation
    embed, head = np.asarray(drawn["embed"]), np.asarray(drawn["head"])
    lean = np.argmax(head @ embed.T, axis=1)
    assert sorted(lean.tolist()) == list(range(96))
    session = deepseek.DeepSeek(cfg, drawn).session(2, 32)
    assert session.prefill(ht.array(prompts(cfg, 2, 5)), lengths=[5, 2]).shape == (2, 96)


def test_quick_start_example_runs():
    """The drafting example of docs/quick_start.md section 19 executes as
    written."""
    text = open(os.path.join(ROOT, "docs", "quick_start.md"), encoding="utf-8").read()
    found = re.search(r"### A model that drafts its own next token\n(.*?)\n## 20\.", text, re.S)
    assert found
    ns = {"ht": ht, "np": np}
    for block in re.findall(r"```python\n(.*?)```", found.group(1), re.S):
        exec(compile(block, "quick_start.md[glm]", "exec"), ns)
    assert ns["draft_session"].drafts == 1
    assert len(set(ns["draft_session"].positions.tolist())) == 2


def test_lint_passes_on_the_new_modules():
    from heat_tpu.analysis import lint

    paths = [os.path.join(ROOT, "heat_tpu", p) for p in (
        "models/deepseek.py", "models/session.py", "ops/latent_stream.py",
        "ops/latent_attention.py")]
    assert [f"{f.code} {f.path}:{f.line}" for f in lint.lint_paths(paths)] == []


# ---- the decode program compiled for the chip

@pytest.fixture(scope="module")
def v5e():
    """One described (not attached) v5e chip: the TPU's compiler runs here."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no compiler for the chip in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_drafted_decode_compiled_for_the_chip_streams_in_place(v5e, monkeypatch):
    """The cell's decode program (five layers and the MTP module at the
    published widths, 16 sessions of 33,024 slots, 12 steps) compiled for a
    v5e: the full read is the kernel ``ht_latent_stream``, which asks for no
    more VMEM than any kernel has; the caches are updated where they lie (the
    program's temporaries are not a cache's size)."""
    monkeypatch.setenv("HEAT_TPU_PALLAS", "tpu")
    cfg = deepseek.DeepSeekConfig.from_dict(PUBLISHED, num_hidden_layers=5)
    shape = lambda *s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(s, dtype, sharding=v5e)  # noqa: E731
    with jax.enable_x64(False):
        params = jax.eval_shape(lambda: deepseek.init_params(cfg, jax.random.key(0)))
        params = jax.tree.map(lambda x: shape(*x.shape, dtype=x.dtype), params)
        batch, capacity = 16, 33024
        shared = {"latent": tuple(shape(batch, capacity, 512) for _ in range(6)),
                  "rope": tuple(shape(batch, capacity // 2, 128) for _ in range(6))}
        tok = shape(batch, dtype=jnp.int32)
        compiled = deepseek._decode_drafted.lower(cfg, params, shared, (tok, tok), tok,
                                                  steps=12).compile()
    text = compiled.as_text()
    assert "ht_latent_stream" in text and "ht_sparse_prefill_attn" not in text
    caches = 6 * batch * capacity * 576 * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= caches
    assert memory.temp_size_in_bytes < caches // 32
    call = next(line for line in text.splitlines()
                if "ht_latent_stream" in line and "custom-call(" in line)
    assert max(map(int, re.findall(r'"size":"(\d+)"', call)), default=0) <= 16 << 20
