"""SDAR (the Qwen3-MoE layer served by block diffusion) held to its plain
reference (``perf/reference/sdar.py``: the block-causal forward pass, float32)
at toy sizes on the CPU, seeded random weights: prefill then blocks with
prompts of lengths of their own, every pass's logits and confidences
teacher-forced; the stream kernel with a length a sequence through the
interpreter, and its shared-length path; the softmax router; save and rewind
of positions and pending blocks; the counters and spans; the published
configuration; the generator's tree; the Qwen3 helpers Brumby shares; and the
decode program compiled for the chip."""

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
from heat_tpu.models import _lm, sdar  # noqa: E402
from heat_tpu.ops import decode_attention as da  # noqa: E402
from heat_tpu.parallel import expert  # noqa: E402
from perf.reference import sdar as ref  # noqa: E402

F32_TOL = 1e-5
CATALOG = os.environ.get("HT_MODEL_CATALOG", "")   # a JSONL file of published configs, if any
PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2048, "intermediate_size": 6144, "max_position_embeddings": 32768,
    "max_window_layers": 48, "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts": 128, "num_experts_per_tok": 8, "num_hidden_layers": 48,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936,
}
MASK = 511


def toy(dtype="float32", **over):
    sizes = dict(vocab_size=512, hidden_size=64, moe_intermediate_size=32, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=16, num_experts=8,
                 num_experts_per_tok=2, max_position_embeddings=128, block_length=4,
                 denoising_steps=2, mask_token_id=MASK, dtype=dtype)
    sizes.update(over)
    return sdar.SDARConfig(**sizes)


def as_reference(cfg):
    return {k: getattr(cfg, k) for k in ref.SIZES}


def rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)))


def prompts(batch, length, seed=0):
    return np.random.default_rng(seed).integers(0, MASK, (batch, length)).astype(np.int32)


@pytest.fixture(scope="module")
def model():
    return sdar.SDAR(toy(), seed=3)


@pytest.fixture
def pallas(monkeypatch):
    """Sets the Pallas mode for a test; the serving programs traced under
    another mode are dropped before the test and after it."""
    programs = (sdar._prefill_chunk, sdar._decode_blocks)

    def set_mode(how):
        monkeypatch.setenv("HEAT_TPU_PALLAS", how)
        for program in programs:
            program.clear_cache()
    yield set_mode
    for program in programs:
        program.clear_cache()


def served(mdl, lengths, blocks, seed=0, capacity=None):
    batch, n = len(lengths), max(lengths)
    tokens = prompts(batch, n, seed)
    session = mdl.session(batch, capacity or n + mdl.cfg.block_length * blocks)
    pending = np.asarray(session.prefill(ht.array(tokens), lengths=np.asarray(lengths)).larray)
    made, logits = (np.asarray(v.larray) for v in session.decode(blocks))
    return session, tokens, pending, made, logits


def reference_walk(cfg, params, prompt, made, confidence, capacity):
    """The reference, teacher-forced on the program's block states: every
    pass's logits and the keys and values each commit wrote, a block at a
    time over the prompt's whole blocks."""
    width, steps = cfg.block_length, cfg.denoising_steps
    length = len(prompt)
    first = length - length % width
    rcfg = as_reference(cfg)
    cache = ref.prefix(rcfg, params, jnp.asarray(prompt[:first]), capacity)
    passes, committed = [], []
    for j in range(confidence.shape[0]):
        pos = first + j * width
        final = made[j * width:(j + 1) * width].copy()
        for i in range(max(0, min(width, length - pos))):
            final[i] = prompt[pos + i]
        fixed = np.isinf(confidence[j])
        for s in range(steps):
            state = np.where(fixed[s], final, cfg.mask_token_id).astype(np.int32)
            passes.append((j, s, fixed[s], ref.block(rcfg, params, cache, jnp.asarray(state), pos)[0]))
        _, kv = ref.block(rcfg, params, cache, jnp.asarray(final), pos)
        cache = ref.committed(cache, kv, pos)
        committed.append(kv)
    return passes, committed, cache


# ---- prefill and block diffusion

@pytest.mark.parametrize("how", ["off", "interpret"], ids=["jnp", "kernel"])
@pytest.mark.parametrize("lengths,blocks,prefill_rows", [((30, 21, 27), 3, 2048),
                                                         ((45, 13), 3, 16), ((9, 11), 2, 2048)])
def test_prefill_then_blocks_equal_the_block_causal_forward_pass(
        model, pallas, monkeypatch, how, lengths, blocks, prefill_rows):
    """Prompts whose lengths are not whole blocks, prefilled in chunks (16
    positions: several programs, the last overlapping), then blocks: at every
    pass, the confidence of every masked position and the logits of every
    position it fixed against the reference over the prompt's whole blocks,
    the committed blocks and the block's state at that pass; the keys and
    values of every committed row.  Under ``interpret`` every pass reads
    through the stream kernel, each session to its own block's end."""
    monkeypatch.setattr(sdar, "PREFILL_ROWS", prefill_rows)
    pallas(how)
    cfg = model.cfg
    width = cfg.block_length
    session, tokens, pending, made, logits = served(model, lengths, blocks, seed=sum(lengths))
    whole = [n - n % width for n in lengths]
    assert session.positions.tolist() == [w + width * blocks for w in whole]
    for b, n in enumerate(lengths):
        tail = n - whole[b]
        assert pending[b, :tail].tolist() == tokens[b, whole[b]:n].tolist()
        assert (pending[b, tail:] == MASK).all() and (made[b, :tail] == -1).all()
        assert (made[b, tail:] >= 0).all()
        passes, committed, _ = reference_walk(cfg, model.params, tokens[b, :n], made[b],
                                              session.confidence[b], session.capacity)
        for j, s, fixed, want in passes:
            after = (np.isinf(session.confidence[b, j, s + 1]) if s + 1 < cfg.denoising_steps
                     else np.ones(width, bool))
            taken = np.flatnonzero(after & ~fixed)
            if taken.size:
                assert rel_err(logits[b, j * width + taken], want[taken]) < 1e-4
            masked = ~fixed
            conf = 1.0 / np.sum(np.exp(want - want.max(-1, keepdims=True)), -1)
            np.testing.assert_allclose(session.confidence[b, j, s][masked], conf[masked],
                                       rtol=1e-4)
        for layer in range(cfg.num_hidden_layers):
            k = session._shared["k"][layer][b][:, whole[b]:whole[b] + width * blocks]
            v = session._shared["v"][layer][b][:, whole[b]:whole[b] + width * blocks]
            want_k = np.concatenate([kv[layer][0] for kv in committed])
            want_v = np.concatenate([kv[layer][1] for kv in committed])
            assert rel_err(np.moveaxis(np.asarray(k), 0, 1), want_k) < 1e-4
            assert rel_err(np.moveaxis(np.asarray(v), 0, 1), want_v) < 1e-4


def test_each_pass_fixes_its_schedule_of_the_most_confident(model):
    """A block's m masks over two passes: m // 2 each, the first pass one more
    where m is odd; each pass the most confident masked positions, each the
    argmax of the logits returned for it; a prompt's tail is never touched."""
    cfg = model.cfg
    width = cfg.block_length
    lengths = (29, 30, 31, 32)                     # tails of 1, 2, 3 and 0 tokens
    session, tokens, pending, made, logits = served(model, lengths, 3, seed=4)
    for b, n in enumerate(lengths):
        conf = session.confidence[b]
        for j in range(3):
            fixed = np.isinf(conf[j])
            masks = width - int(fixed[0].sum())
            assert masks == (width - n % width if j == 0 else width)
            after = np.vstack([fixed[1:], np.ones((1, width), bool)])
            for s in range(cfg.denoising_steps):
                taken = after[s] & ~fixed[s]
                assert taken.sum() == masks // 2 + (s < masks % 2)
                left = ~fixed[s] & ~taken
                if taken.any() and left.any():
                    assert conf[j, s][taken].min() >= conf[j, s][left].max()
            gen = ~fixed[0]
            rows = logits[b, j * width:(j + 1) * width]
            assert (made[b, j * width:(j + 1) * width][gen] == rows.argmax(-1)[gen]).all()
            assert (made[b, j * width:(j + 1) * width][~gen] == -1).all()


def test_unmask_breaks_ties_by_position():
    """Equal confidences: the earlier position first; fixed positions never
    count."""
    tokens = jnp.full((1, 4), MASK, jnp.int32)
    fixed = jnp.asarray([[False, True, False, False]])
    logits = jnp.zeros((1, 4, 8))                  # every confidence 1/8
    got, now, taken, conf = sdar._unmask(tokens, fixed, jnp.arange(4)[None] + 100, logits,
                                         jnp.asarray([2]))
    assert np.asarray(taken).tolist() == [[True, False, True, False]]
    assert np.asarray(got).tolist() == [[100, MASK, 102, MASK]]
    assert np.isinf(np.asarray(conf)[0, 1]) and np.asarray(conf)[0, 0] == pytest.approx(1 / 8)


def test_rewind_restores_positions_and_pending_blocks(model):
    session = model.session(3, 64)
    session.prefill(ht.array(prompts(3, 30, seed=2)), lengths=[30, 22, 27])
    saved = session.save()
    assert saved.state == () and saved.positions.tolist() == [28, 20, 24]
    pending = [np.asarray(x) for x in saved.token]
    tokens, logits = session.decode(3)
    session.decode(2)
    session.rewind(saved)
    assert session.positions.tolist() == [28, 20, 24]
    assert all(np.array_equal(np.asarray(a), b) for a, b in zip(session._token, pending))
    again, logits_again = session.decode(3)
    assert np.array_equal(np.asarray(tokens.larray), np.asarray(again.larray))
    assert np.array_equal(np.asarray(logits.larray), np.asarray(logits_again.larray))


def test_session_refuses_what_it_cannot_hold(model):
    session = model.session(2, 32)
    with pytest.raises(ValueError):
        session.prefill(ht.array(prompts(2, 8)), lengths=[9, 3])
    session.prefill(ht.array(prompts(2, 8)), lengths=[8, 3])
    assert session.positions.tolist() == [8, 0]
    with pytest.raises(ValueError):
        session.prefill(ht.array(prompts(2, 2)))          # the sessions stand apart
    with pytest.raises(ValueError):
        session.decode(7)                                 # 8 + 7 x 4 pass the 32 held
    with pytest.raises(ValueError):
        model.session(1, 129)                             # past max_position_embeddings


def test_spans_counters_and_one_sync_a_decode(model):
    """``lm.decode`` carries the shapes and the key/value rows the passes will
    stream, layers x sessions x visible rows over every pass (a commit reads
    with every layer but the last); the ``lm`` counters count the passes once
    for the batch and the positions the passes fixed on the device."""
    cfg = model.cfg
    session = model.session(2, 64)
    before = telemetry.snapshot()
    with telemetry.telemetry_level("events"):
        telemetry.clear_events()
        session.prefill(ht.array(prompts(2, 13)), lengths=[13, 6])
        at = session.positions.copy()
        session.decode(3)
        begun = {e["name"]: e for e in telemetry.events("span_begin")}["lm.decode"]
        ended = {e["name"]: e for e in telemetry.events("span_end")}["lm.decode"]
    after = telemetry.snapshot()
    ends = at[:, None] + 4 * np.arange(1, 4)
    rows = (2 * 2 + 1) * int(ends.sum())
    assert {k: begun[k] for k in ("batch", "first", "blocks", "block_length", "denoising_steps",
                                  "layers", "row_bytes", "kv_rows_streamed")} == dict(
        batch=2, first=4, blocks=3, block_length=4, denoising_steps=2, layers=2,
        row_bytes=2 * 2 * 16 * 4, kv_rows_streamed=rows)
    lm = {k: after["lm"][k] - before["lm"][k] for k in
          ("decode_steps", "denoise_passes", "commit_passes", "tokens_unmasked",
           "kv_rows_streamed")}
    unmasked = 2 * 3 * 4 - (13 % 4) - (6 % 4)
    assert lm == {"decode_steps": 3, "denoise_passes": 6, "commit_passes": 3,
                  "tokens_unmasked": unmasked, "kv_rows_streamed": rows}
    assert ended["tokens_unmasked"] == unmasked
    assert after["sync"]["count"] - before["sync"]["count"] == 2   # one a prefill, one a decode
    del cfg


# ---- the stream kernel with a length a sequence

def stream_inputs(dtype, batch, rows, slots, groups=2, width=16, seed=0):
    rng = np.random.default_rng(seed)
    normal = lambda *shape: rng.standard_normal(shape).astype(np.float32)  # noqa: E731
    return (jnp.asarray(normal(batch, groups, rows, width), dtype),
            jnp.asarray(normal(batch, groups, slots, width), dtype),
            jnp.asarray(normal(batch, groups, slots, width), dtype))


@pytest.mark.parametrize("lengths", [[4, 260, 512], [256, 129, 33], [512, 512, 512], [36, 1, 300]],
                         ids=["spread", "edges", "all_full", "piece_and_block"])
@pytest.mark.parametrize("rows", [32, 8])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_stream_kernel_reads_each_sequence_to_its_own_length(dtype, rows, lengths,
                                                                 monkeypatch):
    """``ht_decode_attention`` with ``kv_len`` a vector, through the
    interpreter, against :func:`masked_attention` (every row of a sequence at
    its length's last position): 32 rows a group (8 heads x a block of 4) or
    8, sequences ending inside a block, at a block edge, inside the copied
    piece of a last block and past it."""
    q, k, v = stream_inputs(dtype, 3, rows, 512, seed=sum(lengths) + rows)
    kv_len = jnp.asarray(lengths, jnp.int32)
    monkeypatch.setenv("HEAT_TPU_PALLAS", "interpret")
    got = da.decode_attention(q, k, v, kv_len, scale=0.25, block=128)
    q_pos = jnp.broadcast_to((kv_len - 1)[:, None], (3, rows))
    want = da.masked_attention(q, k, v, q_pos, jnp.arange(512), scale=0.25, block=128)
    assert got.shape == (3, 2, rows, 16) and got.dtype == jnp.float32
    assert rel_err(got, want) < (F32_TOL if dtype == "float32" else 4e-3)
    monkeypatch.setenv("HEAT_TPU_PALLAS", "off")
    fallback = da.decode_attention(q, k, v, kv_len, scale=0.25, block=128)
    assert rel_err(fallback, want) < F32_TOL


def test_the_masked_form_with_a_position_a_sequence_is_the_softmax():
    """Sequence ``b``'s rows see the slots up to ``q_pos[b, r]`` and no further."""
    q, k, v = stream_inputs("float32", 2, 4, 64, seed=3)
    q_pos = jnp.asarray([[5, 5, 9, 9], [40, 41, 42, 63]])
    got = np.asarray(da.masked_attention(q, k, v, q_pos, jnp.arange(64), scale=0.3, block=16))
    for b in range(2):
        for g in range(2):
            for r in range(4):
                n = int(q_pos[b, r]) + 1
                logit = np.asarray(k[b, g, :n]) @ np.asarray(q[b, g, r]) * 0.3
                prob = np.exp(logit - logit.max())
                want = (prob / prob.sum()) @ np.asarray(v[b, g, :n])
                np.testing.assert_allclose(got[b, g, r], want, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("length", [1, 130, 511])
def test_a_shared_length_keeps_its_one_plan(length, monkeypatch):
    """A scalar ``kv_len`` runs the shared plan (one for the batch, as a
    decode step of sequences in lockstep has it): bit for bit the per-sequence
    plan at equal lengths, and its plan operand is ``5 + steps`` numbers, not
    one a sequence."""
    monkeypatch.setenv("HEAT_TPU_PALLAS", "interpret")
    q, k, v = stream_inputs("bfloat16", 3, 16, 512, seed=length)
    shared = da.decode_attention(q, k, v, jnp.int32(length), scale=0.2, block=128)
    each = da.decode_attention(q, k, v, jnp.full((3,), length, jnp.int32), scale=0.2, block=128)
    assert np.array_equal(np.asarray(shared), np.asarray(each))
    text = jax.jit(lambda n: da.decode_attention(q, k, v, n, scale=0.2, block=128)).lower(
        jnp.int32(length)).as_text()
    plan = 5 + 512 // 128
    assert f"tensor<{plan}xi32>" in text and f"tensor<{3 * plan}xi32>" not in text
    assert da.keys_fetched(length, 512, 128) == da.keys_fetched(length, 512, 128)


# ---- the router

def test_softmax_topk_routing_is_the_naive_loop():
    rng = np.random.default_rng(0)
    h = rng.standard_normal((6, 16)).astype(np.float32)
    router = rng.standard_normal((16, 8)).astype(np.float32)
    weights, chosen = expert.softmax_topk_routing(jnp.asarray(h), jnp.asarray(router), top_k=3)
    for t in range(6):
        logit = h[t].astype(np.float64) @ router
        p = np.exp(logit - logit.max())
        p /= p.sum()
        top = np.argsort(-p)[:3]
        assert np.asarray(chosen)[t].tolist() == top.tolist()
        np.testing.assert_allclose(np.asarray(weights)[t], p[top] / p[top].sum(), rtol=1e-5)


@pytest.mark.parametrize("tokens", [12, 300], ids=["streamed", "grouped"])
def test_held_experts_with_the_softmax_router_are_the_routed_sum(tokens):
    """Both lowerings of ``held_experts_sum`` of the softmax router's choice
    against a per-token loop over the chosen experts (no token dropped), and
    ``held_experts_ffn`` is the same sum of the sigmoid router's choice."""
    rng = np.random.default_rng(tokens)
    d, f, e = 16, 8, 8
    x = jnp.asarray(rng.standard_normal((tokens, d)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((d, e)) * 0.5, jnp.float32)
    ws = {n: jnp.asarray(rng.standard_normal(s) * 0.3, jnp.float32) for n, s in
          (("w_gate", (e, d, f)), ("w_up", (e, d, f)), ("w_down", (e, f, d)))}
    weights, chosen = expert.softmax_topk_routing(x, router, top_k=2)
    got, counts = expert.held_experts_sum(x, weights, chosen, ws, held=(0, e))
    want = np.zeros((tokens, d))
    for t in range(tokens):
        for g, c in zip(np.asarray(weights)[t], np.asarray(chosen)[t]):
            h = np.asarray(x[t], np.float64)
            inner = (lambda a: a / (1 + np.exp(-a)))(h @ np.asarray(ws["w_gate"][c])) * (
                h @ np.asarray(ws["w_up"][c]))
            want[t] += g * inner @ np.asarray(ws["w_down"][c])
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)
    assert int(counts["pairs"]) == 2 * tokens
    sigmoid, _ = expert.held_experts_ffn(x, router, ws, held=(0, e), top_k=2)
    assert not np.allclose(np.asarray(sigmoid), np.asarray(got))
    s_weights, s_chosen = expert.sigmoid_group_routing(
        x, router, jnp.zeros((e,), jnp.float32), top_k=2, n_group=1, topk_group=1, scale=1.0)
    np.testing.assert_array_equal(
        np.asarray(sigmoid), np.asarray(expert.held_experts_sum(x, s_weights, s_chosen, ws,
                                                                held=(0, e))[0]))


# ---- the Qwen3 helpers Brumby shares

def test_the_moved_rotary_embedding_is_brumbys():
    """``_lm.rope_half`` of positions shared by the batch is Brumby's former
    embedding (lane ``i`` with ``i + d/2``, cos and sin shaped ``(1, seq, 1,
    d/2)``), and of positions a sequence it turns each sequence by its own."""
    x = jnp.asarray(np.random.default_rng(0).standard_normal((2, 5, 3, 16)), jnp.float32)
    pos = jnp.arange(7, 12)
    angle = pos.astype(jnp.float32)[:, None] * _lm.rope_frequencies(16, 1e6)
    cos, sin = jnp.cos(angle)[None, :, None, :], jnp.sin(angle)[None, :, None, :]
    lo, hi = x[..., :8], x[..., 8:]
    former = jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)
    assert np.array_equal(np.asarray(_lm.rope_half(x, pos, 1e6)), np.asarray(former))
    each = _lm.rope_half(x, jnp.stack([pos, pos + 3]), 1e6)
    assert np.array_equal(np.asarray(each[1]), np.asarray(_lm.rope_half(x[1:], pos + 3, 1e6)[0]))


# ---- the published configuration

def test_the_published_config_loads_with_only_its_depth_cut():
    """The configuration's parameter and cache arithmetic, from the catalog
    row's ``config`` where a catalog is given."""
    published = PUBLISHED
    if os.path.isfile(CATALOG):
        rows = [json.loads(line) for line in open(CATALOG, encoding="utf-8")]
        (row,) = [r for r in rows if r["name"] == "SDAR-30B-A3B-Chat"]
        assert row["config"] == PUBLISHED
        published = row["config"]
    cfg = sdar.SDARConfig.from_dict(published, num_hidden_layers=5)
    assert (cfg.block_length, cfg.denoising_steps, cfg.mask_token_id) == (4, 2, 151669)
    assert cfg.group == 8 and cfg.row_bytes == 2048
    n = sdar.param_count(cfg)
    assert n["attention"] == 18_874_624 and n["expert"] == 4_718_592
    assert n["layer"] == 623_120_640 and n["embed_head"] + 2048 == 622_331_904
    assert n["total"] == 3_737_935_104 and round(2 * n["total"] / 1e9, 2) == 7.48
    assert round(16 * 32768 * 5 * 2048 / 1e9, 2) == 5.37
    file = json.load(open(os.path.join(ROOT, "perf", "configs", "sdar-30b-a3b-5L-bd4-bf16.json")))
    assert {k: file[k] for k in published if k != "num_hidden_layers"} == {
        k: v for k, v in published.items() if k != "num_hidden_layers"}
    assert file["num_hidden_layers"] == 5 and file["published"] == {"num_hidden_layers": 48}
    with pytest.raises(ValueError):
        sdar.SDARConfig.from_dict(dict(published, rope_scaling={"type": "yarn"}))
    with pytest.raises(ValueError):
        sdar.SDARConfig.from_dict(dict(published, mlp_only_layers=[3]))
    with pytest.raises(ValueError):
        sdar.SDARConfig.from_dict(dict(published, norm_topk_prob=False))


def test_generators_tree_is_the_programs():
    """``perf/generators/sdar_weights.py`` draws the benchmark's weights without
    importing the program; its tree has to be the one ``param_spec``
    describes, and its queries and keys have to lean on their distance."""
    from perf.generators import sdar_weights
    cfg = toy()
    config = {k: getattr(cfg, k) for k in (
        "hidden_size", "moe_intermediate_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "num_experts", "vocab_size")}
    config.update(dtype="float32", assumed={"init": {
        "residual_scale": 0.5, "attention_out_scale": 1.0, "embed_shared": 0.5,
        "position_tie": 0.95, "position_pairs": 3, "qk_norm_gain": 1.5}})
    drawn = sdar_weights.weights(config, jax.random.key(2), lambda leaf: leaf)
    own = sdar.init_params(cfg, jax.random.key(1))
    assert jax.tree.structure(drawn) == jax.tree.structure(own)
    shapes = lambda tree: [(x.shape, x.dtype) for x in jax.tree.leaves(tree)]  # noqa: E731
    assert shapes(drawn) == shapes(own)
    assert np.allclose(np.asarray(drawn["layers"][0]["attn"]["q_norm"]), 1.5)
    session = sdar.SDAR(cfg, drawn).session(2, 32)
    assert session.prefill(ht.array(prompts(2, 9)), lengths=[9, 6]).shape == (2, 4)


def test_quick_start_example_runs():
    """The block-diffusion example of docs/quick_start.md section 19 executes
    as written."""
    text = open(os.path.join(ROOT, "docs", "quick_start.md"), encoding="utf-8").read()
    found = re.search(r"### A model that denoises blocks\n(.*?)\n## 20\.", text, re.S)
    assert found
    ns = {"ht": ht, "np": np}
    for block in re.findall(r"```python\n(.*?)```", found.group(1), re.S):
        exec(compile(block, "quick_start.md[sdar]", "exec"), ns)
    assert ns["pending"].shape == (2, 4) and ns["block_tokens"].shape == (2, 12)
    assert ns["block_session"].positions.tolist() == [28, 20]      # rewound
    assert ns["block_session"].confidence.shape == (2, 3, 2, 4)


def test_lint_passes_on_the_new_modules():
    from heat_tpu.analysis import lint

    paths = [os.path.join(ROOT, "heat_tpu", p) for p in (
        "models/sdar.py", "models/session.py", "models/_lm.py", "ops/decode_attention.py",
        "parallel/expert.py")]
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


def test_the_block_decode_compiled_for_the_chip_streams_in_place(v5e, monkeypatch):
    """The cell's decode program (five layers at the published widths, 16
    sessions of 32,768 slots, 6 blocks) compiled for a v5e: every pass reads
    through the kernel ``ht_decode_attention``, which asks for no more VMEM
    than any kernel has; the caches are updated where they lie (the
    program's temporaries are not a cache's size)."""
    monkeypatch.setenv("HEAT_TPU_PALLAS", "tpu")
    cfg = sdar.SDARConfig.from_dict(PUBLISHED, num_hidden_layers=5)
    shape = lambda *s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(s, dtype, sharding=v5e)  # noqa: E731
    with jax.enable_x64(False):
        params = jax.eval_shape(lambda: sdar.init_params(cfg, jax.random.key(0)))
        params = jax.tree.map(lambda x: shape(*x.shape, dtype=x.dtype), params)
        batch, capacity = 16, 32768
        shared = {kind: tuple(shape(batch, 4, capacity, 128) for _ in range(5))
                  for kind in ("k", "v")}
        pending = (shape(batch, 4, dtype=jnp.int32), shape(batch, 4, dtype=jnp.bool_))
        compiled = sdar._decode_blocks.lower(cfg, params, shared, pending,
                                             shape(batch, dtype=jnp.int32), blocks=6).compile()
    text = compiled.as_text()
    assert "ht_decode_attention" in text
    caches = 10 * batch * capacity * 128 * 4 * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= caches
    assert memory.temp_size_in_bytes < caches // 16
    call = next(line for line in text.splitlines()
                if "ht_decode_attention" in line and "custom-call(" in line)
    assert max(map(int, re.findall(r'"size":"(\d+)"', call)), default=0) <= 16 << 20
