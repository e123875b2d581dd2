"""DeepSeek-V3.2 (latent attention read through a learned sparse selection,
sigmoid-routed experts of which a share is held) held to its plain reference
(``perf/reference/deepseek.py``: per-head attention with the selection as a
mask, float32) at toy sizes on the CPU, seeded random weights: the rotary
frequencies under YaRN, the folded decode form, the selection at a decode step
and in a prefill chunk, the router, the shares adding up to the uncut layer,
the two lowerings of the held experts, prefill then decode through a session,
save and rewind with an empty state, the caches' bytes, the published
parameter counts and the precision the configuration states.

The toy has 16 index heads: with a handful, a key whose every head scores
below zero reads exactly 0, the ties at the cut make "the top k" ambiguous,
and a threshold (prefill) and a ``top_k`` (decode) rightly differ there.
"""

import json
import math
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
from heat_tpu.models import _lm, deepseek, session as lm_session  # noqa: E402
from heat_tpu.ops import latent_attention as la  # noqa: E402
from heat_tpu.parallel import expert  # noqa: E402
from perf.reference import deepseek as ref  # noqa: E402

F32_TOL = 1e-5
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3, "hidden_act": "silu",
    "hidden_size": 7168, "index_head_dim": 128, "index_n_heads": 64, "index_topk": 2048,
    "intermediate_size": 18432, "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v32", "moe_intermediate_size": 2048, "moe_layer_freq": 1,
    "n_group": 8, "n_routed_experts": 256, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8, "num_hidden_layers": 61,
    "num_key_value_heads": 128, "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40, "mscale": 1,
                     "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 4, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 129280,
}
CUT = dict(num_hidden_layers=5, first_k_dense_replace=1, experts_held=(0, 16), vocab_held=16160)


def toy(dtype="float32", **over):
    sizes = dict(vocab_size=96, hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
                 num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4,
                 q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16, index_n_heads=16, index_head_dim=16, index_topk=12,
                 n_routed_experts=8, num_experts_per_tok=2, n_group=4, topk_group=2,
                 max_position_embeddings=512, rope_original=16, experts_held=(2, 2),
                 vocab_held=48, dtype=dtype)
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
    for i, layer in enumerate(model.params["layers"]):
        if "moe" in layer:
            layer["moe"]["bias"] = std * jax.random.normal(
                jax.random.fold_in(jax.random.key(seed), i), layer["moe"]["bias"].shape, jnp.float32)
    return model


@pytest.fixture(scope="module")
def model():
    return biased(deepseek.DeepSeek(toy(), seed=3))


@pytest.fixture
def rows(monkeypatch):
    def set_rows(n):
        monkeypatch.setattr(deepseek, "PREFILL_ROWS", n)
    return set_rows


def served(mdl, batch, prompt, steps, seed=0):
    """A session that prefilled ``prompt`` tokens and decoded ``steps``; and,
    for every sequence, the reference's full forward pass over the prompt plus
    the tokens the program fed back."""
    tokens = prompts(mdl.cfg, batch, prompt, seed)
    session = mdl.session(batch, prompt + steps)
    first = session.prefill(ht.array(tokens))
    chosen, logits = (np.asarray(v.larray) for v in session.decode(steps))
    first_token = np.asarray(jnp.argmax(first.larray, -1))
    wanted = []
    for b in range(batch):
        seq = jnp.asarray(np.concatenate([tokens[b], [first_token[b]], chosen[b, :-1]]))
        wanted.append(ref.forward(as_reference(mdl.cfg), mdl.params, seq, steps + 1, block=16))
    return session, np.asarray(first.larray), logits, wanted


# ---- the rotary embedding

@pytest.mark.parametrize("pair", [0, 5, 9, 10, 16, 22, 23, 31])
def test_yarn_frequencies_by_hand(pair):
    """Factor 40 over an original 4,096 with beta 32 and 1: a pair below
    correction dimension 10 keeps ``theta^(-2i/64)``, one from 23 on has it
    divided by 40, and between them the two are blended by ``(i - 10) / 13``."""
    low = math.floor(64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(10000)))
    high = math.ceil(64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(10000)))
    assert (low, high) == (10, 23)
    plain = 10000.0 ** (-2 * pair / 64)
    stretch = min(1.0, max(0.0, (pair - low) / (high - low)))
    want = plain / 40 * stretch + plain * (1 - stretch)
    cfg = deepseek.DeepSeekConfig.from_dict(PUBLISHED)
    for got in (cfg.rope_frequencies(), ref.yarn_frequencies(as_reference(cfg)),
                _lm.yarn_frequencies(64, 10000, 40, 4096, 32, 1)):
        assert got.shape == (32,) and got.dtype == np.float32
        assert got[pair] == pytest.approx(want, rel=1e-6)


@pytest.mark.parametrize("factor,want", [(40, 192 ** -0.5 * (0.1 * math.log(40) + 1) ** 2),
                                         (1, 192 ** -0.5)])
def test_softmax_scale_by_hand(factor, want):
    cfg = deepseek.DeepSeekConfig.from_dict(PUBLISHED, rope_factor=factor)
    assert cfg.softmax_scale == pytest.approx(want, rel=1e-12)
    assert ref.softmax_scale(as_reference(cfg)) == pytest.approx(want, rel=1e-12)
    if factor == 40:
        assert cfg.softmax_scale == pytest.approx(0.13524, rel=1e-4)
    else:
        assert np.array_equal(cfg.rope_frequencies(),
                              (10000.0 ** (-np.arange(0, 64, 2) / 64)).astype(np.float32))


# ---- one attention block: the folded form, the selection

def attention_block(cfg, seed, seq):
    """A toy attention block's parameters, a normed stream and what the
    reference makes of it."""
    params = deepseek.init_params(cfg, jax.random.key(seed))["layers"][0]
    u = jax.random.normal(jax.random.key(seed + 1), (seq, cfg.hidden_size), jnp.float32)
    u = ref.rms_norm(u, params["norm1"]["w"], 0.0)           # rows of unit rms: norming them again changes nothing
    out, latent, k_idx, mask = jax.jit(
        lambda p, u: ref.attention(as_reference(cfg), p, u, 16))(params["attn"], u)
    return params, u, out, latent, k_idx, ref.unpacked(mask, seq)


def caches_of(cfg, latent, k_idx, capacity):
    """The program's three caches holding the reference's rows of one session."""
    pad = lambda a: jnp.pad(a, ((0, capacity - a.shape[0]), (0, 0)))[None]  # noqa: E731
    return (pad(latent[0]), pad(latent[1]).reshape(1, capacity // la.ROPE_PACK, -1), pad(k_idx))


@pytest.mark.parametrize("topk", [5, 37, 64], ids=["below", "at", "above"])
@pytest.mark.parametrize("seq", [37])
def test_decode_step_is_the_per_head_form_over_the_same_selection(seq, topk):
    """The folded form (``W_uk`` in the query, ``W_uv`` in the output, over
    gathered latent rows) equals the reference's per-head masked attention,
    and the step's exact top-k is the reference's ``S_t``, for ``k`` below, at
    and above the context."""
    cfg = toy(index_topk=topk)
    params, u, out, latent, k_idx, mask = attention_block(cfg, 11, seq)
    caches = caches_of(cfg, latent, k_idx, 256)
    # a stream whose norm is u: the block norms its input itself (gain 1)
    got, new, chosen = jax.jit(lambda p, x, c: deepseek._attention_step(
        cfg, p, x, np.int32(seq - 1), c))(params, u[-1:] * 3.0, caches)
    assert rel_err(got[0], out[-1]) < F32_TOL
    read = np.zeros(seq, bool)
    slots = np.asarray(chosen[0])
    read[slots[slots >= 0]] = True
    assert np.array_equal(read, np.asarray(mask[-1]))
    assert read.sum() == min(topk, seq) and (slots >= 0).sum() == min(topk, seq)
    # the step wrote the position's own rows where the reference has them
    for held, made in zip(new, caches):
        assert rel_err(held[0, :seq // la.ROPE_PACK], made[0, :seq // la.ROPE_PACK]) < F32_TOL


@pytest.mark.parametrize("topk", [5, 24, 64], ids=["below", "inside", "above"])
@pytest.mark.parametrize("pos0,chunk", [(0, 37), (13, 24), (24, 13)])
def test_prefill_chunk_reads_the_references_selection(pos0, chunk, topk, monkeypatch):
    """A chunk's scores, its cut at the k-th largest and the blockwise
    per-head attention over ``score >= cut`` equal the reference's rows
    (blocks of 16 slots, so that a chunk reads several)."""
    monkeypatch.setattr(la, "KEY_BLOCK", 16)
    cfg = toy(index_topk=topk)
    seq = pos0 + chunk
    params, u, out, latent, k_idx, mask = attention_block(cfg, 5, seq)
    latent_c, rope_c, index_c = (c[0] for c in caches_of(cfg, latent, k_idx, 256))
    positions = pos0 + jnp.arange(chunk, dtype=jnp.int32)
    q_nope, q_pe, _, _, q_idx, _, w_idx = deepseek._projections(cfg, params["attn"], u[pos0:],
                                                               positions)
    scores = la.index_scores_chunk(q_idx, w_idx, index_c, np.int32(pos0))
    cut = la.kth_largest(scores, topk)
    chosen = np.asarray((scores >= cut[:, None]) & (scores > -jnp.inf))[:, :seq]
    assert np.array_equal(chosen, np.asarray(mask[pos0:]))
    o = la.latent_prefill_attention(q_nope, q_pe, latent_c, rope_c, scores, cut, np.int32(pos0),
                                    params["attn"]["w_uk"], params["attn"]["w_uv"],
                                    cfg.softmax_scale)
    got = jnp.dot(o.reshape(chunk, -1), params["attn"]["w_o"])
    assert rel_err(got, out[pos0:]) < F32_TOL


@pytest.mark.parametrize("k", [1, 7, 100, 256, 300])
@pytest.mark.parametrize("visible", [256, 60])
def test_kth_largest_is_the_sorted_rows(visible, k):
    rng = np.random.default_rng(k)
    x = (rng.standard_normal((9, 256)) * 10.0 ** rng.integers(-3, 4, (9, 1))).astype(np.float32)
    x[:, visible:] = -np.inf
    x[3, :5] = 0.0                                   # ties, zeros of both signs
    x[3, 5] = -0.0
    got = np.asarray(la.kth_largest(jnp.asarray(x), k))
    want = np.sort(x, axis=-1)[:, ::-1][:, min(k, 256) - 1]
    assert np.array_equal(got, want)


# ---- the selection by itself: cut, membership, compaction

def selection_scores(kind, batch, capacity, k, seed):
    """Scores ``(batch, capacity)`` with ``-inf`` from the visible count on,
    and that count."""
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((batch, capacity)) * 3).astype(np.float32)
    visible = capacity - 5                                      # off a group boundary
    if kind == "quantised":                                     # ties straddle the cut
        x = np.round(x / 2) * 2
    elif kind == "zeros":
        x[:] = 0.0
    elif kind == "both_zeros":                                  # a relu'd sum: half the row is a zero
        zero = rng.choice(np.float32([0.0, -0.0]), x.shape)
        x = np.where(rng.random(x.shape) < k / capacity / 3, np.abs(x) + 1, zero)
        assert (np.sort(x[:, :visible], axis=-1)[:, ::-1][:, min(k, visible) - 1] == 0).all()
        assert np.signbit(x[x == 0]).any() and not np.signbit(x[x == 0]).all()
    elif kind == "short":                                       # fewer than k visible
        visible = k // 2 + 3
    elif kind == "one_visible":
        visible = 1
    elif kind == "k_visible":
        visible = min(k, capacity)
    elif kind == "full":
        visible = capacity
    x[:, visible:] = -np.inf
    return x, visible


SELECTION_SHAPES = [(64, 12), (384, 128), (33024, 2048), (64, 64), (64, 100), (200, 7)]


@pytest.mark.parametrize("lowering", ["jnp", "kernel"])
@pytest.mark.parametrize("kind", ["random", "quantised", "zeros", "both_zeros", "short",
                                  "one_visible", "k_visible", "full"])
@pytest.mark.parametrize("capacity,k", SELECTION_SHAPES,
                         ids=[f"{c}to{k}" for c, k in SELECTION_SHAPES])
def test_the_selection_is_top_ks_set_in_slot_order(capacity, k, kind, lowering, monkeypatch):
    """``largest_slots`` returns the set ``lax.top_k`` returns (of equal scores
    the lowest slots), in slot order, ``-1`` from the visible count on; the
    yardstick that calls the two zeros equal is numpy's stable sort."""
    monkeypatch.setenv("HEAT_TPU_PALLAS", "interpret" if lowering == "kernel" else "off")
    batch = 3
    x, visible = selection_scores(kind, batch, capacity, k, seed=capacity + k)
    form = la.selection_form(batch, capacity, k)
    assert form == ("all" if k >= capacity else "cut_kernel" if lowering == "kernel" else "cut_jnp")
    got = np.asarray(la.largest_slots(jnp.asarray(x), k))
    picked = min(k, capacity)
    assert got.shape == (batch, picked) and got.dtype == np.int32
    count = min(picked, visible)
    for row, slots in zip(x, got):
        assert (slots[count:] == -1).all()
        slots = slots[:count]
        assert (np.diff(slots) > 0).all() and slots.min(initial=0) >= 0      # none twice
        assert slots.max(initial=0) < visible
        assert np.array_equal(slots, np.sort(np.argsort(-row, kind="stable")[:count]))
    if kind != "both_zeros":      # the CPU's top_k puts -0.0 below 0.0
        top, slot = jax.lax.top_k(jnp.asarray(x), picked)
        theirs = np.where(np.asarray(top) > -np.inf, np.asarray(slot), -1)
        assert np.array_equal(np.sort(theirs, axis=-1), np.sort(got, axis=-1))


@pytest.mark.parametrize("capacity,k", [(64, 12), (384, 128), (33024, 2048)])
def test_the_two_lowerings_of_the_selection_agree_slot_for_slot(capacity, k, monkeypatch):
    x, _ = selection_scores("quantised", 4, capacity, k, seed=k)
    x[1], _ = selection_scores("short", 1, capacity, k, seed=1)
    x[2, : capacity // 2] = 0.0
    got = {}
    for how in ("off", "interpret"):
        monkeypatch.setenv("HEAT_TPU_PALLAS", how)
        got[how] = np.asarray(la.largest_slots(jnp.asarray(x), k))
    assert np.array_equal(got["off"], got["interpret"])


@pytest.mark.parametrize("how,batch,capacity,form", [
    ("tpu", 16, 33024, "cut_kernel"), ("tpu", 16, 64, "cut_jnp"), ("tpu", 16, 33024 + 64, "cut_jnp"),
    ("tpu", 16, 1 << 17, "cut_jnp"), ("tpu", 8, 1 << 17, "cut_kernel"),
    ("tpu", 1, 1 << 20, "cut_jnp"), ("interpret", 16, 64, "cut_kernel"),
    ("off", 16, 33024, "cut_jnp")])
def test_the_kernel_takes_whole_groups_that_fit_its_memory(how, batch, capacity, form,
                                                           monkeypatch):
    monkeypatch.setenv("HEAT_TPU_PALLAS", how)
    assert la.selection_form(batch, capacity, 2048 if capacity > 2048 else 12) == form
    assert la.selection_form(batch, capacity, capacity) == "all"


def test_sparse_select_scans_then_selects(monkeypatch):
    """The scan's scores, then their largest: a decode step's selection equals
    ``top_k`` of ``index_scores`` as a set, whatever the lowering."""
    rng = np.random.default_rng(3)
    batch, heads, width, capacity, k, kv_len = 2, 16, 16, 256, 24, 131
    q = jnp.asarray(rng.standard_normal((batch, heads, width)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((batch, heads)), jnp.float32)
    cache = jnp.asarray(rng.standard_normal((batch, capacity, width)), jnp.float32)
    _, theirs = jax.lax.top_k(la.index_scores(q, w, cache, kv_len), k)
    for how in ("off", "interpret"):
        monkeypatch.setenv("HEAT_TPU_PALLAS", how)
        got = np.asarray(la.sparse_select(q, w, cache, kv_len, k))
        assert np.array_equal(got, np.sort(np.asarray(theirs), axis=-1))


# ---- the router and the held experts

def routed(tokens=40, experts=8, d=32, seed=0, bias_std=0.3):
    ks = jax.random.split(jax.random.key(seed), 3)
    h = jax.random.normal(ks[0], (tokens, d), jnp.float32)
    router = jax.random.normal(ks[1], (d, experts), jnp.float32) * d ** -0.5
    return h, router, bias_std * jax.random.normal(ks[2], (experts,), jnp.float32)


@pytest.mark.parametrize("n_group,topk_group,top_k", [(4, 2, 2), (4, 1, 2), (2, 1, 3), (1, 1, 4),
                                                      (8, 4, 1)])
def test_router_is_the_references(n_group, topk_group, top_k):
    """Group-limited choice by ``s + b``; the bias takes no part in the
    weights; the weights sum to the scaling factor."""
    h, router, bias = routed(experts=16)
    weights, chosen = expert.sigmoid_group_routing(h, router, bias, top_k=top_k, n_group=n_group,
                                                   topk_group=topk_group, scale=2.5)
    cfg = {"router_experts": 16, "n_group": n_group, "topk_group": topk_group,
           "num_experts_per_tok": top_k, "routed_scaling_factor": 2.5}
    want = ref.route(cfg, {"router": router, "bias": bias}, h)
    dense = np.zeros((40, 16), np.float32)
    np.put_along_axis(dense, np.asarray(chosen), np.asarray(weights), axis=1)
    assert np.allclose(dense, np.asarray(want), rtol=1e-6, atol=0)
    assert np.allclose(np.asarray(weights).sum(1), 2.5, rtol=1e-6)
    s = np.asarray(jax.nn.sigmoid(h @ router))
    picked = np.take_along_axis(s, np.asarray(chosen), 1)
    assert np.allclose(np.asarray(weights), 2.5 * picked / picked.sum(1, keepdims=True), rtol=1e-5)
    # every chosen expert lies in one of the topk_group best groups, by the sum of their two best
    per = 16 // n_group
    best = np.sort((s + np.asarray(bias)).reshape(40, n_group, per), -1)[..., -2:].sum(-1)
    kept = np.argsort(-best, axis=1)[:, :topk_group]
    assert all(set(np.asarray(chosen[t]) // per) <= set(kept[t]) for t in range(40))
    # without the bias other experts are chosen: the bias is in the choice
    _, plain = expert.sigmoid_group_routing(h, router, 0 * bias, top_k=top_k, n_group=n_group,
                                            topk_group=topk_group, scale=2.5)
    assert not np.array_equal(np.sort(np.asarray(plain)), np.sort(np.asarray(chosen)))


def expert_layer(cfg, seed=21, tokens=24):
    params = biased(deepseek.DeepSeek(cfg, seed=seed), std=0.2).params["layers"][1]
    h = jax.random.normal(jax.random.key(seed + 1), (tokens, cfg.hidden_size), jnp.float32)
    return params["moe"], h


@pytest.mark.parametrize("share", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(share):
    """At 8 experts, the parts that shares of ``share`` give, with the shared
    expert counted once, sum to the reference's layer with every expert held."""
    whole = toy(experts_held=(0, 8))
    moe, h = expert_layer(whole)
    want, _ = ref.experts(as_reference(whole), moe, h, 16)
    total = deepseek._gated_mlp(moe["shared"], h)
    pairs = 0
    for first in range(0, 8, share):
        mine = {name: w[first:first + share] for name, w in moe["experts"].items()}
        part, counts = expert.held_experts_ffn(
            h, moe["router"], mine, held=(first, share), top_k=2, n_group=4, topk_group=2,
            scale=2.5, bias=moe["bias"])
        total = total + part
        pairs += int(counts["pairs"])
        # and the reference given the same share gives the same part
        cut = dict(as_reference(whole), experts_first=first, n_routed_experts=share)
        ref_part, _ = ref.experts(cut, dict(moe, experts=mine), h, 16)
        assert rel_err(part + deepseek._gated_mlp(moe["shared"], h), ref_part) < F32_TOL
    assert pairs == 24 * 2                                      # every pair computed once
    assert rel_err(total, want) < F32_TOL


@pytest.mark.parametrize("routing", ["seeded", "all_to_one", "none_here"])
@pytest.mark.parametrize("tokens,tile", [(24, 4), (24, 64), (70, 8)])
def test_the_two_lowerings_agree_and_drop_nothing(tokens, tile, routing, monkeypatch):
    cfg = toy()
    moe, h = expert_layer(cfg, tokens=tokens)
    bias = moe["bias"]
    if routing == "all_to_one":          # every token's two choices are experts 2 and 3: held
        bias = jnp.zeros(8).at[jnp.array([2, 3])].set(50.0)
    elif routing == "none_here":
        bias = jnp.zeros(8).at[jnp.array([2, 3])].set(-50.0)
    kw = dict(held=(2, 2), top_k=2, n_group=4, topk_group=2, scale=2.5, bias=bias)
    monkeypatch.setattr(expert, "STREAM_TOKENS", tokens)
    streamed, counts_s = expert.held_experts_ffn(h, moe["router"], moe["experts"], **kw)
    monkeypatch.setattr(expert, "STREAM_TOKENS", 0)
    monkeypatch.setattr(expert, "GROUP_TILE", tile)
    grouped, counts_g = jax.jit(lambda h: expert.held_experts_ffn(
        h, moe["router"], moe["experts"], **kw))(h)
    assert {k: int(v) for k, v in counts_s.items()} == {k: int(v) for k, v in counts_g.items()}
    want = {"seeded": None, "all_to_one": (2 * tokens, 2), "none_here": (0, 0)}[routing]
    if want:
        assert (int(counts_g["pairs"]), int(counts_g["hit"])) == want
    scale = float(jnp.max(jnp.abs(streamed))) or 1.0
    assert float(jnp.max(jnp.abs(streamed - grouped))) <= 1e-5 * scale
    cut = dict(as_reference(cfg))
    ref_part, _ = ref.experts(cut, dict(moe, bias=bias), h, 16)
    assert rel_err(grouped + deepseek._gated_mlp(moe["shared"], h), ref_part) < F32_TOL


# ---- the session

@pytest.mark.parametrize("prompt,steps,prefill_rows", [(40, 5, 2048), (40, 3, 16), (33, 4, 20),
                                                       (9, 6, 2048)])
def test_prefill_then_decode_equals_the_full_forward_pass(model, rows, prompt, steps,
                                                          prefill_rows):
    """Logits of every decoded position, and every position of the caches."""
    rows(prefill_rows)
    session, first, logits, wanted = served(model, 2, prompt, steps, seed=prompt)
    n = prompt + steps
    for b, want in enumerate(wanted):
        assert rel_err(first[b], want["logits"][0]) < F32_TOL
        assert rel_err(logits[b], want["logits"][1:]) < F32_TOL
        for layer in range(model.cfg.num_hidden_layers):
            held = session._shared
            rope = held["rope"][layer][b].reshape(session.capacity, -1)
            assert rel_err(held["latent"][layer][b, :n], want["latent"][layer][0]) < F32_TOL
            assert rel_err(rope[:n], want["latent"][layer][1]) < F32_TOL
            assert rel_err(held["index"][layer][b, :n], want["index"][layer]) < F32_TOL
            read = np.zeros(n, bool)
            slots = np.asarray(model.last_selection[layer, b])
            read[slots[slots >= 0]] = True
            assert np.array_equal(read, np.asarray(want["selected"][layer]))


def test_a_prompt_in_two_calls_is_the_prompt_in_one(model, rows):
    rows(16)
    tokens = prompts(model.cfg, 2, 31, seed=4)
    one = model.session(2, 40)
    whole = one.prefill(ht.array(tokens))
    two = model.session(2, 40)
    two.prefill(ht.array(tokens[:, :13]))
    parts = two.prefill(ht.array(tokens[:, 13:]))
    assert rel_err(parts.larray, whole.larray) < F32_TOL
    assert two.position == one.position == 31


@pytest.mark.parametrize("steps", [1, 4])
def test_rewind_with_an_empty_state_repeats_the_decode(model, steps):
    session = model.session(2, 64)
    session.prefill(ht.array(prompts(model.cfg, 2, 21, seed=2)))
    saved = session.save()
    assert saved.state == () and saved.position == 21 and saved.token.shape == (2,)
    tokens, logits = session.decode(steps)
    session.decode(2)
    assert session.position == 21 + steps + 2
    session.rewind(saved)
    assert session.position == 21
    assert np.array_equal(np.asarray(session._token), np.asarray(saved.token))
    again, logits_again = session.decode(steps)
    assert np.array_equal(np.asarray(tokens.larray), np.asarray(again.larray))
    assert np.array_equal(np.asarray(logits.larray), np.asarray(logits_again.larray))


@pytest.mark.parametrize("batch,dtype", [(1, "float32"), (3, "float32"), (2, "bfloat16")])
def test_cache_bytes_are_a_latent_row_and_an_index_key_a_position(batch, dtype):
    cfg = toy(dtype=dtype)
    session = deepseek.DeepSeek(cfg, seed=1).session(batch, 300)
    assert session.capacity == 512                     # whole blocks of 256 positions
    each = jnp.dtype(dtype).itemsize * (cfg.kv_lora_rank + cfg.qk_rope_head_dim
                                        + cfg.index_head_dim)
    assert session.cache_bytes() == {"shared": batch * 512 * cfg.num_hidden_layers * each}
    assert session._state == ()
    published = deepseek.DeepSeekConfig.from_dict(PUBLISHED, **CUT)
    assert 2 * (published.latent_width + published.index_head_dim) == 2 * (576 + 128)
    assert 5 * 2 * (576 + 128) == 7040


def test_session_refuses_what_it_cannot_hold(model):
    with pytest.raises(ValueError):
        model.session(1, model.cfg.max_position_embeddings + 1)
    session = model.session(2, 16)
    with pytest.raises(ValueError):
        session.decode(1)
    with pytest.raises(ValueError):
        session.prefill(ht.array(prompts(model.cfg, 2, 300)))
    with pytest.raises(ValueError):
        session.prefill(ht.array(prompts(model.cfg, 3, 4)))


def test_spans_counters_and_one_sync_a_decode(model):
    session = model.session(2, 64)
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
    decode = {e["name"]: e for e in begun}["lm.decode"]
    assert {k: decode[k] for k in ("batch", "context", "steps", "layers", "moe_layers", "selected",
                                   "select", "latent_bytes", "index_bytes", "experts_held",
                                   "expert_bytes")
            } == dict(batch=2, context=11, steps=2, layers=3, moe_layers=2, selected=12,
                      select="cut_jnp", latent_bytes=4 * 40, index_bytes=4 * 16, experts_held=2,
                      expert_bytes=4 * 3 * 64 * 32)
    lm = {k: after["lm"][k] - before["lm"][k] for k in
          ("decode_steps", "prefill_tokens", "index_keys_scanned", "latent_rows_read",
           "selections_by_cut", "state_bytes_copied", "state_bytes_stepped")}
    seen = [12, 13, 14, 12, 13]
    assert lm == {"decode_steps": 5, "prefill_tokens": 22, "index_keys_scanned": 2 * 3 * sum(seen),
                  "latent_rows_read": 2 * 3 * sum(min(12, s) for s in seen),
                  "selections_by_cut": 3 * 5, "state_bytes_copied": 0, "state_bytes_stepped": 0}
    pairs = after["lm"]["expert_pairs"] - before["lm"]["expert_pairs"]
    hit = after["lm"]["experts_hit"] - before["lm"]["experts_hit"]
    assert 0 <= hit <= 5 * 2 * 2 and hit <= pairs <= 5 * 2 * 2 * 2
    assert after["sync"]["count"] - before["sync"]["count"] == 2
    assert session.tokens.shape == (2, 2) and session.position == 13
    assert model.last_selection.shape == (3, 2, 12)


def test_counted_pairs_are_the_references(model):
    """``expert_pairs`` and ``experts_hit`` of one decode step are what the
    reference's routing weights say of the decoded position."""
    tokens = prompts(model.cfg, 2, 17, seed=8)
    session = model.session(2, 32)
    first = session.prefill(ht.array(tokens))
    before = telemetry.snapshot()["lm"]
    session.decode(1)
    after = telemetry.snapshot()["lm"]
    pairs = hit = 0
    first_token = np.asarray(jnp.argmax(first.larray, -1))
    x = None
    per_layer = [np.zeros((2, 2)) for _ in range(2)]
    for b in range(2):
        seq = jnp.asarray(np.concatenate([tokens[b], [first_token[b]]]))
        x = jnp.take(model.params["embed"], seq, axis=0).astype(jnp.float32)
        weights = [made["weights"] for _, made in ref.layers_of(as_reference(model.cfg),
                                                                model.params, x, 16)
                   if made["weights"] is not None]
        for layer, w in enumerate(weights):
            per_layer[layer][b] = np.asarray(w[-1])
    for w in per_layer:
        pairs += int((w > 0).sum())
        hit += int((w > 0).any(axis=0).sum())
    assert after["expert_pairs"] - before["expert_pairs"] == pairs
    assert after["experts_hit"] - before["experts_hit"] == hit


# ---- the published sizes, the generator, the precision

def test_published_parameter_counts():
    """ISSUE 34's arithmetic, from the catalog row's ``config``."""
    published = PUBLISHED
    if os.path.isfile(CATALOG):
        rows = [json.loads(line) for line in open(CATALOG, encoding="utf-8")]
        (row,) = [r for r in rows if r["name"] == "DeepSeek-V3.2"]
        assert row["config"] == PUBLISHED
        published = row["config"]
    whole = deepseek.DeepSeekConfig.from_dict(published)
    assert whole == deepseek.DeepSeekConfig()
    assert whole.experts_held == (0, 256) and whole.vocab_held == 129280
    n = deepseek.param_count(whole)
    mla = (7168 * 1536 + 1536 * 24576 + 7168 * 576 + 512 * 32768 + 16384 * 7168 + 1536 + 512)
    indexer = 1536 * 8192 + 7168 * 128 + 7168 * 64 + 256
    assert (mla, indexer) == (187_107_328, 13_959_424)
    assert n["attention"] == mla + indexer == 201_066_752
    assert n["expert"] == 3 * 7168 * 2048 == 44_040_192
    assert n["expert_layer"] == n["expert_layer_uncut"] == (
        201_066_752 + 2 * 7168 + 7168 * 256 + 256 + 257 * 44_040_192)
    assert round(n["expert_layer_uncut"] / 1e9, 2) == 11.52
    cut = deepseek.DeepSeekConfig.from_dict(published, **CUT)
    n = deepseek.param_count(cut)
    assert n["dense_layer"] == 201_066_752 + 3 * 7168 * 18432 + 2 * 7168 == 597_442_816
    assert n["expert_layer"] == 201_066_752 + 2 * 7168 + 7168 * 256 + 256 + 17 * 44_040_192
    assert n["embed"] == n["head"] == 16160 * 7168
    assert n["total"] == (n["dense_layer"] + 4 * n["expert_layer"] + 2 * 16160 * 7168 + 7168
                          ) == 4_635_518_208
    assert round(n["total"] / 1e6, 1) == 4635.5 and round(2 * n["total"] / 1e9, 2) == 9.27
    assert (cut.moe_layers, cut.latent_width, cut.qk_head_dim) == (4, 576, 192)


@pytest.mark.parametrize("bad", [{"experts_held": (250, 16)}, {"vocab_held": 0},
                                 {"scoring_func": "softmax"}, {"tie_word_embeddings": True},
                                 {"attention_bias": True}, {"n_group": 7},
                                 {"qk_rope_head_dim": 63}, {"n_shared_experts": 2}],
                         ids=lambda v: next(iter(v)))
def test_config_refuses_what_the_model_is_not(bad):
    with pytest.raises(ValueError):
        deepseek.DeepSeekConfig(**bad)


def test_generators_tree_is_the_programs():
    """``perf/generators/deepseek_weights.py`` draws the benchmark's weights
    without importing the program; its tree has to be the one ``param_spec``
    describes, the queries have to be tied to their own keys, and the model
    has to serve from it."""
    from perf.generators import deepseek_weights
    cfg = toy()
    config = {k: getattr(cfg, k) for k in (
        "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers",
        "first_k_dense_replace", "num_attention_heads", "q_lora_rank", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "index_n_heads", "index_head_dim")}
    config.update(n_routed_experts=2, vocab_size=48, dtype="float32", assumed={
        "sizes": {"router_experts": 8},
        "init": {"residual_scale": 0.4, "attention_out_scale": 1.5, "query_key_tie": 0.5,
                 "index_key_tie": 0.4, "index_bias": 6.4, "embed_std": 1.0, "bias_std": 0.1}})
    drawn = deepseek_weights.weights(config, jax.random.key(2), lambda leaf: leaf)
    own = deepseek.init_params(cfg, jax.random.key(1))
    assert jax.tree.structure(drawn) == jax.tree.structure(own)
    shapes = lambda tree: [(x.shape, x.dtype) for x in jax.tree.leaves(tree)]  # noqa: E731
    assert shapes(drawn) == shapes(own)
    attn = drawn["layers"][1]["attn"]
    assert np.array_equal(np.asarray(attn["w_dq"][:, :32]), np.asarray(attn["w_dkv"][:, :32]))
    w_uq = np.asarray(attn["w_uq"]).reshape(48, 4, 24)
    assert np.allclose(w_uq[:32, :, :16], 0.5 * np.transpose(np.asarray(attn["w_uk"]), (2, 0, 1)))
    w_iq = np.asarray(attn["w_iq"]).reshape(48, 16, 16)
    assert np.array_equal(np.asarray(attn["w_dq"][:, 32:]), np.asarray(attn["w_ik"]))
    assert np.allclose(w_iq[32:], 0.4 * np.diag([1.0] * 15 + [0.0])[:, None, :])
    offset = np.asarray(attn["ik_norm_b"])
    assert np.array_equal(offset, np.where(np.arange(16) < 15, 0.0, 6.4).astype(np.float32))
    through = np.einsum("rhj,j->rh", w_iq[:32], offset) / np.linalg.norm(offset)
    through = np.concatenate([through, np.zeros((16, 16))])
    assert np.allclose(np.asarray(attn["w_iw"]), np.asarray(attn["w_dq"]) @ through, atol=1e-6)
    assert float(jnp.std(drawn["layers"][1]["moe"]["bias"])) == pytest.approx(0.1, rel=0.6)
    session = deepseek.DeepSeek(cfg, drawn).session(1, 16)
    assert session.prefill(ht.array(prompts(cfg, 1, 5))).shape == (1, 48)


def test_limits_lie_between_the_stated_precision_and_the_one_below():
    """The float32 model passes ``F32_TOL`` and fails it with fp8 weights; the
    bfloat16 model (bfloat16 weights, caches and product operands) stays
    within 2e-2 of the float32 reference in the median position, and with its
    weights rounded to fp8 (e4m3) it does not.  The median, because at these
    toy widths one position in ten jumps by 0.05 to 0.12: a near-tie among
    two-of-eight experts or twelve-of-thirty keys that bfloat16 decides the
    other way is a whole expert or a twelfth of the keys (at the published
    widths it is one of 2,048 keys: PERF.md section 2)."""
    def errors(serving, seeds_own):
        tokens = prompts(serving.cfg, 2, 30, 9)
        session = serving.session(2, 38)
        start = np.asarray(jnp.argmax(session.prefill(ht.array(tokens)).larray, -1))
        chosen, got = (np.asarray(v.larray) for v in session.decode(8))
        errs = []
        for b in range(2):
            seq = jnp.asarray(np.concatenate([tokens[b], [start[b]], chosen[b, :-1]]))
            want = np.asarray(ref.forward(as_reference(serving.cfg), seeds_own.params, seq, 8,
                                          block=16)["logits"])
            errs.extend(np.linalg.norm(got[b] - want, axis=-1) / np.linalg.norm(want, axis=-1))
        return np.asarray(errs)

    def fp8(mdl):
        return deepseek.DeepSeek(mdl.cfg, jax.tree.map(
            lambda x: jax.lax.reduce_precision(x, 4, 3) if x.ndim >= 2 else x, mdl.params))

    exact = biased(deepseek.DeepSeek(toy(), seed=13))
    assert errors(exact, exact).max() < F32_TOL < errors(fp8(exact), exact).min()
    half = biased(deepseek.DeepSeek(toy(dtype="bfloat16"), seed=13))
    assert half.params["layers"][0]["attn"]["w_uq"].dtype == jnp.bfloat16
    assert half.session(1, 8)._shared["latent"][0].dtype == jnp.bfloat16
    assert 1e-4 < np.median(errors(half, half)) < 2e-2 < np.median(errors(fp8(half), half))


def test_one_session_class_serves_the_three_models():
    assert ht.models.DeepSeek is deepseek.DeepSeek
    assert ht.models.DeepSeekConfig is deepseek.DeepSeekConfig
    assert type(deepseek.DeepSeek(toy(), seed=1).session(1, 8)) is lm_session.DecodeSession


def test_quick_start_example_runs():
    """The DeepSeek example of docs/quick_start.md section 19 executes as
    written and leaves the section's other names alone."""
    import re

    text = open(os.path.join(ROOT, "docs", "quick_start.md"), encoding="utf-8").read()
    found = re.search(r"### A share of an expert-parallel model\n(.*?)\n## 20\.", text, re.S)
    assert found
    ns = {"ht": ht, "np": np}
    for block in re.findall(r"```python\n(.*?)```", found.group(1), re.S):
        exec(compile(block, "quick_start.md[deepseek]", "exec"), ns)
    assert isinstance(ns["share_session"].model, deepseek.DeepSeek)
    assert ns["share_session"].model.cfg.experts_held == (8, 4)


def test_lint_passes_on_the_new_modules():
    from heat_tpu.analysis import lint

    paths = [os.path.join(ROOT, "heat_tpu", p) for p in (
        "models/deepseek.py", "models/session.py", "ops/latent_attention.py",
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


def test_the_caches_lie_rows_major_on_the_chip(v5e):
    """Each cache's default layout on a v5e keeps a position's row together
    (the width is whole lane tiles), so that a step's write and its row gather
    work where the cache lies: a cache 576 wide would lie positions-minor and
    every decode program would hold a second copy of it (PERF.md section 6,
    PR 34).  A toy step compiled for the chip holds no copy of its caches."""
    cfg = deepseek.DeepSeekConfig.from_dict(PUBLISHED, **CUT)
    widths = (cfg.kv_lora_rank, la.ROPE_PACK * cfg.qk_rope_head_dim, cfg.index_head_dim)
    assert all(w % 128 == 0 for w in widths)
    batch, capacity = 4, 4096

    def step(latent, rope, index, c_kv, k_pe, k_idx, slots, pos):
        zero = np.int32(0)
        latent = jax.lax.dynamic_update_slice(latent, c_kv[:, None], (zero, pos, zero))
        rope = jax.lax.dynamic_update_slice(rope, k_pe[:, None],
                                            (zero, pos // 2, (pos % 2) * np.int32(64)))
        index = jax.lax.dynamic_update_slice(index, k_idx[:, None], (zero, pos, zero))
        rows = jnp.take_along_axis(latent, slots[:, :, None], axis=1)
        return latent, rope, index, rows.astype(jnp.float32).sum() + la.rope_rows(
            rope, slots).astype(jnp.float32).sum()

    shape = lambda *s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(s, dtype, sharding=v5e)  # noqa: E731
    compiled = jax.jit(step, donate_argnums=(0, 1, 2)).lower(
        shape(batch, capacity, 512), shape(batch, capacity // 2, 128), shape(batch, capacity, 128),
        shape(batch, 512), shape(batch, 64), shape(batch, 128),
        shape(batch, 256, dtype=jnp.int32), shape(dtype=jnp.int32)).compile()
    held = batch * capacity * (512 + 64 + 128) * 2
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= held
    assert memory.temp_size_in_bytes < held // 8
    for fmt in compiled.output_formats[:3]:
        assert tuple(fmt.layout.major_to_minor) == (0, 1, 2)


def test_the_selection_compiled_for_the_chip_holds_no_sort(v5e, monkeypatch):
    """``sparse_select`` at the cell's 16 x 33,024 -> 2,048 compiled for a v5e
    is the scan and the kernel ``ht_sparse_cut``: no sort in the optimised
    program, and no temporary larger than the scores themselves."""
    monkeypatch.setenv("HEAT_TPU_PALLAS", "tpu")
    batch, heads, width, capacity, k = 16, 64, 128, 33024, 2048
    assert la.selection_form(batch, capacity, k) == "cut_kernel"

    def select(q, w, cache, kv_len):
        return la.sparse_select(q, w, cache, kv_len, k)

    shape = lambda *s, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(s, dtype, sharding=v5e)  # noqa: E731
    with jax.enable_x64(False):
        compiled = jax.jit(select).lower(
            shape(batch, heads, width), shape(batch, heads, dtype=jnp.float32),
            shape(batch, capacity, width), shape(dtype=jnp.int32)).compile()
    text = compiled.as_text()
    assert not re.search(r"\bsort\(|topk", text, re.I) and "ht_sparse_cut" in text
    scores = batch * (-(-capacity // (128 * 128)) * 128 * 128) * 4     # padded to the kernel's groups
    assert compiled.memory_analysis().temp_size_in_bytes <= scores
    # the kernel reserves no more VMEM than any kernel has: with 64 MiB reserved, XLA could no
    # longer keep a layer's rope cache (67 MB) in VMEM for the gather behind it (PERF.md, PR 35)
    call = next(line for line in text.splitlines() if "ht_sparse_cut" in line and "custom-call(" in line)
    assert max(map(int, re.findall(r'"size":"(\d+)"', call)), default=0) <= 16 << 20
