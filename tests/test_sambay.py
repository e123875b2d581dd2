"""SambaY (``heat_tpu.models.sambay``) against the plain reference
(``perf/reference/sambay.py``) at toy sizes on the CPU, seeded random weights.

Tolerances.  The toy model of most tests computes in float32, so the program
and the reference differ only in the order of float32 sums (blockwise softmax,
chunked scan, cached keys): relative 2-norm errors of the logits come out at
1e-6; the limit 5e-5 leaves room for longer sums and fails anything coarser: a
bfloat16 cache reads 8e-4 and up (``test_precision_separates``).  The bfloat16
model reads 1e-2 to 2.5e-2 against the float32 reference at this size (eight
layers of bfloat16 products) and is held to 4e-2, which the reference computed
with fp8 operands fails.
"""

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
from heat_tpu.models import sambay  # noqa: E402
from heat_tpu.ops import decode_attention as da  # noqa: E402
from heat_tpu.ops.selective_scan import selective_scan, selective_step  # noqa: E402
from perf.reference import sambay as ref  # noqa: E402

F32_TOL = 5e-5


def toy(dtype="float32", **over):
    sizes = dict(vocab_size=96, hidden_size=64, intermediate_size=128, num_attention_heads=8,
                 num_key_value_heads=4, num_hidden_layers=8, sliding_window=8, d_inner=128,
                 d_state=4, d_conv=4, dt_rank=8, dtype=dtype)
    sizes.update(over)
    return sambay.SambaYConfig(**sizes)


def as_reference(cfg, operands=None):
    out = {k: getattr(cfg, k) for k in ref.SIZES}
    out.update(num_hidden_layers=cfg.num_hidden_layers, layer_types=cfg.layer_types,
               operands=operands)
    return out


def rel_err(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.linalg.norm(got - want, axis=-1) / np.linalg.norm(want, axis=-1)))


def prompts(cfg, batch, length, seed=0):
    return np.random.default_rng(seed).integers(0, cfg.vocab_size, (batch, length)).astype(np.int32)


def served_against_reference(model, tokens, steps, session):
    """Worst relative error, over sequences and positions, of the logits that
    prefill + decode return against the full forward pass over the prompt and
    the tokens the program fed back."""
    first = session.prefill(ht.array(tokens))
    chosen, logits = session.decode(steps)
    chosen, logits = np.asarray(chosen.larray), np.asarray(logits.larray)
    first_token = np.asarray(jnp.argmax(first.larray, -1))
    worst = 0.0
    for b in range(tokens.shape[0]):
        seq = np.concatenate([tokens[b], [first_token[b]], chosen[b, :-1]])
        want = ref.logits_at_end(as_reference(model.cfg), model.params, jnp.asarray(seq), steps + 1)
        got = np.concatenate([np.asarray(first.larray)[b][None], logits[b]])
        worst = max(worst, rel_err(got, want))
    assert np.array_equal(chosen, np.argmax(logits, -1))
    return worst


@pytest.fixture(scope="module")
def model():
    return sambay.SambaY(toy(), seed=3)


@pytest.fixture
def tiles(monkeypatch):
    """Set the module's tile constants for a test: toy contexts then cross
    chunk and block boundaries as the published sizes do at theirs."""
    def patch(prefill_chunk=None, attn_block=None, scan_chunk=None):
        for name, value in (("PREFILL_CHUNK", prefill_chunk), ("ATTN_BLOCK", attn_block),
                            ("SCAN_CHUNK", scan_chunk)):
            if value is not None:
                monkeypatch.setattr(sambay, name, value)
    return patch


CASES = {
    # prompt, steps, prefill chunk, window: what the case crosses
    "window_rolls_over_chunks_uneven": (21, 5, 6, 8),
    "chunk_longer_than_window": (40, 3, 16, 4),
    "chunk_of_one_position": (9, 4, 1, 8),
    "prompt_inside_one_window": (5, 2, 8, 8),
    "prompt_one_chunk_many_windows": (33, 4, 64, 8),
    "decode_rolls_the_window": (7, 12, 4, 8),
}


@pytest.mark.parametrize("prompt,steps,chunk,window", list(CASES.values()), ids=list(CASES))
def test_prefill_then_decode_equals_full_forward(prompt, steps, chunk, window, tiles):
    mdl = sambay.SambaY(toy(sliding_window=window), seed=5)
    tiles(prefill_chunk=chunk, attn_block=16, scan_chunk=4)
    session = mdl.session(2, prompt + steps)
    err = served_against_reference(mdl, prompts(mdl.cfg, 2, prompt, seed=prompt), steps, session)
    assert err < F32_TOL


def test_published_placement_at_toy_widths(tiles):
    """All 32 layers in the published order, window 4."""
    mdl = sambay.SambaY(toy(num_hidden_layers=32, sliding_window=4), seed=7)
    assert mdl.cfg.layer_types == sambay.SambaYConfig().layer_types
    tiles(prefill_chunk=8, attn_block=8)
    session = mdl.session(1, 24)
    assert served_against_reference(mdl, prompts(mdl.cfg, 1, 14), 3, session) < F32_TOL


def test_rewind_returns_to_the_saved_position(model, tiles):
    tiles(prefill_chunk=6, attn_block=8)
    session = model.session(2, 40)
    session.prefill(ht.array(prompts(model.cfg, 2, 19)))
    saved = session.save()
    tokens, logits = (np.asarray(v.larray) for v in session.decode(6))
    assert session.position == 25
    session.rewind(saved)
    assert session.position == 19
    again_tokens, again_logits = (np.asarray(v.larray) for v in session.decode(6))
    assert np.array_equal(tokens, again_tokens) and np.array_equal(logits, again_logits)
    # a snapshot holds the constant-size states and no copy of the shared cache
    held = sum(int(leaf.nbytes) for leaf in jax.tree.leaves(saved.state))
    sizes = session.cache_bytes()
    assert held == sizes["window"] + sizes["state"]
    # the same six steps as two calls, from the same saved position
    session.rewind(saved)
    session.decode(2)
    more_tokens, more_logits = session.decode(4)
    assert np.array_equal(np.asarray(more_tokens.larray), tokens[:, 2:])
    assert rel_err(np.asarray(more_logits.larray), logits[:, 2:]) < F32_TOL


@pytest.mark.parametrize("chunk", [1, 4, 7, 64])
def test_chunked_scan_equals_sequential(chunk):
    keys = jax.random.split(jax.random.key(11), 6)
    batch, seq, di, ds = 2, 23, 16, 4
    u = jax.random.normal(keys[0], (batch, seq, di), jnp.float32)
    delta = jax.nn.softplus(jax.random.normal(keys[1], (batch, seq, di), jnp.float32))
    a = -jnp.exp(jax.random.normal(keys[2], (ds, di), jnp.float32))
    b = jax.random.normal(keys[3], (batch, seq, ds), jnp.float32)
    c = jax.random.normal(keys[4], (batch, seq, ds), jnp.float32)
    d = jnp.ones((di,), jnp.float32)
    state = jax.random.normal(keys[5], (batch, ds, di), jnp.float32)
    want, carried = [], state
    for t in range(seq):
        y, carried = selective_step(u[:, t], delta[:, t], a, b[:, t], c[:, t], d, carried)
        want.append(y)
    y, last = selective_scan(u, delta, a, b, c, d, state, chunk=chunk)
    # the same float32 operations in the same order: equal to rounding
    np.testing.assert_allclose(np.asarray(y), np.stack(want, 1), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(last), np.asarray(carried), rtol=1e-6, atol=1e-6)


# ---- each layer kind alone, against its equation as the reference writes it

def _layer(model, kind):
    index = model.cfg.layer_types.index(kind)
    return index, model.params["layers"][index]


def _hidden(model, seq, seed):
    return jax.random.normal(jax.random.key(seed), (2, seq, model.cfg.hidden_size), jnp.float32)


def test_mamba_layer_alone(model):
    cfg = model.cfg
    _, p = _layer(model, "mamba")
    h = _hidden(model, 19, 1)
    tail = jnp.zeros((2, cfg.d_conv - 1, cfg.d_inner), jnp.float32)
    state = jnp.zeros((2, cfg.d_state, cfg.d_inner), jnp.float32)
    out, y, _, _ = sambay._mamba(cfg, p["mixer"], h, tail, state, 4)
    for b in range(2):
        want_out, want_y = ref.mamba_mixer(as_reference(cfg), p["mixer"], h[b])
        assert rel_err(out[b], want_out) < F32_TOL and rel_err(y[b], want_y) < F32_TOL


def test_mamba_layer_carries_tail_and_state_between_chunks(model):
    cfg = model.cfg
    _, p = _layer(model, "mamba")
    h = _hidden(model, 19, 2)
    tail = jnp.zeros((2, cfg.d_conv - 1, cfg.d_inner), jnp.float32)
    state = jnp.zeros((2, cfg.d_state, cfg.d_inner), jnp.float32)
    whole, _, _, _ = sambay._mamba(cfg, p["mixer"], h, tail, state, 4)
    parts = []
    for lo, hi in ((0, 7), (7, 8), (8, 19)):        # the middle part takes the one-step path
        out, _, tail, state = sambay._mamba(cfg, p["mixer"], h[:, lo:hi], tail, state, 4)
        parts.append(out)
    assert rel_err(jnp.concatenate(parts, 1), whole) < F32_TOL


def test_window_layer_alone(model):
    cfg = model.cfg
    layer, p = _layer(model, "window")
    h = _hidden(model, 21, 3)
    ring = tuple(jnp.zeros((2, cfg.kv_groups, cfg.sliding_window, 2 * cfg.head_dim), jnp.float32)
                 for _ in range(2))
    out, _, _ = sambay._attention(cfg, "window", layer, p["mixer"], h, jnp.int32(0), None, ring, 16)
    for b in range(2):
        want = ref.window_mixer(as_reference(cfg), p["mixer"], ref.lambda_init(layer), h[b])
        assert rel_err(out[b], want) < F32_TOL


def _reference_attention(cfg, p, layer, h_q, h_kv, q_pos):
    """The full layer's equation on one sequence: queries ``h_q`` at ``q_pos``
    over the keys and values of every row of ``h_kv``."""
    q_width = cfg.num_attention_heads * cfg.head_dim
    kv_width = cfg.num_key_value_heads * cfg.head_dim
    w = p["w_qkv"].astype(jnp.float32)
    kv = jnp.dot(h_kv, w[:, q_width:], precision="highest")
    q = jnp.dot(h_q, w[:, :q_width], precision="highest")
    rows = ref.diff_attention(as_reference(cfg), p, ref.lambda_init(layer), q, kv[:, :kv_width],
                              kv[:, kv_width:], q_pos, jnp.arange(h_kv.shape[0]))
    return jnp.dot(rows, p["w_o"].astype(jnp.float32), precision="highest")


def test_full_layer_alone_in_chunks_and_one_position(model):
    cfg = model.cfg
    layer, p = _layer(model, "full")
    h = _hidden(model, 22, 4)
    shared = tuple(jnp.zeros((2, cfg.kv_groups, 32, 2 * cfg.head_dim), jnp.float32) for _ in range(2))
    outs = []
    for lo, hi in ((0, 9), (9, 21), (21, 22)):      # two chunks, then the decode path
        out, shared, _ = sambay._attention(
            cfg, "full", layer, p["mixer"], h[:, lo:hi], jnp.int32(lo), shared, None, 8)
        outs.append(out)
    got = jnp.concatenate(outs, 1)
    for b in range(2):
        want = _reference_attention(cfg, p["mixer"], layer, h[b], h[b], jnp.arange(22))
        assert rel_err(got[b], want) < F32_TOL


def test_cross_layer_alone_reads_the_full_layers_cache(model):
    cfg = model.cfg
    full_layer, full = _layer(model, "full")
    layer, p = _layer(model, "cross")
    h = _hidden(model, 13, 5)
    shared = tuple(jnp.zeros((2, cfg.kv_groups, 16, 2 * cfg.head_dim), jnp.float32) for _ in range(2))
    _, shared, _ = sambay._attention(
        cfg, "full", full_layer, full["mixer"], h, jnp.int32(0), shared, None, 8)
    h_q = _hidden(model, 1, 6)
    out, _, _ = sambay._attention(
        cfg, "cross", layer, p["mixer"], h_q, jnp.int32(12), shared, None, 8)
    ref_cfg = as_reference(cfg)
    q_width, kv_width = ref._widths(ref_cfg)
    for b in range(2):
        kv = jnp.dot(h[b], full["mixer"]["w_qkv"][:, q_width:], precision="highest")
        q = jnp.dot(h_q[b], p["mixer"]["w_q"], precision="highest")
        rows = ref.diff_attention(ref_cfg, p["mixer"], ref.lambda_init(layer), q, kv[:, :kv_width],
                                  kv[:, kv_width:], jnp.array([12]), jnp.arange(13))
        want = jnp.dot(rows, p["mixer"]["w_o"], precision="highest")
        assert rel_err(out[b], want) < F32_TOL


def test_gated_memory_unit_alone(model):
    cfg = model.cfg
    layer, p = _layer(model, "gmu")
    x = _hidden(model, 1, 7)
    memory = jax.random.normal(jax.random.key(8), (2, 1, cfg.d_inner), jnp.float32)
    empty = {"ring": (), "conv": (), "ssm": ()}
    got, _, _, _ = sambay._run_layers(cfg, model.params, layer, layer + 1, x, jnp.int32(3), None,
                                      empty, memory, block=8, scan_chunk=1)
    for b in range(2):
        h = ref.layer_norm(x[b], p["norm1"], cfg.layer_norm_eps)
        mixed = jnp.dot(jax.nn.silu(jnp.dot(h, p["mixer"]["w_in"], precision="highest")) * memory[b],
                        p["mixer"]["w_out"], precision="highest")
        want = ref.mlp(as_reference(cfg), p, x[b] + mixed)
        assert rel_err(got[b], want) < F32_TOL


# ---- the cache, the counts, the kernel, the precision

def test_shared_cache_is_allocated_once(model, tiles):
    cfg = model.cfg
    batch, context = 3, 50
    tiles(attn_block=16)
    session = model.session(batch, context)
    capacity = 64                                                  # 50 rounded up to blocks of 16
    assert session.capacity == capacity
    sizes = session.cache_bytes()
    item = jnp.dtype(cfg.dtype).itemsize
    token = 2 * cfg.num_key_value_heads * cfg.head_dim * item      # keys and values, every head
    assert sizes["shared"] == batch * capacity * token             # once, not once a reading layer
    assert len(jax.tree.leaves(session._shared)) == 2
    assert sizes["window"] == cfg.layer_types.count("window") * batch * cfg.sliding_window * token
    assert sizes["state"] == cfg.layer_types.count("mamba") * batch * (
        cfg.d_state * cfg.d_inner * 4 + (cfg.d_conv - 1) * cfg.d_inner * item)
    assert telemetry.snapshot_group("lm")["cache_bytes"] == sizes


def test_published_parameter_counts():
    """ISSUE 27's table, from the published config and the assumed sizes:
    shapes only, nothing is allocated."""
    cfg = sambay.SambaYConfig()
    counts = sambay.param_count(cfg)
    d, f, di = 2560, 10240, 5120
    assert counts["mlp"] == 3 * d * f
    small = 4 * d + 4 * 64 + 128                   # two LayerNorms, lambda vectors, RMS gain
    assert counts["window"] == counts["full"] == d * (2560 + 2 * 1280) + 2560 * d + 3 * d * f + small
    assert counts["cross"] == 2 * 2560 * d + 3 * d * f + small
    assert counts["gmu"] == 2 * d * di + 3 * d * f + 4 * d
    mamba_small = di * (160 + 32) + 160 * di + di + 16 * di + 4 * di + di + di
    assert counts["mamba"] == d * 2 * di + di * d + mamba_small + 3 * d * f + 4 * d
    assert counts["embed"] == 200064 * d
    for kind, millions in (("mamba", 119.9), ("window", 98.3), ("gmu", 104.9), ("cross", 91.8)):
        assert round(counts[kind] / 1e6, 1) == millions
    kinds = cfg.layer_types
    assert [kinds.count(k) for k in ("mamba", "window", "full", "gmu", "cross")] == [9, 8, 1, 7, 7]
    assert counts["total"] == counts["embed"] + 2 * d + sum(counts[k] for k in kinds)
    assert round(counts["total"] / 1e9, 2) == 3.85 and round(2 * counts["total"] / 1e9, 2) == 7.70
    assert 2 * cfg.num_key_value_heads * cfg.head_dim * 2 == 5120     # a token of the shared cache


@pytest.mark.parametrize("kinds", [
    ("mamba", "window", "gmu", "cross"),                  # no full layer
    ("mamba", "full", "full", "cross"),                   # two caches
    ("mamba", "full", "window", "cross"),                 # a window layer after the full one
    ("window", "full", "gmu", "cross"),                   # a memory unit without a Mamba layer
    ("mamba", "full", "gmu", "attention"),                # an unknown kind
])
def test_config_refuses_impossible_stacks(kinds):
    with pytest.raises(ValueError):
        toy(num_hidden_layers=4, layer_types=kinds)


def test_config_from_published_dict():
    published = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 8,
                 "num_key_value_heads": 4, "num_hidden_layers": 8, "sliding_window": 8,
                 "vocab_size": 96, "mb_per_layer": 2, "model_type": "phi4flash", "mlp_bias": False}
    cfg = sambay.SambaYConfig.from_dict(published, d_inner=128, d_state=4, dt_rank=8)
    assert cfg.layer_types == ("mamba", "window", "mamba", "window", "mamba", "full", "gmu", "cross")
    assert cfg.head_dim == 8 and cfg.kv_groups == 2 and cfg.n_self == 6


# block 256 (a piece is 32 keys), capacity 1,024: under one block (the pipeline's
# block, masked), on and around a bfloat16 tile edge, a block edge (no tail),
# just past a block edge (the piece), a piece edge, past a piece (the pipeline
# again), and the whole capacity
KV_LENS = [1, 15, 16, 17, 255, 256, 257, 272, 287, 288, 289, 300, 511, 512, 513, 544, 545, 700,
           1023, 1024]


def kernel_operands(kv_len):
    keys = jax.random.split(jax.random.key(kv_len), 3)
    shape = (2, 3, 1024, 128)
    q = jax.random.normal(keys[0], (2, 3, 4, 128), jnp.float32).astype(jnp.bfloat16)
    k = jax.random.normal(keys[1], shape, jnp.float32).astype(jnp.bfloat16)
    v = jax.random.normal(keys[2], shape, jnp.float32).astype(jnp.bfloat16)
    return q, k, v


def copied(kv_len, block=256):
    """Whether the kernel brings the last block of ``kv_len`` keys as a piece."""
    return bool(da._tail_rule(*divmod(kv_len, block), da._piece(block))[1])


def fallback_attention(q, k, v, kv_len):
    """The plain ``jax.numpy`` path, which shares no line with the kernel: every
    block of 256 keys a sum of its own, softmax weights rounded to bfloat16
    before the value product as in the kernel."""
    return da.masked_attention(q, k, v, jnp.full((4,), kv_len - 1), jnp.arange(1024),
                               scale=0.125, kv_len=jnp.int32(kv_len), block=256)


def fallback_tol(kv_len):
    """How far the kernel may lie from :func:`fallback_attention` (readings on
    these operands, interpreter on the CPU).  The fallback without its newest
    key lies 1.6e-2 (1,023 keys) to 3.7 (one key) from itself, and 3.4e-2 at
    least at the lengths with a copied piece: every limit here sees one lost key.

    * Nothing copied: the parent's sum in the parent's order, and the parent's
      limit 2e-5 (readings 0 to 7.1e-6).
    * 545: 1.15e-4, one weight of 0.03 that rounds to the other bfloat16
      neighbour (XLA's batched product and the kernel's product of one stream
      sum in another order, a float32 ulp apart in a score).
    * A piece copied: the piece is folded in one sum with the block before it,
      so the weights of both round from another running maximum than in the
      fallback: 6.6e-7 to 9.3e-4."""
    if copied(kv_len):
        return 2e-3
    return 2e-4 if kv_len == 545 else 2e-5


@pytest.mark.parametrize("kv_len", KV_LENS)
def test_decode_kernel_equals_plain_attention(kv_len):
    q, k, v = kernel_operands(kv_len)
    got = da._decode_pallas(q, k, v, jnp.int32(kv_len), scale=0.125, block=256, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(fallback_attention(q, k, v, kv_len)),
                               rtol=0, atol=fallback_tol(kv_len))
    exact = jax.nn.softmax(
        jnp.where(jnp.arange(1024) < kv_len,
                  jnp.einsum("bgmd,bgtd->bgmt", q.astype(jnp.float32), k.astype(jnp.float32)) * 0.125,
                  -jnp.inf), axis=-1) @ v.astype(jnp.float32)
    # the weights' rounding to bfloat16: 2.3e-3 at 15 keys, 1.1e-3 and less from 255 on
    np.testing.assert_allclose(np.asarray(got), np.asarray(exact), rtol=0, atol=5e-3)


@pytest.mark.parametrize("kv_len", KV_LENS)
def test_decode_kernel_weighs_every_visible_key_once(kv_len):
    """Operands whose softmax weights do not round: each query row reads one
    coordinate of the keys, which holds 0, -1 or -2, and the scale is ln 2, so
    every weight is 1, 1/2 or 1/4 whatever maximum it is taken from, exactly a
    bfloat16.  What is left between the kernel and the float32 softmax is the
    order of float32 sums (readings 7.7e-9 to 7.6e-8); one key of 1,024 left out or
    counted twice moves a value by 5.4e-3 and more."""
    _, k, v = kernel_operands(kv_len)
    powers = jax.random.randint(jax.random.key(kv_len + 5000), (2, 3, 1024, 4), 0, 3)
    k = k.at[..., :4].set(-powers.astype(jnp.bfloat16))
    q = jnp.broadcast_to(jnp.eye(4, 128, dtype=jnp.bfloat16), (2, 3, 4, 128))
    unseen = jnp.arange(1024)[:, None] >= da.keys_fetched(kv_len, 1024, 256)
    got = da._decode_pallas(q, jnp.where(unseen, jnp.nan, k), jnp.where(unseen, jnp.nan, v),
                            jnp.int32(kv_len), scale=math.log(2), block=256, interpret=True)
    weights = jnp.where(jnp.arange(1024) < kv_len, 0.5 ** jnp.swapaxes(powers, 2, 3), 0.0)
    exact = (weights / weights.sum(-1, keepdims=True)) @ v.astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exact), rtol=0, atol=1e-6)


@pytest.mark.parametrize("kv_len", KV_LENS)
def test_decode_kernel_never_touches_unseen_slots(kv_len):
    """Keys and values are NaN in every slot past those that ``keys_fetched``
    says the kernel fetches: a kernel that computed on one of them gives NaN
    (a masked weight of 0 times NaN is NaN), so the rule holds the kernel.
    Just past a block edge that is all but a piece of the last block."""
    q, k, v = kernel_operands(kv_len)
    unseen = jnp.arange(1024)[:, None] >= da.keys_fetched(kv_len, 1024, 256)
    got = da._decode_pallas(q, jnp.where(unseen, jnp.nan, k), jnp.where(unseen, jnp.nan, v),
                            jnp.int32(kv_len), scale=0.125, block=256, interpret=True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(fallback_attention(q, k, v, kv_len)),
                               rtol=0, atol=fallback_tol(kv_len))


@pytest.mark.parametrize("kv_len,slots,block,fetched", [
    # the benchmark's cell: 16 whole blocks and one piece of 256 keys, of 34,816 slots
    (32769, 34816, 2048, 32768 + 256), (32776, 34816, 2048, 32768 + 256),
    (32768 + 256, 34816, 2048, 32768 + 256),
    # more than a piece of the last block visible: the block, through the pipeline
    (32768 + 257, 34816, 2048, 34816), (34815, 34816, 2048, 34816),
    # multiples of the block: what is visible, no piece
    (32768, 34816, 2048, 32768), (2048, 34816, 2048, 2048), (34816, 34816, 2048, 34816),
    # under one block: the pipeline's first block, as there is none to hide a copy behind
    (1, 34816, 2048, 2048), (2047, 34816, 2048, 2048),
    # a capacity of one short block (a toy session), and blocks under a tile
    (5, 64, 2048, 64), (64, 64, 2048, 64), (11, 32, 8, 16), (3, 8, 8, 8),
])
def test_keys_fetched_rule(kv_len, slots, block, fetched):
    assert da.keys_fetched(kv_len, slots, block) == fetched


def test_masked_attention_reads_a_ring_in_any_order():
    keys = jax.random.split(jax.random.key(2), 3)
    q = jax.random.normal(keys[0], (1, 2, 3, 16), jnp.float32)
    k = jax.random.normal(keys[1], (1, 2, 8, 16), jnp.float32)
    v = jax.random.normal(keys[2], (1, 2, 8, 16), jnp.float32)
    pos = sambay.ring_positions(jnp.int32(10), 8)           # slots 3..7 hold 3..7, 0..2 hold 8..10
    assert pos.tolist() == [8, 9, 10, 3, 4, 5, 6, 7]
    assert sambay.ring_positions(jnp.int32(2), 8).tolist() == [0, 1, 2, -5, -4, -3, -2, -1]
    got = da.masked_attention(q, k, v, jnp.full((3,), 10), pos, scale=0.25, window=8, block=8)
    order = np.argsort(np.asarray(pos))
    want = da.masked_attention(q, k[:, :, order], v[:, :, order], jnp.full((3,), 10),
                               jnp.arange(3, 11), scale=0.25, window=8, block=4)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_precision_separates(tiles):
    """Each limit lies between the stated precision and the one below it.  The
    float32 model passes ``F32_TOL`` and fails it with a bfloat16 shared cache.
    The bfloat16 model passes 4e-2 (it reads 1e-2 to 2.5e-2 here), and the
    reference itself, computed with fp8 operands, does not.  A reference with
    bfloat16 operands is no closer to the bfloat16 program than the float32
    one: after a few layers two computations round independently."""
    def served(mdl, rounding=None, operands=None):
        tokens = prompts(mdl.cfg, 2, 30, seed=9)
        session = mdl.session(2, 40)
        first = session.prefill(ht.array(tokens))
        if rounding:
            session._shared = tuple(jax.lax.reduce_precision(x, *rounding) for x in session._shared)
        chosen, logits = (np.asarray(v.larray) for v in session.decode(4))
        first_token = np.asarray(jnp.argmax(first.larray, -1))
        worst, lower = 0.0, 0.0
        for b in range(2):
            seq = jnp.asarray(np.concatenate([tokens[b], [first_token[b]], chosen[b, :-1]]))
            want = ref.logits_at_end(as_reference(mdl.cfg), mdl.params, seq, 4)
            worst = max(worst, rel_err(logits[b], want))
            if operands:
                low = ref.logits_at_end(as_reference(mdl.cfg, operands), mdl.params, seq, 4)
                lower = max(lower, rel_err(low, want))
        return worst, lower

    tiles(prefill_chunk=8, attn_block=8)
    exact = sambay.SambaY(toy(), seed=13)
    assert served(exact)[0] < F32_TOL < served(exact, rounding=(8, 7))[0]
    half = sambay.SambaY(toy(dtype="bfloat16"), seed=13)
    program, reference_in_fp8 = served(half, operands="float8_e4m3fn")
    assert program < 4e-2 < reference_in_fp8


@pytest.fixture
def kernel_runs(monkeypatch):
    """The session's programs read the shared cache through the Pallas kernel
    (the interpreter here), as on a TPU; the tests' default is the fallback."""
    def retrace():
        sambay._decode.clear_cache()
        sambay._prefill_finish.clear_cache()
    monkeypatch.setenv("HEAT_TPU_PALLAS", "interpret")
    retrace()
    yield
    retrace()


def test_spans_counters_and_one_sync_a_decode(model, tiles, kernel_runs):
    tiles(prefill_chunk=8, attn_block=8)
    session = model.session(2, 32)
    before = telemetry.snapshot()
    with telemetry.telemetry_level("events"):
        telemetry.clear_events()
        session.prefill(ht.array(prompts(model.cfg, 2, 11)))
        saved = session.save()
        session.decode(3)
        session.rewind(saved)
        session.decode(2)
        names = [e["name"] for e in telemetry.events("span_begin")]
        ends = {e["name"]: e for e in telemetry.events("span_begin")}
    after = telemetry.snapshot()
    assert names == ["lm.prefill", "lm.decode", "sync:lm.tokens", "lm.rewind", "lm.decode",
                     "sync:lm.tokens"]
    assert ends["lm.prefill"]["tokens"] == 22 and ends["lm.prefill"]["chunk"] == 8
    assert ends["lm.decode"]["batch"] == 2 and ends["lm.decode"]["context"] == 11
    # what the roofline's reader takes from the span: the readers of the shared
    # cache (the full layer and the cross layer of the toy stack) and a token in it
    assert ends["lm.decode"]["steps"] == 2 and ends["lm.decode"]["readers"] == 2
    assert ends["lm.decode"]["token_bytes"] == 2 * 4 * 8 * 4
    # keys the decode kernel's rule fetches for 2 sessions x 2 readers at lengths
    # 12 and 13 of 32 slots in blocks of 8: one whole block and one piece of 8
    assert ends["lm.decode"]["fetched"] == 4 * (16 + 16) and ends["lm.decode"]["context"] == 11
    visible = 4 * (12 + 13 + 14) + 4 * (12 + 13)
    assert after["lm"]["cache_keys_visible"] - before["lm"]["cache_keys_visible"] == visible
    assert after["lm"]["cache_keys_fetched"] - before["lm"]["cache_keys_fetched"] == 4 * 16 * 5
    assert after["lm"]["decode_steps"] - before["lm"]["decode_steps"] == 5
    assert after["lm"]["prefill_tokens"] - before["lm"]["prefill_tokens"] == 22
    assert after["sync"]["count"] - before["sync"]["count"] == 2
    assert after["sync"]["by_site"]["lm.tokens"] - before["sync"]["by_site"].get("lm.tokens", 0) == 2
    assert session.tokens.shape == (2, 2) and session.position == 13


def test_fetch_counters_stand_still_under_the_fallback(model, tiles):
    """``cache_keys_fetched`` states the kernel's rule, so where the kernel does
    not run (the tests' default: ``masked_attention`` reads the cache) neither it
    nor ``cache_keys_visible`` moves, and the span carries no ``fetched``."""
    tiles(prefill_chunk=8, attn_block=8)
    session = model.session(2, 32)
    session.prefill(ht.array(prompts(model.cfg, 2, 11)))
    before = telemetry.snapshot()["lm"]
    with telemetry.telemetry_level("events"):
        telemetry.clear_events()
        session.decode(2)
        (span,) = [e for e in telemetry.events("span_begin") if e["name"] == "lm.decode"]
    after = telemetry.snapshot()["lm"]
    assert "fetched" not in span and span["context"] == 11
    assert after["decode_steps"] - before["decode_steps"] == 2
    assert after["cache_keys_visible"] == before["cache_keys_visible"]
    assert after["cache_keys_fetched"] == before["cache_keys_fetched"]


def test_session_refuses_what_it_cannot_hold(model):
    session = model.session(2, 16)
    with pytest.raises(ValueError):
        session.decode(1)                                   # no prompt yet
    with pytest.raises(ValueError):
        session.prefill(ht.array(prompts(model.cfg, 3, 4)))  # another batch
    with pytest.raises(ValueError):
        session.prefill(ht.array(prompts(model.cfg, 2, 17)))  # past the cache
    session.prefill(ht.array(prompts(model.cfg, 2, 15)))
    with pytest.raises(ValueError):
        session.decode(2)
    tokens, logits = session.decode(1)
    assert tokens.shape == (2, 1) and logits.shape == (2, 1, model.cfg.vocab_size)
    assert tokens.split is None and logits.split is None
    assert math.isfinite(float(jnp.max(jnp.abs(logits.larray))))


@pytest.mark.parametrize("context,block,capacity", [
    (32776, 2048, 34816),      # the benchmark's cell: whole blocks of the kernel
    (2048, 2048, 2048), (2049, 2048, 4096),
    (43, 2048, 64), (5, 2048, 16),       # shorter than a block: one block, a power of two
    (50, 16, 64), (24, 8, 24),
])
def test_capacity_is_whole_blocks(model, tiles, context, block, capacity):
    tiles(attn_block=block)
    assert model.session(1, context).capacity == capacity


def test_benchmark_generator_draws_the_programs_layout():
    """``perf/generators/sambay_weights.py`` draws the benchmark's weights
    without importing the program; its tree has to be the one ``param_spec``
    describes: same keys, shapes and types (values differ: the draws do)."""
    from perf.generators import sambay_weights

    config = {"hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 8,
              "num_key_value_heads": 4, "num_hidden_layers": 8, "mb_per_layer": 2,
              "vocab_size": 96, "dtype": "bfloat16",
              "assumed": {"sizes": {"d_inner": 128, "d_state": 4, "d_conv": 4, "dt_rank": 8},
                          "init": {"residual_scale": 0.25, "shared_reader_gain": 3.0,
                                   "query_key_tie": 0.75, "query_temperature": 1.5,
                                   "lambda_std": 0.1, "dt_range": [1e-3, 1e-1]}}}
    drawn = sambay_weights.weights(config, jax.random.key(1), lambda leaf: leaf)
    cfg = toy(dtype="bfloat16")
    own = sambay.init_params(cfg, jax.random.key(1))
    assert jax.tree.structure(drawn) == jax.tree.structure(own)
    for (path, got), want in zip(jax.tree.leaves_with_path(drawn), jax.tree.leaves(own)):
        assert (got.shape, got.dtype) == (want.shape, want.dtype), jax.tree_util.keystr(path)
    # the queries of the layers that read the shared cache lean on its keys
    q_width, kv_width = 64, 32
    full = drawn["layers"][5]["mixer"]["w_qkv"].astype(jnp.float32)
    keys = full[:, q_width:q_width + kv_width].reshape(64, 2, 1, 2, 8)
    for queries in (full[:, :q_width], drawn["layers"][7]["mixer"]["w_q"].astype(jnp.float32)):
        lean = jnp.sum(queries.reshape(64, 2, 2, 2, 8) * keys) / jnp.sum(jnp.square(keys)) / 2
        assert abs(float(lean) - 1.5 * 0.75) < 0.1
    served_by = sambay.SambaY(cfg, drawn).session(1, 16)
    served_by.prefill(ht.array(prompts(cfg, 1, 5)))
    assert math.isfinite(float(jnp.max(jnp.abs(served_by.decode(2)[1].larray))))


def test_models_exports():
    assert ht.models.SambaY is sambay.SambaY
    assert ht.models.SambaYConfig is sambay.SambaYConfig
    assert ht.models.DecodeSession is sambay.DecodeSession


def test_quick_start_section_runs():
    """docs/quick_start.md section 19 (the session API) executes as written."""
    import re

    text = open(os.path.join(ROOT, "docs", "quick_start.md"), encoding="utf-8").read()
    found = re.search(r"## 19\. Serve a language model from a session\n(.*?)\n## 20\.", text, re.S)
    assert found, "quick_start.md lost its session section"
    blocks = re.findall(r"```python\n(.*?)```", found.group(1), re.S)
    assert blocks
    ns = {}
    for block in blocks:
        exec(compile(block, "quick_start.md[session]", "exec"), ns)
    assert np.array_equal(np.asarray(ns["tokens"].larray), np.asarray(ns["again"].larray))
