"""Each call returns a batch of sessions of a DeepSeek-V3.2 share (latent
attention read through a learned sparse selection, routed experts of which
some are held) to the saved end of their prompts and decodes
``serve.decode_steps`` greedy tokens: ``session.rewind(snapshot); tokens,
logits = session.decode(steps)`` on ``heat_tpu.models.DecodeSession``.  The
rewind copies nothing but the pending token: all of this model's cache grows
with the context, and rows past the saved position are overwritten.

Set-up builds the model from the generator's weights, prefills every session
with its ``serve.context`` prompt tokens (``prefill_s`` in the check's
``info``) and saves the position.  The check runs the plain reference's full
forward pass (per-head attention with the selection as a mask, no cache) over
a judged session's prompt plus the tokens the program itself fed back, on the
generator's weights and with the same share, and compares, on the last call's
own output,

- ``logits_err``: relative error (2-norm over the vocabulary held) of the
  decoded positions' logits, the worst one (the mean is printed in ``info``).
  Logits, never tokens.  A routing near-tie that two roundings decide
  differently moves a position by one expert's output, which at these widths
  is no more than the selection's near-ties move every position (PERF.md
  section 2), so no position is left out;
- ``latent_cache_err``, ``index_cache_err``: the held caches of the judged
  sessions as the last call left them, every position, against the
  reference's ``(c_kv, k_pe)`` and ``k^I``: relative, Frobenius, the worst
  layer;
- ``selection_missed``: at the last decoded position, the share of the
  reference's ``S_t`` that the program's attention did not read (``1 -
  recall``: every number here is held to an upper limit), the worst layer and
  judged session.  The program's choice is ``model.last_selection``, the array
  its attention gathered by.  Near-ties at rank 2,048 flip under bfloat16;
- exact numbers: the session holds what the configuration's ``memory``
  states, ``layers x (576 + 128) x 2`` B a position and session, stored once
  and for all of the context, and a snapshot holds no state
  (``cache_bytes_off``); the model holds the experts the configuration says,
  under a router over all of them (``experts_held_off``); the tokens are the
  argmax of the program's own logits, in range of the vocabulary held, and as
  many as asked for, the session having advanced as far.

``control`` makes the program wrong where it lies, by the workload's
``check.control_operands``: ``weights_fp8`` (every weight matrix rounded to
e4m3), or one of the planted faults of :data:`FAULTS`."""

import time

import jax
import jax.numpy as jnp
import numpy as np

from heat_tpu.core import telemetry
from heat_tpu.models import deepseek as lm  # a program without the model fails here, at once
from heat_tpu.parallel import expert as ep
from perf.drivers import _lm
from perf.drivers.lm_decode import judged_sessions
from perf.generators import deepseek_weights
from perf.reference import deepseek as ref

ROPE_KEYS = {"factor": "rope_factor", "original_max_position_embeddings": "rope_original",
             "beta_fast": "rope_beta_fast", "beta_slow": "rope_beta_slow",
             "mscale": "rope_mscale", "mscale_all_dim": "rope_mscale_all_dim"}


def model_config(config: dict):
    size = config["assumed"]["sizes"]
    return lm.DeepSeekConfig.from_dict(
        config, n_routed_experts=size["router_experts"],
        experts_held=(size["experts_first"], config["n_routed_experts"]),
        vocab_size=max(config["published"]["vocab_size"], config["vocab_size"]),
        vocab_held=config["vocab_size"], layer_norm_eps=size["layer_norm_eps"],
        dtype=config["dtype"])


def reference_config(config: dict) -> dict:
    flat = _lm.sizes(config)
    flat.update({name: config["rope_scaling"][key] for key, name in ROPE_KEYS.items()})
    return {k: flat[k] for k in ref.SIZES}


def _start(ctx, model, tokens):
    """A session prefilled with the prompts ``tokens``."""
    serve = ctx.config["serve"]
    session = model.session(serve["sessions"], serve["context"] + serve["decode_steps"])
    first = session.prefill(ctx.ht.array(tokens, split=None))
    return session, np.asarray(jnp.argmax(first.larray, axis=-1))


def setup(ctx):
    serve = ctx.config["serve"]
    params = ctx.data["params"]
    model = lm.DeepSeek(model_config(ctx.config), params)
    started = time.perf_counter()
    session, first_token = _start(ctx, model, ctx.data["tokens"])
    prefill_s = time.perf_counter() - started
    snapshot = session.save()
    return {
        "model": model, "params": params, "session": session, "snapshot": snapshot,
        "steps": int(serve["decode_steps"]), "prompt": ctx.data["tokens"],
        "first_token": first_token, "prefill_s": prefill_s,
        "judged": judged_sessions(ctx.seed, serve["sessions"],
                                  int(ctx.workload["check"]["sessions_judged"])),
        "cache": {"bytes": session.cache_bytes(), "capacity": session.capacity,
                  "snapshot_bytes": sum(int(x.nbytes) for x in jax.tree.leaves(snapshot.state))},
        "counted": dict(telemetry.snapshot()["lm"]),
    }


def call(state, item):
    session = state["session"]
    session.rewind(state["snapshot"])
    tokens, logits = session.decode(state["steps"])
    return {"tokens": tokens, "logits": logits}


def keep(state, item, out):
    return dict(out, position=state["session"].position,
                selection=state["model"].last_selection)


def _held_rows(state, b):
    """Session ``b``'s rows of the caches as the newest call left them:
    ``[(latent rows, index keys)]``, a pair a layer; a latent row is ``c_kv``
    then ``k_pe``, which the program keeps apart, two positions' rotary keys
    in one row of its own cache (the configuration's ``assumed.cache_layout``)."""
    if "session" not in state:
        return state["cache_kept"][b]
    shared = state["session"]._shared
    return [(jnp.concatenate([latent[b], rope[b].reshape(latent.shape[1], -1)], axis=-1), index[b])
            for latent, rope, index in zip(shared["latent"], shared["rope"], shared["index"])]


def _keep_rows(state):
    """Copies of the judged sessions' rows, for when the session is gone."""
    state["cache_kept"] = {b: jax.tree.map(jnp.copy, _held_rows(state, b))
                           for b in state["judged"]}


def release(state):
    _keep_rows(state)
    state.pop("session", None)
    state.pop("snapshot", None)
    # a loaded program keeps its temporaries' room (a prefill program 1.4 GB): the
    # reference needs it beside the weights
    for program in (lm._prefill_chunk, lm._prefill_finish, lm._decode):
        program.clear_cache()


@jax.jit
def _errors(got, want):
    return jnp.linalg.norm(got - want, axis=-1) / jnp.linalg.norm(want, axis=-1)


@jax.jit
def _cache_error(held, want):
    n = want.shape[0]
    return jnp.linalg.norm(held[:n].astype(jnp.float32) - want) / jnp.linalg.norm(want)


def _parked(state):
    """Where the session is still alive while the reference runs
    (``perf/control.py`` judges a live program and goes on calling it): its
    caches go to the host meanwhile, because the caches, the weights and the
    reference's temporaries do not fit the chip together."""
    if "session" not in state:
        return None
    _keep_rows(state)
    session = state["session"]
    held, session._shared = jax.device_get(session._shared), None
    del state["session"]                     # _held_rows reads the kept copies meanwhile
    lm._prefill_chunk.clear_cache()          # a loaded program keeps its temporaries' room
    return session, held


def _unparked(state, parked):
    if parked is not None:
        session, held = parked
        session._shared = jax.device_put(held, state["model"]._placement)
        state["session"] = session
        state.pop("cache_kept", None)


def _program_restored(state, ctx):
    """Undo what a control did to the program where it lay."""
    for module, name, original in state.pop("patched", ()):
        setattr(module, name, original)
        lm._decode.clear_cache()
    if state.pop("weights_rounded", False):
        state["params"] = state["model"].params = None
        sharding = ctx.ht.get_comm().sharding(ctx.config["split"], 2)
        state["params"] = deepseek_weights.make(ctx.config, ctx.seed, sharding)["params"]
        state["model"].params = state["params"]


def check(state, kept, ctx):
    steps, model = state["steps"], state["model"]
    config, serve, cache = ctx.config, ctx.config["serve"], state["cache"]
    sessions, vocab = state["prompt"].shape[0], config["vocab_size"]
    info = {"prefill_s": state["prefill_s"], "cache": cache}
    # what the router sent to the held experts, a call (every call decodes the same tokens):
    # of steps x expert layers x experts held
    counted = telemetry.snapshot()["lm"]
    calls = (counted["decode_steps"] - state["counted"]["decode_steps"]) // max(1, steps)
    for name in ("expert_pairs", "experts_hit"):
        info[name + "_per_call"] = (counted[name] - state["counted"][name]) / max(1, calls)
    _program_restored(state, ctx)
    out = kept[-1]
    tokens, logits = out["tokens"], out["logits"]
    numbers = {
        "n_steps_off": float(abs(tokens.shape[-1] - steps)
                             + abs(out["position"] - serve["context"] - steps)),
        "bad_shape": float(tuple(tokens.shape[:1]) != (sessions,)
                           or tuple(logits.shape) != tuple(tokens.shape) + (vocab,)),
    }
    if numbers["n_steps_off"] or numbers["bad_shape"]:
        return numbers, info
    chosen = np.asarray(tokens.larray)
    layers = config["num_hidden_layers"]
    token_bytes = layers * 2 * (config["kv_lora_rank"] + config["qk_rope_head_dim"]
                                + config["index_head_dim"])
    if config["dtype"] == "float32":
        token_bytes *= 2
    moe = [p["moe"] for p in state["params"]["layers"] if "moe" in p]
    numbers.update({
        "cache_bytes_off": float(
            abs(cache["bytes"].get("shared", 0) - sessions * cache["capacity"] * token_bytes)
            + sum(v for k, v in cache["bytes"].items() if k != "shared") + cache["snapshot_bytes"]
            + max(0, serve["context"] + steps - cache["capacity"])),
        "experts_held_off": float(
            abs(len(moe) - (layers - config["first_k_dense_replace"]))
            + sum(abs(p["experts"]["w_gate"].shape[0] - config["n_routed_experts"])
                  + abs(p["router"].shape[1] - config["assumed"]["sizes"]["router_experts"])
                  for p in moe)
            + abs(model.cfg.experts_held[0] - config["assumed"]["sizes"]["experts_first"])),
        "tokens_out_of_range": float(np.sum((chosen < 0) | (chosen >= vocab))),
        "tokens_not_argmax": float(np.sum(chosen != np.asarray(jnp.argmax(logits.larray, -1)))),
    })
    rcfg = reference_config(config)
    selection = np.asarray(out["selection"])
    parked = _parked(state)
    per_session, cache_err, missed = {}, {"latent": {}, "index": {}}, {}
    for b in state["judged"]:
        fed = np.concatenate([[state["first_token"][b]], chosen[b, :-1]]).astype(np.int32)
        sequence = jnp.concatenate([state["prompt"][b], jnp.asarray(fed)])
        want = ref.forward(rcfg, state["params"], sequence, steps)
        per_session[str(b)] = [float(e) for e in _errors(logits.larray[b], want["logits"])]
        held = _held_rows(state, b)
        cache_err["latent"][str(b)] = [
            float(_cache_error(h[0], jnp.concatenate(w, axis=-1)))
            for h, w in zip(held, want["latent"])]
        cache_err["index"][str(b)] = [
            float(_cache_error(h[1], w)) for h, w in zip(held, want["index"])]
        missed[str(b)] = []
        for layer in range(layers):
            read = np.zeros(int(sequence.shape[0]), bool)
            slots = selection[layer, b]
            read[slots[(slots >= 0) & (slots < read.size)]] = True
            wanted = np.asarray(want["selected"][layer])
            missed[str(b)].append(float(np.sum(wanted & ~read) / max(1, np.sum(wanted))))
    _unparked(state, parked)
    every = [e for each in per_session.values() for e in each]
    numbers["logits_err"] = max(every)
    numbers["latent_cache_err"] = max(e for each in cache_err["latent"].values() for e in each)
    numbers["index_cache_err"] = max(e for each in cache_err["index"].values() for e in each)
    numbers["selection_missed"] = max(e for each in missed.values() for e in each)
    info.update({
        "logits_err_mean": sum(every) / len(every), "logits_err_by_session": per_session,
        "cache_err_by_session_and_layer": cache_err,
        "selection_recall_by_session_and_layer":
            {b: [1.0 - m for m in each] for b, each in missed.items()},
    })
    return numbers, info


# ------------------------------------------------------------------- controls

def _rounded(tree, exponent_bits, mantissa_bits):
    """Arrays of two dimensions or more rounded where they lie."""
    return jax.tree.map(
        lambda x: jax.lax.reduce_precision(x, exponent_bits, mantissa_bits) if x.ndim >= 2 else x,
        tree)


_rounded = jax.jit(_rounded, static_argnums=(1, 2), donate_argnums=0)


def _reads_newest():
    """Attention over the newest ``k`` positions in place of ``S_t``."""
    def select(q_idx, w, k_idx_cache, kv_len, k):
        k = min(int(k), k_idx_cache.shape[1])
        slot = kv_len - 1 - jnp.arange(k, dtype=jnp.int32)
        return jnp.broadcast_to(jnp.where(slot >= 0, slot, -1), (q_idx.shape[0], k))
    return [(lm, "sparse_select", select)]


def _reads_all():
    """Dense attention over every visible key."""
    select, attend, seen = lm.sparse_select, lm.latent_decode_attention, []

    def selects(q_idx, w, k_idx_cache, kv_len, k):
        seen.append(kv_len)
        return select(q_idx, w, k_idx_cache, kv_len, k)

    def attends(q_lat, q_pe, latent_cache, rope_cache, chosen, scale):
        slot = jnp.arange(latent_cache.shape[1], dtype=jnp.int32)
        every = jnp.broadcast_to(jnp.where(slot < seen.pop(), slot, -1),
                                 (q_lat.shape[0], slot.shape[0]))
        return attend(q_lat, q_pe, latent_cache, rope_cache, every, scale)
    return [(lm, "sparse_select", selects), (lm, "latent_decode_attention", attends)]


def _index_stale():
    """The index cache misses the position the step just wrote: the scan sees
    the positions before it only."""
    select = lm.sparse_select
    return [(lm, "sparse_select",
             lambda q_idx, w, cache, kv_len, k: select(q_idx, w, cache, kv_len - 1, k))]


def _bias_in_weights():
    """``s + b`` used for the weights, not only for the choice."""
    route = ep.sigmoid_group_routing

    def routes(h, router, bias, *, top_k, scale, **kw):
        _, chosen = route(h, router, bias, top_k=top_k, scale=scale, **kw)
        s = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                                   precision=jax.lax.Precision.HIGHEST))
        biased = jnp.take_along_axis(s + bias, chosen, axis=1)
        return biased / jnp.sum(biased, axis=-1, keepdims=True) * scale, chosen
    return [(ep, "sigmoid_group_routing", routes)]


def _absent_experts_counted():
    """Chosen experts that are not held computed with the first held expert's
    weights."""
    ffn = lm.held_experts_ffn

    def counts_them(x, router, experts, *, held, top_k, n_group, topk_group, scale, bias):
        y, counts = ffn(x, router, experts, held=held, top_k=top_k, n_group=n_group,
                        topk_group=topk_group, scale=scale, bias=bias)
        weights, chosen = ep.sigmoid_group_routing(x, router, bias, top_k=top_k, n_group=n_group,
                                                   topk_group=topk_group, scale=scale)
        absent = (chosen < held[0]) | (chosen >= held[0] + held[1])
        first = {name: w[0] for name, w in experts.items()}
        return y + jnp.sum(jnp.where(absent, weights, 0.0), axis=1, keepdims=True) * lm._gated_mlp(
            first, x), counts
    return [(lm, "held_experts_ffn", counts_them)]


FAULTS = {"reads_newest": _reads_newest, "reads_all": _reads_all, "index_stale": _index_stale,
          "bias_in_weights": _bias_in_weights, "absent_experts_counted": _absent_experts_counted}


def control(state, item, ctx):
    """The program made wrong where it lies, then the same call.  One
    precision lower: ``weights_fp8``, every weight matrix rounded to fp8
    (e4m3; the reference keeps the seed's).  A planted fault of
    :data:`FAULTS`: the decode program traced anew with one of its parts
    replaced (the prompts were prefilled by the sound program)."""
    what = ctx.workload["check"]["control_operands"]
    model = state["model"]
    if what == "weights_fp8":
        if not state.get("weights_rounded"):
            state["params"] = None
            model.params = _rounded(model.params, 4, 3)
            state["weights_rounded"] = True
    elif what in FAULTS:
        if not state.get("patched"):
            state["patched"] = []
            for module, name, planted in FAULTS[what]():
                state["patched"].append((module, name, getattr(module, name)))
                setattr(module, name, planted)
            lm._decode.clear_cache()
    else:
        raise ValueError(f"sparse_decode has no control {what!r}")
    return call(state, item)
