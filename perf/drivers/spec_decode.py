"""Each call returns a batch of sessions of GLM-4.7-Flash (latent attention
read in full, routed experts, and its MTP module drafting one token a step) to
the saved ends of their prompts and runs ``serve.decode_steps`` speculative
steps: ``session.rewind(snapshot); tokens, logits = session.decode(steps)`` on
``heat_tpu.models.DecodeSession``.  A step checks two positions a session and
emits one token or two, so the sessions end the call at positions of their
own.  The rewind copies nothing but the pending tokens and drafts.

Set-up builds the model from the generator's weights, prefills every session
with its own prompt (``serve.context_min`` to ``serve.context`` tokens;
``prefill_s`` in the check's ``info``) and saves the positions.  The check
runs the plain reference's full forward pass (per-head attention over every
earlier position, no cache, and the MTP module over the whole sequence) over a
judged session's prompt, its first token and the tokens the program emitted,
on the generator's weights, and compares, on the last call's own output,

- ``logits_err``: relative error (2-norm over the vocabulary) of the logits
  of every position the call checked and kept (a step's first position, and
  its second where the draft stood: together each position once), the worst
  one; ``logits_err_mean``, their mean: a rare position reads some twenty
  times the mean (a routing near-tie that bfloat16 decides the other way
  moves a quarter of the routed sum), so a fault that moves every position
  by less than that tail shows in the mean;
- ``draft_logits_err``: the same of the logits each step drafted from (the
  MTP module's row at the session's last accepted position);
- ``latent_cache_err``, ``mtp_latent_cache_err``: the main layers' and the
  MTP layer's caches of the judged sessions as the last call left them, every
  position before the session's own, against the reference's ``(c_kv,
  k_pe)``: relative, Frobenius, the worst layer;
- exact numbers: every emitted token is the argmax of the program's own
  logits, a draft stood exactly where it was the first argmax, and each
  step's draft is the argmax of the drafting logits before it
  (``tokens_not_argmax``); the sessions advanced as far as they emitted, from
  their own prompt ends (``positions_off``); the session holds what the
  configuration's ``memory`` states, ``(layers + 1) x 576 x 2`` B a position
  and session, stored once, and a snapshot holds no state
  (``cache_bytes_off``); shapes and the steps asked for.

``info`` carries the acceptance rate, the sessions' positions at the call's
end and how many of them differ.  ``control`` makes the program wrong where it
lies, by the workload's ``check.control_operands``: ``weights_fp8`` (every
weight matrix rounded to e4m3), or one of the planted faults of
:data:`FAULTS`."""

import time

import jax
import jax.numpy as jnp
import numpy as np

from heat_tpu.models import deepseek as lm
from heat_tpu.ops import latent_stream  # noqa: F401  a program without the full read fails here, at once
from perf.drivers.lm_decode import judged_sessions
from perf.generators import glm_weights
from perf.reference import glm as ref

# what a decode call keeps for the check
_DRAFTED = ("positions", "emitted", "accepted")


def model_config(config: dict):
    size = config["assumed"]["sizes"]
    return lm.DeepSeekConfig.from_dict(
        config, n_routed_experts=size["router_experts"],
        experts_held=(size["experts_first"], config["n_routed_experts"]), dtype=config["dtype"])


def reference_config(config: dict) -> dict:
    flat = {k: v for k, v in config.items() if isinstance(v, (int, float)) and not isinstance(v, bool)}
    flat.update(config["assumed"]["sizes"])
    return {k: flat[k] for k in ref.SIZES}


def setup(ctx):
    serve = ctx.config["serve"]
    params = ctx.data["params"]
    model = lm.DeepSeek(model_config(ctx.config), params)
    lengths = np.asarray(ctx.data["lengths"])
    started = time.perf_counter()
    session = model.session(serve["sessions"], serve["context"] + 2 * serve["decode_steps"])
    first = session.prefill(ctx.ht.array(ctx.data["tokens"], split=None), lengths=lengths)
    first_token = np.asarray(jnp.argmax(first.larray, axis=-1))
    prefill_s = time.perf_counter() - started
    snapshot = session.save()
    return {
        "model": model, "params": params, "session": session, "snapshot": snapshot,
        "steps": int(serve["decode_steps"]), "prompt": ctx.data["tokens"], "lengths": lengths,
        "first_token": first_token, "prefill_s": prefill_s,
        "judged": judged_sessions(ctx.seed, serve["sessions"],
                                  int(ctx.workload["check"]["sessions_judged"])),
        "cache": {"bytes": session.cache_bytes(), "capacity": session.capacity,
                  "snapshot_bytes": sum(int(x.nbytes) for x in jax.tree.leaves(snapshot.state))},
    }


def call(state, item):
    session = state["session"]
    session.rewind(state["snapshot"])
    tokens, logits = session.decode(state["steps"])
    return {"tokens": tokens, "logits": logits}


def keep(state, item, out):
    session = state["session"]
    return dict(out, drafts=state["model"].last_drafts,
                **{name: np.array(getattr(session, name)) for name in _DRAFTED})


def _held_rows(state, b):
    """Session ``b``'s rows of the caches as the newest call left them, a
    ``(latent | rotary)`` array a layer, the MTP layer's last (the program
    keeps two positions' rotary keys in one row of its own cache)."""
    if "session" not in state:
        return state["cache_kept"][b]
    shared = state["session"]._shared
    return [jnp.concatenate([latent[b], rope[b].reshape(latent.shape[1], -1)], axis=-1)
            for latent, rope in zip(shared["latent"], shared["rope"])]


def _keep_rows(state):
    """Copies of the judged sessions' rows, for when the session is gone."""
    state["cache_kept"] = {b: [jnp.copy(x) for x in _held_rows(state, b)] for b in state["judged"]}


def release(state):
    _keep_rows(state)
    state.pop("session", None)
    state.pop("snapshot", None)
    # a loaded program keeps its temporaries' room: the reference needs it beside the weights
    for program in (lm._prefill_chunk_drafted, lm._decode_drafted):
        program.clear_cache()


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
    lm._prefill_chunk_drafted.clear_cache()  # a loaded program keeps its temporaries' room
    return session, held


def _unparked(state, parked):
    if parked is not None:
        session, held = parked
        session._shared = jax.device_put(held, state["model"]._placement)
        state["session"] = session
        state.pop("cache_kept", None)


@jax.jit
def _errors(got, want):
    return jnp.linalg.norm(got - want, axis=-1) / jnp.linalg.norm(want, axis=-1)


@jax.jit
def _cache_error(held, want):
    n = want.shape[0]
    return jnp.linalg.norm(held[:n].astype(jnp.float32) - want) / jnp.linalg.norm(want)


def _program_restored(state, ctx):
    """Undo what a control did to the program where it lay."""
    for module, name, original in state.pop("patched", ()):
        setattr(module, name, original)
        lm._decode_drafted.clear_cache()
    if state.pop("weights_rounded", False):
        state["params"] = state["model"].params = None
        sharding = ctx.ht.get_comm().sharding(ctx.config["split"], 2)
        state["params"] = glm_weights.make(ctx.config, ctx.seed, sharding)["params"]
        state["model"].params = state["params"]


def _walk(steps, tokens, accepted, logits, fed, draft_logits):
    """One session's call as the host reads it: ``(tokens not the argmax,
    positions checked and kept a step, the rows of those logits)``."""
    wrong, kept, rows = 0, [], []
    at = 0
    for j in range(steps):
        first = int(np.argmax(logits[j, 0]))
        stood = bool(accepted[j])
        wrong += int(tokens[at] != first) + int(stood != (int(fed[j]) == first))
        kept.append(1 + stood)
        rows.append((j, 0))
        if stood:
            wrong += int(tokens[at + 1] != int(np.argmax(logits[j, 1])))
            rows.append((j, 1))
        at += 1 + stood
        if j + 1 < steps:
            wrong += int(int(fed[j + 1]) != int(np.argmax(draft_logits[j])))
    wrong += int(np.sum(tokens[at:] != -1))
    return wrong, kept, rows


def check(state, kept, ctx):
    steps, model = state["steps"], state["model"]
    config, cache = ctx.config, state["cache"]
    sessions, vocab = state["prompt"].shape[0], config["vocab_size"]
    lengths = state["lengths"]
    out = kept[-1]
    accepted, emitted, positions = out["accepted"], out["emitted"], out["positions"]
    info = {"prefill_s": state["prefill_s"], "cache": cache,
            "acceptance": float(np.mean(accepted)), "positions": positions.tolist(),
            "positions_distinct": int(len(set(positions.tolist()))),
            "prompt_lengths": lengths.tolist()}
    _program_restored(state, ctx)
    tokens, logits = out["tokens"], out["logits"]
    fed, draft_logits = out["drafts"]["fed"], out["drafts"]["logits"]
    numbers = {
        "n_steps_off": float(abs(tokens.shape[-1] - 2 * steps) + abs(logits.shape[1] - steps)),
        "bad_shape": float(tuple(tokens.shape[:1]) != (sessions,)
                           or tuple(logits.shape) != (sessions, steps, 2, vocab)
                           or tuple(fed.shape) != (sessions, steps)
                           or tuple(draft_logits.shape) != (sessions, steps, vocab)
                           or tuple(accepted.shape) != (sessions, steps)),
    }
    if numbers["n_steps_off"] or numbers["bad_shape"]:
        return numbers, info
    chosen = np.asarray(tokens.larray)
    layers = config["num_hidden_layers"] + config["num_nextn_predict_layers"]
    token_bytes = layers * 2 * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
    if config["dtype"] == "float32":
        token_bytes *= 2
    numbers.update({
        "cache_bytes_off": float(
            abs(cache["bytes"].get("shared", 0) - sessions * cache["capacity"] * token_bytes)
            + sum(v for k, v in cache["bytes"].items() if k != "shared") + cache["snapshot_bytes"]
            + max(0, config["serve"]["context"] + 2 * steps - cache["capacity"])),
        "positions_off": float(np.sum(np.abs(positions - lengths - emitted))
                               + np.sum(np.abs(emitted - steps - accepted.sum(axis=1)))),
    })
    rcfg = reference_config(config)
    all_logits, fed, draft_logits = (np.asarray(x) for x in (logits.larray, fed, draft_logits))
    wrong, per_session, drafted, cache_err = 0, {}, {}, {"main": {}, "mtp": {}}
    walked = {}
    for b in range(sessions):
        walked[b] = _walk(steps, chosen[b], accepted[b], all_logits[b], fed[b], draft_logits[b])
        wrong += walked[b][0] + int(np.sum((chosen[b] < -1) | (chosen[b] >= vocab)))
    numbers["tokens_not_argmax"] = float(wrong)
    parked = _parked(state)
    for b in state["judged"]:
        _, advanced, rows = walked[b]
        length, count = int(lengths[b]), int(emitted[b])
        sequence = jnp.concatenate([state["prompt"][b, :length],
                                    jnp.asarray([state["first_token"][b]], jnp.int32),
                                    jnp.asarray(chosen[b, :count], jnp.int32)])
        main_rows = [length + sum(advanced[:j]) + i for j, i in rows]
        mtp_rows = [length + sum(advanced[:j + 1]) - 1 for j in range(steps)]
        want = ref.forward(rcfg, state["params"], sequence, main_rows, mtp_rows)
        got = np.stack([all_logits[b, j, i] for j, i in rows])
        per_session[str(b)] = [float(e) for e in _errors(jnp.asarray(got), want["logits"])]
        drafted[str(b)] = [float(e) for e in _errors(jnp.asarray(draft_logits[b]),
                                                     want["draft_logits"])]
        held = _held_rows(state, b)
        end = length + count
        cache_err["main"][str(b)] = [
            float(_cache_error(h[:end], jnp.concatenate(w, axis=-1)[:end]))
            for h, w in zip(held, want["latent"])]
        cache_err["mtp"][str(b)] = [
            float(_cache_error(held[-1][:end], jnp.concatenate(want["mtp_latent"], axis=-1)[:end]))]
        del want
    _unparked(state, parked)
    every = [e for each in per_session.values() for e in each]
    drafts = [e for each in drafted.values() for e in each]
    numbers["logits_err"] = max(every)
    numbers["logits_err_mean"] = sum(every) / len(every)
    numbers["draft_logits_err"] = max(drafts)
    numbers["latent_cache_err"] = max(e for each in cache_err["main"].values() for e in each)
    numbers["mtp_latent_cache_err"] = max(e for each in cache_err["mtp"].values() for e in each)
    info.update({
        "logits_err_by_session": per_session,
        "draft_logits_err_mean": sum(drafts) / len(drafts), "draft_logits_err_by_session": drafted,
        "cache_err_by_session_and_layer": cache_err,
    })
    return numbers, info


# ------------------------------------------------------------------- controls

def _rounded(tree, exponent_bits, mantissa_bits):
    """Arrays of two dimensions or more rounded where they lie."""
    return jax.tree.map(
        lambda x: jax.lax.reduce_precision(x, exponent_bits, mantissa_bits) if x.ndim >= 2 else x,
        tree)


_rounded = jax.jit(_rounded, static_argnums=(1, 2), donate_argnums=0)


def _mtp_fed_previous_h():
    """The MTP module fed ``h_{i-1}`` beside the token at ``i + 1`` (a step's
    first row keeps its own: the step holds no earlier one)."""
    rows = lm._mtp_input

    def fed(cfg, params, h, nexts):
        return rows(cfg, params, jnp.concatenate([h[:, :1], h[:, :-1]], axis=1), nexts)
    return [(lm, "_mtp_input", fed)]


def _every_draft_accepted():
    """Every draft stands, whatever the main model chose."""
    return [(lm, "_accepted", lambda checked, draft: jnp.ones(checked.shape, bool))]


def _stream_drops_newest():
    """The full read misses each query's own row: every query reads the slots
    before its position only."""
    stream = lm.latent_stream_attention

    def drops(q_lat, q_pe, latent, rope, positions, scale):
        return stream(q_lat, q_pe, latent, rope, positions - 1, scale)
    return [(lm, "latent_stream_attention", drops)]


def _stale_draft_row():
    """A step writes the draft's rows alone, trusting the pending token's rows
    to be there: after a rejection the stale draft row is read in their
    place."""
    write = lm._write_rows

    def drafts_only(latent, rope, c_kv, k_pe, positions):
        return write(latent, rope, c_kv[:, 1:], k_pe[:, 1:], positions[:, 1:])
    return [(lm, "_write_rows", drafts_only)]


FAULTS = {"mtp_fed_previous_h": _mtp_fed_previous_h, "every_draft_accepted": _every_draft_accepted,
          "stream_drops_newest": _stream_drops_newest, "stale_draft_row": _stale_draft_row}


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
            lm._decode_drafted.clear_cache()
    else:
        raise ValueError(f"spec_decode has no control {what!r}")
    return call(state, item)
