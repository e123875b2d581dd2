"""Each call returns a batch of sessions of a power-retention model to the
saved end of their prompts and decodes ``serve.decode_steps`` greedy tokens:
``session.rewind(snapshot); tokens, logits = session.decode(steps)`` on
``heat_tpu.models.DecodeSession``.  The rewind copies the whole state back:
on this architecture there is nothing else to return to.

Set-up builds the model from the generator's weights, prefills every session
with its ``serve.context`` prompt tokens in chunks (``prefill_s`` in the
check's ``info``) and saves the position.  The check runs the plain
reference's full forward pass (attention form, no state) over a judged
session's prompt plus the tokens the program itself fed back, on the
generator's weights, and compares

- the logits of the decoded positions: relative error (2-norm over the
  vocabulary) per position, the worst one (``logits_err``; the mean over the
  judged positions is printed in ``info``).  Logits, never sampled tokens;
- the state of the judged sessions against the reference's closed sum over
  its own keys, values and gates, twice: the saved state, what ``rewind``
  restores (after the prompt's last position; ``state_err``), and the live
  state as the last call's steps left it, what the timed kernel wrote (after
  the last token fed; ``stepped_state_err``: a step that drops its write or
  its key, a rewind that restored nothing).  Relative, Frobenius, ``S`` and
  ``z`` of a layer together, the worst layer; the rows the layout holds beside
  the features count as error where they are not zero.  And of each the share
  of the held values that bfloat16 holds exactly (``state_bf16_share``,
  ``stepped_state_bf16_share``: about 2^-16 of float32 values by chance, all
  of a state that was rounded or is kept rounded by the step): the guarantee
  "float32 state of every position";
- exact numbers: the tokens are the argmax of the program's own logits, in
  range, and as many as asked for, the session having advanced as far
  (``n_steps_off``); the state takes what the configuration's ``memory``
  states, ``state_rows x (head_dim + 1) x 4`` B for every key/value head,
  layer and session, and the snapshot as much again (``state_bytes_off``: a
  state in fewer bits, or a third copy, is another configuration).

The program's layout of the features (the configuration's
``assumed.state_layout``) is mapped here to the reference's order."""

import time

import jax
import jax.numpy as jnp
import numpy as np

from heat_tpu.models import brumby as lm  # a program without the model fails here, at once
from perf.drivers import _lm
from perf.drivers.lm_decode import judged_sessions
from perf.generators import brumby_weights
from perf.reference import brumby as ref


def model_config(config: dict):
    return lm.BrumbyConfig.from_dict(_lm.sizes(config), dtype=config["dtype"])


def reference_config(config: dict) -> dict:
    flat = _lm.sizes(config)
    return {k: flat[k] for k in ref.SIZES}


def held_rows(d: int) -> tuple:
    """Where the program's layout holds the reference's features, in the
    reference's order (the squares, then the pairs ``a < b`` row-major), and
    the rows it holds beside them."""
    a, b = np.triu_indices(d, 1)
    j = b - a
    pairs = np.where(j <= d // 2, j * d + a, (d - j) * d + b)
    rows = np.concatenate([np.arange(d), pairs])
    spare = np.setdiff1d(np.arange((d // 2 + 1) * d), rows)
    return rows, spare


def _start(ctx, model, tokens):
    """A session prefilled with the prompts ``tokens``."""
    serve = ctx.config["serve"]
    session = model.session(serve["sessions"], serve["context"] + serve["decode_steps"])
    first = session.prefill(ctx.ht.array(tokens, split=None))
    return session, np.asarray(jnp.argmax(first.larray, axis=-1))


def setup(ctx):
    serve = ctx.config["serve"]
    params = ctx.data["params"]
    model = lm.Brumby(model_config(ctx.config), params)
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
        "cache": {"bytes": session.cache_bytes(),
                  "snapshot_bytes": sum(int(x.nbytes) for x in jax.tree.leaves(snapshot.state))},
    }


def call(state, item):
    session = state["session"]
    session.rewind(state["snapshot"])
    tokens, logits = session.decode(state["steps"])
    return {"tokens": tokens, "logits": logits}


def keep(state, item, out):
    return dict(out, position=state["session"].position)


def _held_rows(state, b):
    """Session ``b``'s saved state and its live one, as the newest call left
    it: each ``[(S, z)]``, a pair a layer."""
    if "session" not in state:
        return state["state_kept"][b]
    return tuple([(S[b], z[b]) for S, z in zip(tree["S"], tree["z"])]
                 for tree in (state["snapshot"].state, state["session"]._state))


def release(state):
    state["state_kept"] = {b: _held_rows(state, b) for b in state["judged"]}
    state.pop("session", None)
    state.pop("snapshot", None)


@jax.jit
def _errors(got, want):
    return jnp.linalg.norm(got - want, axis=-1) / jnp.linalg.norm(want, axis=-1)


@jax.jit
def _state_error(held, want, rows, spare):
    """``held``: a session's ``(S, z)`` of one layer as the program lays them,
    ``(heads, d, rows)`` and ``(heads, blocks, d)``; ``want``: the
    reference's, ``(heads, features, d)`` and ``(heads, features)``.  Returns
    the relative distance, and the share of the held values that bfloat16
    holds exactly."""
    S, z = held
    z = z.reshape(z.shape[0], -1)
    S_feat, z_feat = S[:, :, rows], z[:, rows]
    off = (jnp.sum(jnp.square(jnp.moveaxis(S_feat, 1, 2) - want[0]))
           + jnp.sum(jnp.square(z_feat - want[1]))
           + jnp.sum(jnp.square(S[:, :, spare])) + jnp.sum(jnp.square(z[:, spare])))
    total = jnp.sum(jnp.square(want[0])) + jnp.sum(jnp.square(want[1]))
    # of the features alone: the rows that stay zero are exact in any type
    coarse = (jnp.sum(jax.lax.reduce_precision(S_feat, 8, 7) == S_feat)
              + jnp.sum(jax.lax.reduce_precision(z_feat, 8, 7) == z_feat))
    return jnp.sqrt(off / total), coarse / (S_feat.size + z_feat.size)


def _program_restored(state, ctx):
    """Undo what a control did to the program where it lay."""
    if state.pop("weights_rounded", False):
        state["params"] = state["model"].params = None
        sharding = ctx.ht.get_comm().sharding(ctx.config["split"], 2)
        state["params"] = brumby_weights.make(ctx.config, ctx.seed, sharding)["params"]
        state["model"].params = state["params"]


def check(state, kept, ctx):
    steps, model = state["steps"], state["model"]
    sessions, vocab = state["prompt"].shape[0], model.cfg.vocab_size
    serve, cache = ctx.config["serve"], state["cache"]
    info = {"prefill_s": state["prefill_s"], "cache": cache}
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
    stated = (sessions * ctx.config["num_hidden_layers"] * ctx.config["num_key_value_heads"]
              * ctx.config["assumed"]["sizes"]["state_rows"] * (ctx.config["head_dim"] + 1) * 4)
    numbers.update({
        "state_bytes_off": float(abs(cache["bytes"]["state"] - stated)
                                 + abs(cache["snapshot_bytes"] - stated)),
        "tokens_out_of_range": float(np.sum((chosen < 0) | (chosen >= vocab))),
        "tokens_not_argmax": float(np.sum(chosen != np.asarray(jnp.argmax(logits.larray, -1)))),
    })
    rcfg = reference_config(ctx.config)
    rows, spare = held_rows(ctx.config["head_dim"])
    per_session = {}
    kinds = ("state", "stepped_state")  # saved at the prompt's end; live, after the steps
    state_err, state_bf16 = {kind: {} for kind in kinds}, dict.fromkeys(kinds, 0.0)
    for b in state["judged"]:
        fed = np.concatenate([[state["first_token"][b]], chosen[b, :-1]]).astype(np.int32)
        sequence = jnp.concatenate([state["prompt"][b], jnp.asarray(fed)])
        want, states = ref.logits_at_end(rcfg, state["params"], sequence, steps, with_state=True)
        per_session[str(b)] = [float(e) for e in _errors(logits.larray[b], want)]
        for kind, held, wanted in zip(kinds, _held_rows(state, b), zip(*states)):
            by_layer = [tuple(float(x) for x in _state_error(h, w, rows, spare))
                        for h, w in zip(held, wanted)]
            state_err[kind][str(b)] = [e for e, _ in by_layer]
            state_bf16[kind] = max(state_bf16[kind], *(share for _, share in by_layer))
    every = [e for each in per_session.values() for e in each]
    numbers["logits_err"] = max(every)
    for kind in kinds:
        numbers[kind + "_err"] = max(e for each in state_err[kind].values() for e in each)
        numbers[kind + "_bf16_share"] = state_bf16[kind]
        info[kind + "_err_by_session_and_layer"] = state_err[kind]
    info["logits_err_mean"] = sum(every) / len(every)
    info["logits_err_by_session"] = per_session
    return numbers, info


def _rounded(tree, exponent_bits, mantissa_bits):
    """Arrays of two dimensions or more rounded where they lie."""
    return jax.tree.map(
        lambda x: jax.lax.reduce_precision(x, exponent_bits, mantissa_bits) if x.ndim >= 2 else x,
        tree)


_rounded = jax.jit(_rounded, static_argnums=(1, 2), donate_argnums=0)


def control(state, item, ctx):
    """The program made wrong where it lies, by the workload's
    ``check.control_operands``, then the same call.  One precision lower:
    ``state_bf16``, the live and the saved state rounded to bfloat16 where
    they lie; ``weights_fp8``, every weight matrix rounded to fp8 (e4m3; the
    reference keeps the seed's).  A position short: ``state_stale``, the
    sessions start again from a state that holds the prompt without its last
    position (the state of the position before), with the right pending token
    at the right position."""
    what = ctx.workload["check"]["control_operands"]
    session, model = state["session"], state["model"]
    if what == "state_bf16":
        if not state.get("state_rounded"):
            session._state = _rounded(session._state, 8, 7)
            saved = state["snapshot"]
            state["snapshot"] = saved._replace(state=_rounded(saved.state, 8, 7))
            state["state_rounded"] = True
    elif what == "weights_fp8":
        if not state.get("weights_rounded"):
            state["params"] = None
            model.params = _rounded(model.params, 4, 3)
            state["weights_rounded"] = True
    elif what == "state_stale":
        if not state.get("state_stale"):
            context = ctx.config["serve"]["context"]
            state["session"] = state["snapshot"] = session = None
            session, _ = _start(ctx, model, state["prompt"][:, :context - 1])
            session.position = context
            session._token = jnp.asarray(state["first_token"], jnp.int32)
            state["session"], state["snapshot"] = session, session.save()
            state["state_stale"] = True
    else:
        raise ValueError(f"retention_decode has no control {what!r}")
    return call(state, item)
