"""Each call returns a batch of sessions to the saved end of their prompts and
decodes ``serve.decode_steps`` greedy tokens: ``session.rewind(snapshot);
tokens, logits = session.decode(steps)`` on ``heat_tpu.models.DecodeSession``.

Set-up builds the model from the generator's weights, prefills every session
with its ``serve.context`` prompt tokens in chunks (``prefill_s`` in the
check's ``info``) and saves the position.  The check runs the plain reference's
full forward pass over a judged session's prompt plus the tokens the program
itself fed back, on the generator's weights, and compares

- the logits of the decoded positions: relative error (2-norm over the
  vocabulary) per position, the worst one (``logits_err``; the mean over the
  judged positions is printed in ``info``).  Logits, never sampled tokens;
- what the session's shared cache holds for that sequence against the
  reference's keys and values of every position (``shared_cache_err``,
  relative, Frobenius: a position lost, stale or misplaced), and the share
  of the held values that the type one below the stated one, fp8 (e4m3),
  holds exactly (``shared_cache_fp8_share``: 1 in 16 of bfloat16 values by
  chance, all of a cache that was rounded): the guarantee "every position,
  unquantised", which neither the logits nor the distance can hold alone
  (an fp8 cache moves the logits by a seventh and the distance by less
  than seeds do: PERF.md section 2);
- exact numbers: the tokens are the argmax of the program's own logits, in
  range, and as many as asked for, the session having advanced as far
  (``n_steps_off``); the shared cache takes ``token_bytes`` for every position
  the session says it can hold, once, and it can hold the context
  (``shared_cache_bytes_off``: a cache in fewer bits, or one copy a reading
  layer, is another configuration)."""

import time

import jax
import jax.numpy as jnp
import numpy as np

from heat_tpu.models import sambay as lm  # a program without the model fails here, at once
from perf.drivers import _lm
from perf.generators import sambay_weights
from perf.reference import sambay as ref
from perf.work_models import shared_kv_read


def judged_sessions(seed: int, sessions: int, count: int) -> list:
    """The last session and ``count - 1`` others drawn from the seed."""
    rng = np.random.default_rng([int(seed), 0x10617])
    drawn = rng.permutation(sessions - 1)[: max(0, count - 1)]
    return sorted(int(b) for b in drawn) + [sessions - 1]


def setup(ctx):
    serve = ctx.config["serve"]
    params = ctx.data["params"]
    model = lm.SambaY(_lm.model_config(ctx.config), params)
    session = model.session(serve["sessions"], serve["context"] + serve["decode_steps"])
    started = time.perf_counter()
    first = session.prefill(ctx.ht.array(ctx.data["tokens"], split=None))
    first_token = np.asarray(jnp.argmax(first.larray, axis=-1))
    prefill_s = time.perf_counter() - started
    return {
        "model": model, "params": params, "session": session, "snapshot": session.save(),
        "steps": int(serve["decode_steps"]), "prompt": ctx.data["tokens"],
        "first_token": first_token, "prefill_s": prefill_s,
        "judged": judged_sessions(ctx.seed, serve["sessions"],
                                  int(ctx.workload["check"]["sessions_judged"])),
        "cache": {"bytes": session.cache_bytes(), "capacity": session.capacity},
    }


def call(state, item):
    session = state["session"]
    session.rewind(state["snapshot"])
    tokens, logits = session.decode(state["steps"])
    return {"tokens": tokens, "logits": logits}


def keep(state, item, out):
    return dict(out, position=state["session"].position)


def _cache_rows(state, b):
    """Session ``b``'s part of the shared cache, ``(keys, values)``."""
    if "session" in state:
        return tuple(x[b] for x in state["session"]._shared)
    return state["cache_kept"][b]


def release(state):
    state["cache_kept"] = {b: _cache_rows(state, b) for b in state["judged"]}
    state.pop("session", None)
    state.pop("snapshot", None)


@jax.jit
def _errors(got, want):
    return jnp.linalg.norm(got - want, axis=-1) / jnp.linalg.norm(want, axis=-1)


@jax.jit
def _cache_error(held, k, v):
    """``held``: a session's ``(keys, values)``, each ``(groups, slots, lanes)``;
    ``k``, ``v``: the reference's, ``(seq, groups * lanes)``.  Returns the
    relative distance of the held positions from the reference's, and the
    share of the held values that fp8 (e4m3) holds exactly."""
    off = total = coarse = count = 0.0
    for got, want in zip(held, (k, v)):
        groups, _, lanes = got.shape
        want = jnp.moveaxis(want.reshape(want.shape[0], groups, lanes), 0, 1)
        got = got[:, :want.shape[1]].astype(jnp.float32)
        off += jnp.sum(jnp.square(got - want))
        total += jnp.sum(jnp.square(want))
        coarse += jnp.sum(jax.lax.reduce_precision(got, 4, 3) == got)
        count += got.size
    return jnp.sqrt(off / total), coarse / count


def _program_restored(state, ctx):
    """Undo what a control did to the program where it lay."""
    if state.pop("weights_rounded", False):
        state["params"] = state["model"].params = None
        sharding = ctx.ht.get_comm().sharding(ctx.config["split"], 2)
        state["params"] = sambay_weights.make(ctx.config, ctx.seed, sharding)["params"]
        state["model"].params = state["params"]
    if "plain_attention" in state:
        lm._attention = state.pop("plain_attention")
        lm._decode.clear_cache()


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
    token_bytes = shared_kv_read.token_bytes(ctx.config)
    numbers.update({
        "shared_cache_bytes_off": float(
            abs(cache["bytes"]["shared"] - sessions * cache["capacity"] * token_bytes)
            + token_bytes * sessions * max(0, serve["context"] + steps - cache["capacity"])),
        "tokens_out_of_range": float(np.sum((chosen < 0) | (chosen >= vocab))),
        "tokens_not_argmax": float(np.sum(chosen != np.asarray(jnp.argmax(logits.larray, -1)))),
    })
    cfg = _lm.reference_config(ctx.config)
    per_session, cache_err, cache_fp8 = {}, {}, {}
    for b in state["judged"]:
        fed = np.concatenate([[state["first_token"][b]], chosen[b, :-1]]).astype(np.int32)
        sequence = jnp.concatenate([state["prompt"][b], jnp.asarray(fed)])
        want, k, v = ref.logits_at_end(cfg, state["params"], sequence, steps, with_cache=True)
        per_session[str(b)] = [float(e) for e in _errors(logits.larray[b], want)]
        cache_err[str(b)], cache_fp8[str(b)] = (
            float(x) for x in _cache_error(_cache_rows(state, b), k, v))
    every = [e for each in per_session.values() for e in each]
    numbers["logits_err"] = max(every)
    numbers["shared_cache_err"] = max(cache_err.values())
    numbers["shared_cache_fp8_share"] = max(cache_fp8.values())
    info["logits_err_mean"] = sum(every) / len(every)
    info["logits_err_by_session"] = per_session
    info["shared_cache_err_by_session"] = cache_err
    return numbers, info


def _to_fp8(tree):
    """Matrices and caches rounded to fp8 (e4m3) where they lie."""
    return jax.tree.map(
        lambda x: jax.lax.reduce_precision(x, 4, 3) if x.ndim >= 2 else x, tree)


_to_fp8 = jax.jit(_to_fp8, donate_argnums=0)


def control(state, item, ctx):
    """The program made wrong where it lies, by the workload's
    ``check.control_operands``, then the same call.  One precision lower:
    ``shared_cache_fp8``, the prompt's keys and values in the shared cache
    rounded to fp8 (e4m3); ``weights_fp8``, every weight matrix (the reference
    keeps the seed's).  Keys short: ``cross_misses_newest``, the
    cross-attention layers of a decode step read the cache without the
    position the step itself wrote, 32,768 keys of 32,769;
    ``oldest_block_zeroed``, the first 2,048 positions' keys and values
    zeroed, what a read that skips its first block amounts to."""
    what = ctx.workload["check"]["control_operands"]
    session, model = state["session"], state["model"]
    if what == "shared_cache_fp8":
        if not state.get("cache_rounded"):
            session._shared = _to_fp8(session._shared)
            state["cache_rounded"] = True
    elif what == "oldest_block_zeroed":
        if not state.get("cache_rounded"):
            session._shared = tuple(x.at[:, :, :2048].set(0) for x in session._shared)
            state["cache_rounded"] = True
    elif what == "weights_fp8":
        if not state.get("weights_rounded"):
            state["params"] = None
            model.params = _to_fp8(model.params)
            state["weights_rounded"] = True
    elif what == "cross_misses_newest":
        if "plain_attention" not in state:
            plain = state["plain_attention"] = lm._attention

            def stale(cfg, kind, layer, p, h, pos0, shared, ring, block):
                return plain(cfg, kind, layer, p, h, pos0 - (kind == "cross"), shared, ring, block)

            lm._attention = stale
            lm._decode.clear_cache()
    else:
        raise ValueError(f"lm_decode has no control {what!r}")
    return call(state, item)
