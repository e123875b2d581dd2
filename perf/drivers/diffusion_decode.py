"""Each call returns a batch of sessions of SDAR-30B-A3B-Chat (grouped-query
attention under a block-causal mask, 128 softmax-routed experts a layer) to
the saved ends of their prompts' whole blocks and generates
``serve.decode_blocks`` blocks by block diffusion: ``session.rewind(snapshot);
tokens, logits = session.decode(blocks)`` on
``heat_tpu.models.DecodeSession``.  A block is ``denoising_steps`` passes that
fix positions by confidence and a commit pass; every session starts from its
own position, its prompt's tail fixed in its first block.  The rewind copies
nothing but the pending blocks.

Set-up builds the model from the generator's weights, prefills every session
with its own prompt (``serve.context_min`` to ``serve.context`` less a call's
blocks; ``prefill_s`` in the check's ``info``) and saves the positions.  The
check reads the last call's own output.  On every session, from the host:

- ``tokens_not_argmax``: every generated token is the argmax of the logits the
  call returned for its position (those of the pass that fixed it);
- ``unmask_not_topk``: a pass fixed exactly its schedule's count of the masked
  positions (``m // S`` each, the first ``m % S`` passes one more), the most
  confident ones by the confidences it reported, and after the last pass
  nothing is masked;
- ``prompt_changed``: the positions fixed before a block's first pass are the
  prompt's tail in the first block and none in the others, and the tokens
  read -1 there and nowhere else;
- ``positions_off``: every session ended the call its blocks past its
  prompt's whole blocks; ``cache_bytes_off``: the session holds what the
  configuration's ``memory`` states, ``layers x 2,048`` B a position and
  session, stored once, and a snapshot holds no state; ``bad_shape``.

On the judged sessions the plain reference (``perf/reference/sdar.py``,
float32 at ``highest``) computes the prompt's whole blocks once, then every
pass of every block over the block's state at that pass, **teacher-forced**:
the state the program reported (the positions fixed before the pass, with the
tokens they took; the mask id elsewhere), so that a near-tie in confidence
cannot fork the two; a block's finished tokens are then committed into the
reference's keys and values.  Against it:

- ``logits_err``: relative error (2-norm over the vocabulary) of the logits
  of every generated position at the pass that fixed it, the worst one;
  ``logits_err_mean``, their mean;
- ``confidence_err``: relative error of the confidence of every masked
  position at every pass, the worst one;
- ``kv_cache_err``: the keys and values the call's commits wrote (every
  block's rows, a layer), relative, Frobenius, the worst layer and session.

``info`` carries the sessions' positions, the prompts' lengths and the errors
by session.  ``control`` makes the program wrong where it lies, by the
workload's ``check.control_operands``: ``weights_fp8`` (every weight matrix
rounded to e4m3; the reference keeps the seed's), or one of the planted
faults of :data:`FAULTS`."""

import time

import jax
import jax.numpy as jnp
import numpy as np

from heat_tpu.models import sdar
from heat_tpu.ops.decode_attention import masked_attention
from perf.drivers.lm_decode import judged_sessions
from perf.drivers.spec_decode import _rounded
from perf.generators import sdar_weights
from perf.reference import sdar as ref


def model_config(config: dict):
    assumed = config["assumed"]
    return sdar.SDARConfig.from_dict(
        config, block_length=assumed["block_length"], denoising_steps=assumed["denoising_steps"],
        mask_token_id=assumed["mask_token_id"], dtype=config["dtype"])


def reference_config(config: dict) -> dict:
    flat = dict(config, block_length=config["assumed"]["block_length"])
    return {k: flat[k] for k in ref.SIZES}


def setup(ctx):
    serve = ctx.config["serve"]
    params = ctx.data["params"]
    model = sdar.SDAR(model_config(ctx.config), params)
    lengths = np.asarray(ctx.data["lengths"])
    started = time.perf_counter()
    session = model.session(serve["sessions"], serve["context"])
    session.prefill(ctx.ht.array(ctx.data["tokens"], split=None), lengths=lengths)
    prefill_s = time.perf_counter() - started
    snapshot = session.save()
    return {
        "model": model, "params": params, "session": session, "snapshot": snapshot,
        "blocks": int(serve["decode_blocks"]), "prompt": ctx.data["tokens"], "lengths": lengths,
        "whole": np.array(session.positions), "prefill_s": prefill_s,
        "judged": judged_sessions(ctx.seed, serve["sessions"],
                                  int(ctx.workload["check"]["sessions_judged"])),
        "cache": {"bytes": session.cache_bytes(), "capacity": session.capacity,
                  "snapshot_bytes": sum(int(x.nbytes) for x in jax.tree.leaves(snapshot.state))},
    }


def call(state, item):
    session = state["session"]
    session.rewind(state["snapshot"])
    tokens, logits = session.decode(state["blocks"])
    return {"tokens": tokens, "logits": logits}


def keep(state, item, out):
    session = state["session"]
    return dict(out, confidence=np.array(session.confidence),
                positions=np.array(session.positions))


def _committed_rows(state, b):
    """Session ``b``'s keys and values of the rows the newest call committed,
    ``(rows, kv heads, head_dim)`` a layer, as ``[(keys, values), ..]``."""
    if "session" not in state:
        return state["rows_kept"][b]
    shared = state["session"]._shared
    first = int(state["whole"][b])
    rows = slice(first, first + state["blocks"] * state["model"].cfg.block_length)
    return [(jnp.moveaxis(k[b, :, rows], 0, 1), jnp.moveaxis(v[b, :, rows], 0, 1))
            for k, v in zip(shared["k"], shared["v"])]


def _keep_rows(state):
    """Copies of the judged sessions' committed rows, for when the session is gone."""
    state["rows_kept"] = {b: [(jnp.copy(k), jnp.copy(v)) for k, v in _committed_rows(state, b)]
                          for b in state["judged"]}


def release(state):
    _keep_rows(state)
    state.pop("session", None)
    state.pop("snapshot", None)
    # a loaded program keeps its temporaries' room: the reference needs it beside the weights
    for program in (sdar._prefill_chunk, sdar._decode_blocks):
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
    del state["session"]                     # _committed_rows reads the kept copies meanwhile
    sdar._prefill_chunk.clear_cache()
    return session, held


def _unparked(state, parked):
    if parked is not None:
        session, held = parked
        session._shared = jax.device_put(held, state["model"]._placement)
        state["session"] = session
        state.pop("rows_kept", None)


@jax.jit
def _errors(got, want):
    return jnp.linalg.norm(got - want, axis=-1) / jnp.linalg.norm(want, axis=-1)


@jax.jit
def _confidences(logits):
    return 1.0 / jnp.sum(jnp.exp(logits - jnp.max(logits, axis=-1, keepdims=True)), axis=-1)


@jax.jit
def _relative(got, want):
    got = got.astype(jnp.float32)
    return jnp.linalg.norm(got - want) / jnp.linalg.norm(want)


def _program_restored(state, ctx):
    """Undo what a control did to the program where it lay."""
    for module, name, original in state.pop("patched", ()):
        setattr(module, name, original)
        sdar._decode_blocks.clear_cache()
    if state.pop("weights_rounded", False):
        state["params"] = state["model"].params = None
        sharding = ctx.ht.get_comm().sharding(ctx.config["split"], 2)
        state["params"] = sdar_weights.make(ctx.config, ctx.seed, sharding)["params"]
        state["model"].params = state["params"]


def _walk(cfg, tokens, confidence, argmax, whole, length):
    """One session's call as the host reads it: ``(tokens not the argmax,
    passes off their schedule or their top-k, prompt positions wrong, the
    fixed-before masks a block and pass)``."""
    width, steps = cfg.block_length, cfg.denoising_steps
    wrong = off = changed = 0
    before = []
    for j in range(confidence.shape[0]):
        fixed = np.isinf(confidence[j]) & (confidence[j] < 0)              # (passes, B)
        prompt = (whole + j * width + np.arange(width)) < length
        changed += int(np.sum(fixed[0] != prompt))
        made = tokens[j * width:(j + 1) * width]
        changed += int(np.sum((made == -1) != prompt))
        masks = width - int(prompt.sum())
        after = np.vstack([fixed[1:], np.ones((1, width), bool)])
        for s in range(steps):
            taken = after[s] & ~fixed[s]
            want = masks // steps + int(s < masks % steps)
            off += int(taken.sum() != want) + int(np.any(fixed[s] & ~after[s]))
            left = ~fixed[s] & ~taken
            if taken.any() and left.any():
                off += int(confidence[j, s][taken].min() < confidence[j, s][left].max())
        gen = ~prompt
        wrong += int(np.sum(made[gen] != argmax[j * width:(j + 1) * width][gen]))
        before.append(fixed)
    return wrong, off, changed, before


def check(state, kept, ctx):
    blocks, model = state["blocks"], state["model"]
    cfg, config, cache = model.cfg, ctx.config, state["cache"]
    width, steps = cfg.block_length, cfg.denoising_steps
    sessions, vocab = state["prompt"].shape[0], config["vocab_size"]
    lengths, whole = state["lengths"], state["whole"]
    out = kept[-1]
    positions, confidence = out["positions"], out["confidence"]
    info = {"prefill_s": state["prefill_s"], "cache": cache, "positions": positions.tolist(),
            "prompt_lengths": lengths.tolist()}
    _program_restored(state, ctx)
    tokens, logits = out["tokens"], out["logits"]
    numbers = {"bad_shape": float(
        tuple(tokens.shape) != (sessions, blocks * width)
        or tuple(logits.shape) != (sessions, blocks * width, vocab)
        or tuple(confidence.shape) != (sessions, blocks, steps, width))}
    if numbers["bad_shape"]:
        return numbers, info
    chosen = np.asarray(tokens.larray)
    argmax = np.asarray(jnp.argmax(logits.larray, axis=-1))
    layers = config["num_hidden_layers"]
    row_bytes = 2 * config["num_key_value_heads"] * config["head_dim"] * (
        4 if config["dtype"] == "float32" else 2)
    numbers.update({
        "cache_bytes_off": float(
            abs(cache["bytes"].get("shared", 0) - sessions * cache["capacity"] * layers * row_bytes)
            + sum(v for k, v in cache["bytes"].items() if k != "shared") + cache["snapshot_bytes"]
            + max(0, config["serve"]["context"] - cache["capacity"])),
        "positions_off": float(np.sum(np.abs(positions - whole - blocks * width))
                               + np.sum(np.abs(whole - (lengths - lengths % width)))),
    })
    wrong = off = changed = 0
    walked = {}
    for b in range(sessions):
        w, o, c, before = _walk(cfg, chosen[b], confidence[b], argmax[b], int(whole[b]),
                                int(lengths[b]))
        wrong, off, changed = wrong + w, off + o, changed + c
        walked[b] = before
    numbers.update({"tokens_not_argmax": float(wrong), "unmask_not_topk": float(off),
                    "prompt_changed": float(changed)})
    rcfg = reference_config(config)
    parked = _parked(state)
    by_session, conf_by_session, kv_by_session = {}, {}, {}
    all_logits = logits.larray
    for b in state["judged"]:
        first, length = int(whole[b]), int(lengths[b])
        prompt = np.asarray(state["prompt"][b, :length])
        cache_ref = ref.prefix(rcfg, state["params"], jnp.asarray(prompt[:first]),
                               cache["capacity"])
        errs, confs, made_rows = [], [], []
        for j in range(blocks):
            pos = first + j * width
            final = chosen[b, j * width:(j + 1) * width].copy()
            for i in range(max(0, min(width, length - pos))):
                final[i] = prompt[pos + i]                      # the prompt's tail, in place of -1
            for s in range(steps):
                fixed = walked[b][j][s]
                state_s = np.where(fixed, final, cfg.mask_token_id).astype(np.int32)
                want, _ = ref.block(rcfg, state["params"], cache_ref, jnp.asarray(state_s), pos)
                after = walked[b][j][s + 1] if s + 1 < steps else np.ones(width, bool)
                taken = np.flatnonzero(after & ~fixed)
                if taken.size:
                    got = all_logits[b, j * width + taken]
                    errs += [float(e) for e in _errors(got, want[taken])]
                masked = np.flatnonzero(~fixed)
                if masked.size:
                    ref_conf = np.asarray(_confidences(want[masked]))
                    confs += [float(e) for e in
                              np.abs(confidence[b, j, s, masked] - ref_conf) / ref_conf]
            _, made = ref.block(rcfg, state["params"], cache_ref, jnp.asarray(final), pos)
            cache_ref = ref.committed(cache_ref, made, pos)
            made_rows.append(made)
            del want
        held = _committed_rows(state, b)
        kv_by_session[str(b)] = [
            max(float(_relative(hk, jnp.concatenate([m[i][0] for m in made_rows]))),
                float(_relative(hv, jnp.concatenate([m[i][1] for m in made_rows]))))
            for i, (hk, hv) in enumerate(held)]
        by_session[str(b)], conf_by_session[str(b)] = errs, confs
        del cache_ref, made_rows
    _unparked(state, parked)
    every = [e for each in by_session.values() for e in each]
    confs = [e for each in conf_by_session.values() for e in each]
    numbers["logits_err"] = max(every)
    numbers["logits_err_mean"] = sum(every) / len(every)
    numbers["confidence_err"] = max(confs)
    numbers["kv_cache_err"] = max(e for each in kv_by_session.values() for e in each)
    info.update({"logits_err_by_session": by_session,
                 "confidence_err_mean": sum(confs) / len(confs),
                 "kv_cache_err_by_session_and_layer": kv_by_session})
    return numbers, info


# ------------------------------------------------------------------- controls

def _causal_in_block():
    """A causal mask inside the block: a row sees the block's positions up to
    its own, not the whole block."""
    def causal(cfg, q, k_cache, v_cache, pos):
        seq, slots = q.shape[1], k_cache.shape[2]
        q_pos = jnp.repeat(pos[:, None] + jnp.arange(seq, dtype=jnp.int32), cfg.group, axis=1)
        return masked_attention(
            sdar._query_rows(cfg, q).astype(k_cache.dtype), k_cache, v_cache, q_pos,
            jnp.arange(slots, dtype=jnp.int32), scale=cfg.head_dim ** -0.5, kv_len=pos + seq,
            block=min(sdar.ATTN_BLOCK, slots))
    return [(sdar, "_block_attention", causal)]


def _no_commit():
    """No commit pass: the keys and values of the block's last denoising pass
    (made from its state before that pass) stay in the cache."""
    return [(sdar, "_commit", lambda cfg, params, shared, tokens, pos: shared)]


FAULTS = {"causal_in_block": _causal_in_block, "no_commit": _no_commit}


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
            sdar._decode_blocks.clear_cache()
    else:
        raise ValueError(f"diffusion_decode has no control {what!r}")
    return call(state, item)
