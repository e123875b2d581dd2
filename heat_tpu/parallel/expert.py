"""Expert parallelism: mixture-of-experts FFN with all-to-all dispatch.

No reference counterpart — the reference has no MoE and its parallelism
checklist marks expert parallelism absent (SURVEY.md §2.5).  This module
supplies the capability TPU-first, completing the parallelism matrix
(dp / tp / pp / sp / ep) alongside :mod:`heat_tpu.parallel.pipeline` and
:mod:`heat_tpu.parallel.sequence`:

* tokens stay sharded along the ``ep`` mesh axis (the data axis);
* expert weights are sharded along the same axis (``E // N`` experts
  resident per device);
* dispatch is the GShard/Switch schedule: top-k routing with a static
  per-expert capacity, one ``all_to_all`` to move token slabs to their
  experts' devices, the expert FFN as one batched einsum over the local
  experts, and the inverse ``all_to_all`` + weighted combine back.
  Token→slot movement is a scatter-add / gather pair — O(tokens·k·d)
  HBM traffic — not GShard's dense one-hot dispatch einsum, whose
  O(tokens·experts·capacity·d) FLOPs dwarf the expert GEMMs themselves
  at transformer sizes (measured 4.5x slower end-to-end on one v5e;
  docs/PERFORMANCE.md).

Everything is shape-static so the whole step jits into a single XLA
program; the two all-to-alls ride ICI.  ``mesh=None`` runs the same
routing on one device; since capacity and drop priority are enforced per
shard, the two paths agree exactly only while nothing is dropped
(``fraction_dropped == 0`` — the regime training aims for).

**Which entry point drops tokens.**  :func:`moe_ffn` (softmax top-k, a
static per-expert capacity, either every expert here or an ``all_to_all``)
drops the choices that pass an expert's capacity.  :func:`held_experts_sum`
never drops one: it serves a model whose experts are shared between chips,
takes a choice of every token over *all* the experts (sigmoid scores, a
balancing bias in the choice only, groups of experts,
:func:`sigmoid_group_routing`, which :func:`held_experts_ffn` routes by; or a
softmax over all of them and its top k, :func:`softmax_topk_routing`), is
told which consecutive experts this chip holds, and returns their part of
the routed sum.  It runs no exchange and stands in for none: what the other
chips' experts would add is theirs to add.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .collectives import all_to_all, axis_size, psum, shard_map_unchecked

__all__ = ["top_k_routing", "moe_ffn", "expert_capacity", "held_experts_ffn",
           "held_experts_sum", "sigmoid_group_routing", "softmax_topk_routing"]


def expert_capacity(
    tokens_per_shard: int, num_experts: int, k: int, capacity_factor: float
) -> int:
    """Static per-expert, per-shard token capacity (GShard's rule).

    ``capacity_factor`` > 1 leaves headroom over the perfectly-balanced
    load ``k * tokens / E``; tokens routed past an expert's capacity are
    dropped (their combine weight is zero, so they pass through the
    residual connection unchanged in a transformer block).
    """
    cap = int(math.ceil(capacity_factor * k * tokens_per_shard / num_experts))
    return max(cap, 1)


def _softmax_top_k(logits, k: int):
    """``softmax(logits)`` in float32 over the experts, its ``k`` largest and
    their weights divided by their sum: ``(probs, weights, chosen)``."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, chosen = jax.lax.top_k(probs, k)
    return probs, weights / jnp.sum(weights, axis=-1, keepdims=True), chosen


def _route(gate_logits: jax.Array, k: int, capacity: int):
    """Top-k token→expert assignment with capacity-limited slot positions.

    Returns ``(top_w, top_idx, pos_in_expert, kept, aux)`` — each of the
    first four is (t, k); ``aux`` holds the Switch load-balancing loss and
    the dropped fraction for this shard.

    Position assignment is token-major: when an expert oversubscribes,
    earlier tokens win — the same deterministic priority for any mesh
    size, since routing happens on each shard's local tokens.
    """
    t, num_experts = gate_logits.shape
    probs, top_w, top_idx = _softmax_top_k(gate_logits, k)  # (t, E), (t, k), (t, k)

    # position of each (token, choice) within its expert's queue; choices
    # are ranked token-major then slot-major so priority is deterministic
    flat_idx = top_idx.reshape(-1)  # (t*k,) in token-major order
    onehot = jax.nn.one_hot(flat_idx, num_experts, dtype=jnp.int32)  # (t*k, E)
    position = jnp.cumsum(onehot, axis=0) * onehot - onehot  # pos within expert
    pos_in_expert = jnp.sum(position, axis=-1).reshape(t, k)  # (t, k)
    kept = pos_in_expert < capacity

    # Switch-style auxiliary load-balancing loss: E * sum_e f_e * p_e where
    # f_e is the fraction of routed choices sent to expert e and p_e the
    # mean router probability of e over the shard's tokens.
    f = jnp.mean(jax.nn.one_hot(top_idx, num_experts, dtype=jnp.float32), axis=(0, 1))
    p = jnp.mean(probs, axis=0)
    aux = {
        "load_balance_loss": num_experts * jnp.sum(f * p),
        "fraction_dropped": 1.0 - jnp.mean(kept.astype(jnp.float32)),
    }
    return top_w, top_idx, pos_in_expert, kept, aux


def top_k_routing(gate_logits: jax.Array, k: int, capacity: int):
    """GShard-style dense routing tensors (reference formulation, kept for
    inspection/debugging; the hot path uses the scatter/gather form).

    Returns ``(dispatch, combine, aux)``: dispatch (t, E, C) one-hot,
    combine = dispatch scaled by the normalized top-k router weight, and
    the aux dict of :func:`_route`.
    """
    num_experts = gate_logits.shape[1]
    top_w, top_idx, pos_in_expert, kept, aux = _route(gate_logits, k, capacity)
    dispatch = (
        jax.nn.one_hot(top_idx, num_experts, dtype=jnp.float32)[..., None]
        * jax.nn.one_hot(jnp.minimum(pos_in_expert, capacity - 1), capacity)[
            :, :, None, :
        ]
        * kept[..., None, None]
    )  # (t, k, E, C)
    combine = jnp.sum(dispatch * top_w[..., None, None], axis=1)  # (t, E, C)
    dispatch = jnp.sum(dispatch, axis=1)  # (t, E, C)
    return dispatch, combine, aux


def _moe_shard(
    x,
    gate_w,
    w_in,
    w_out,
    s_in=None,
    s_out=None,
    *,
    k: int,
    capacity: int,
    activation: Callable,
    axis: Optional[str],
):
    """One shard's MoE FFN. ``x`` (t, d); ``w_in`` (E_local, d, h),
    ``w_out`` (E_local, h, d); ``gate_w`` (d, E_global) replicated.

    ``s_in``/``s_out`` (E_local, h) / (E_local, d) switch the expert
    GEMMs to the quantized form: ``w_in``/``w_out`` are then int8/fp8
    buffers whose upcast to the f32 accumulator dtype fuses into the
    GEMM read (HBM and the all-to-alls never carry the dequantized
    copy), with the per-(expert, out-channel) scales folded in as
    epilogue multiplies."""
    t, d = x.shape
    num_experts = gate_w.shape[1]
    top_w, top_idx, pos_in_expert, kept, aux = _route(x @ gate_w, k, capacity)

    # token→slot scatter: each kept (token, choice) lands in flat slot
    # e*C + pos; dropped choices land in a trash slot that is sliced off.
    # O(t·k·d) HBM traffic vs the dense dispatch einsum's O(t·E·C·d) FLOPs.
    n_slots = num_experts * capacity
    # dropped choices get index n_slots — out of bounds, discarded by
    # mode="drop"; the in-bounds (kept) indices are unique by construction
    # (each expert slot is assigned at most once)
    dest = jnp.where(kept, top_idx * capacity + pos_in_expert, n_slots)  # (t, k)
    src = jnp.broadcast_to(x[:, None, :], (t, k, d)).reshape(t * k, d)
    # (no unique_indices hint: every dropped choice shares the sentinel
    # index, which would violate the uniqueness contract)
    slots = jnp.zeros((n_slots, d), x.dtype).at[dest.reshape(-1)].add(src, mode="drop")
    expert_inputs = slots.reshape(num_experts, capacity, d)
    if axis is not None:
        # exchange slabs so each device holds ALL shards' tokens for its
        # resident experts: (E, C, d) -> (E/N, N*C, d)
        expert_inputs = all_to_all(expert_inputs, axis, split_axis=0, concat_axis=1)

    if s_in is None:
        hidden = activation(jnp.einsum("ecd,edh->ech", expert_inputs, w_in))
        expert_outputs = jnp.einsum("ech,ehd->ecd", hidden, w_out)
    else:
        comp = jnp.promote_types(x.dtype, jnp.float32)
        pre = jnp.einsum(
            "ecd,edh->ech", expert_inputs.astype(comp), w_in.astype(comp)
        )
        hidden = activation(pre * s_in[:, None, :].astype(comp)).astype(x.dtype)
        pre = jnp.einsum("ech,ehd->ecd", hidden.astype(comp), w_out.astype(comp))
        expert_outputs = (pre * s_out[:, None, :].astype(comp)).astype(x.dtype)

    if axis is not None:
        # inverse exchange: (E/N, N*C, d) -> (E, C, d), back token-resident
        expert_outputs = all_to_all(expert_outputs, axis, split_axis=1, concat_axis=0)
        aux = {key: psum(val, axis) / axis_size(axis) for key, val in aux.items()}

    # slot→token gather + weighted combine; the trash row returns zeros
    # for dropped choices (they pass through the residual unchanged)
    out_flat = jnp.concatenate(
        [expert_outputs.reshape(n_slots, d), jnp.zeros((1, d), expert_outputs.dtype)]
    )
    gathered = out_flat[dest.reshape(-1)].reshape(t, k, d)
    y = jnp.sum(gathered * top_w[..., None].astype(gathered.dtype), axis=1)
    return y.astype(x.dtype), aux


def moe_ffn(
    x: jax.Array,
    gate_w: jax.Array,
    w_in: jax.Array,
    w_out: jax.Array,
    *,
    k: int = 2,
    capacity_factor: float = 2.0,
    activation: Callable = jax.nn.gelu,
    mesh: Optional[Mesh] = None,
    axis: str = "ep",
):
    """Mixture-of-experts feed-forward over an expert-parallel mesh axis.

    Args:
        x: (..., t, d) tokens; leading dims are flattened into the token
            dim for routing. When ``mesh`` is given, the token dim must be
            divisible by the ``axis`` mesh size (tokens sharded over it).
        gate_w: (d, E) router weights (replicated).
        w_in: (E, d, h) expert up-projections (sharded over ``axis``).
        w_out: (E, h, d) expert down-projections (sharded over ``axis``).
        k: experts per token.
        capacity_factor: headroom over perfectly-balanced expert load.
        mesh: expert-parallel mesh; ``None`` = single-device dense path
            (no collectives; matches the sharded path exactly while
            ``fraction_dropped == 0`` — capacity is per shard).
        axis: mesh axis name carrying both tokens and experts.

    Returns:
        (y, aux): y shaped like ``x``; aux holds ``load_balance_loss``
        (add ``alpha * loss`` to the training objective) and
        ``fraction_dropped``.

    ``w_in``/``w_out`` may also be :class:`~heat_tpu.core.quantize
    .QuantizedTensor` pairs (``quantize_tensor(w, axis=(0, 2))`` /
    ``quantize_params``): the expert GEMMs then read the int8/fp8
    buffers directly with the per-(expert, channel) scales folded in,
    dispatched per geometry as ``("bf16", "int8")`` autotune arms with
    the usual explore-returns-reference guarantee.
    """
    from ..core import quantize as _quantize

    q_in = isinstance(w_in, _quantize.QuantizedTensor)
    q_out = isinstance(w_out, _quantize.QuantizedTensor)
    if q_in != q_out:
        raise ValueError(
            "moe_ffn: quantize both w_in and w_out or neither "
            f"(got {type(w_in).__name__} / {type(w_out).__name__})"
        )
    if q_in:
        return _moe_ffn_quantized(
            x, gate_w, w_in, w_out, k=k, capacity_factor=capacity_factor,
            activation=activation, mesh=mesh, axis=axis,
        )
    return _moe_run(
        x, gate_w, w_in, w_out, None, None, k=k,
        capacity_factor=capacity_factor, activation=activation, mesh=mesh,
        axis=axis,
    )


def _moe_run(
    x, gate_w, w_in, w_out, s_in, s_out, *, k, capacity_factor, activation,
    mesh, axis,
):
    """The (possibly quantized) MoE step body behind :func:`moe_ffn`:
    ``s_in``/``s_out`` are None for the master-dtype path, per-(expert,
    channel) scales for the quantized one (they enter the shard program
    as runtime operands — a re-quantized checkpoint never retraces)."""
    orig_shape = x.shape
    d = orig_shape[-1]
    x2 = x.reshape(-1, d)
    tokens = x2.shape[0]
    num_experts = gate_w.shape[1]
    quantized = s_in is not None

    if mesh is None:
        cap = expert_capacity(tokens, num_experts, k, capacity_factor)
        y, aux = _moe_shard(
            x2, gate_w, w_in, w_out, s_in, s_out, k=k, capacity=cap,
            activation=activation, axis=None,
        )
        return y.reshape(orig_shape), aux

    n = mesh.shape[axis]
    if tokens % n:
        raise ValueError(f"token count {tokens} not divisible by mesh axis {axis}={n}")
    if num_experts % n:
        raise ValueError(f"num_experts {num_experts} not divisible by mesh axis {axis}={n}")
    cap = expert_capacity(tokens // n, num_experts, k, capacity_factor)

    w_spec = NamedSharding(mesh, P(axis, None, None))
    s_spec = NamedSharding(mesh, P(axis, None))
    in_specs = [P(axis, None), P(), P(axis, None, None), P(axis, None, None)]
    operands = [
        jax.device_put(x2, NamedSharding(mesh, P(axis, None))),
        gate_w,
        jax.device_put(w_in, w_spec),
        jax.device_put(w_out, w_spec),
    ]
    if quantized:
        # scales shard with their experts, like the weights they scale
        in_specs += [P(axis, None), P(axis, None)]
        operands += [
            jax.device_put(s_in, s_spec),
            jax.device_put(s_out, s_spec),
        ]
    shard_fn = shard_map_unchecked(
        partial(_moe_shard, k=k, capacity=cap, activation=activation, axis=axis),
        mesh,
        in_specs=tuple(in_specs),
        out_specs=(P(axis, None), P()),
    )
    y, aux = shard_fn(*operands)
    return y.reshape(orig_shape), aux


def _moe_ffn_quantized(
    x, gate_w, qw_in, qw_out, *, k, capacity_factor, activation, mesh, axis,
):
    """Arm-dispatched quantized MoE FFN: bf16 = dequantize both experts'
    weights and run the master-dtype path (the reference arm — bitwise
    the unquantized flow over the same dequantized values); int8 = the
    low-precision buffers ride the expert GEMMs directly."""
    from ..core import quantize as _quantize

    for name, qt in (("w_in", qw_in), ("w_out", qw_out)):
        if qt.axes != (0, 2):
            raise ValueError(
                f"moe_ffn: quantized {name} needs per-(expert, "
                f"out-channel) scales — quantize with axis=(0, 2), got "
                f"axes {qt.axes}"
            )

    def _bf16():
        return _moe_run(
            x, gate_w, _quantize.dequantize_tensor(qw_in),
            _quantize.dequantize_tensor(qw_out), None, None, k=k,
            capacity_factor=capacity_factor, activation=activation,
            mesh=mesh, axis=axis,
        )

    def _int8():
        return _moe_run(
            x, gate_w, qw_in.q, qw_out.q, qw_in.scale, qw_out.scale, k=k,
            capacity_factor=capacity_factor, activation=activation,
            mesh=mesh, axis=axis,
        )

    if _quantize._is_traced(x):
        # inside someone else's trace (grad/training): no timing, no
        # table writes — the reference arm, unconditionally
        return _bf16()
    tokens = 1
    for dim in x.shape[:-1]:
        tokens *= dim
    d = x.shape[-1]
    n = 1 if mesh is None else mesh.shape[axis]
    geometry = (
        tokens, d, qw_in.shape[2], gate_w.shape[1], n, k, str(qw_in.q.dtype),
    )
    return _quantize.tuned_arm(
        "moe_ffn", geometry, _bf16, _int8,
        desc=f"moe_ffn t={tokens} d={d} h={qw_in.shape[2]} "
             f"E={gate_w.shape[1]} S={n}",
    )


# ------------------------------------------------- one chip's share of experts

# up to this many tokens the held experts are streamed over all of them: the
# layer is then bound by reading each expert once (256 tokens are 23 GFLOP an
# expert, as long on one v5e's MXU as the expert's 88 MB are on its HBM), and
# past it the tokens are sorted by expert
STREAM_TOKENS = 256
# rows of one expert's tokens that a step of the grouped products takes
GROUP_TILE = 256


def sigmoid_group_routing(h, router, bias, *, top_k: int, n_group: int, topk_group: int,
                          scale: float):
    """Route ``h`` of ``(tokens, d)`` over all ``E`` experts of ``router``
    ``(d, E)``: scores ``s = sigmoid(h router)``; the choice is made by ``s +
    bias`` (the balancing bias takes no part in the weights): the experts lie
    in ``n_group`` groups of consecutive numbers, a group scores the sum of
    its two largest, the ``topk_group`` best groups stay, and among their
    experts the ``top_k`` largest are chosen.  Weights ``scale * s_e / sum of
    the chosen s``.  The product is float32 at ``highest``: a choice is a
    discontinuity, and the router is the smallest matrix of the layer.

    Returns ``(weights, chosen)``: float32 ``(tokens, top_k)`` and int32
    ``(tokens, top_k)`` expert numbers."""
    experts = router.shape[1]
    s = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    choice = s + bias.astype(jnp.float32)
    if n_group > 1:
        by_group = choice.reshape(-1, n_group, experts // n_group)
        group_score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
        kept = jax.lax.top_k(group_score, topk_group)[1]                     # (tokens, topk_group)
        stays = jnp.any(kept[:, :, None] == jnp.arange(n_group)[None, None, :], axis=1)
        choice = jnp.where(jnp.repeat(stays, experts // n_group, axis=1), choice, -jnp.inf)
    chosen = jax.lax.top_k(choice, top_k)[1]
    weights = jnp.take_along_axis(s, chosen, axis=1)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True) * scale
    return weights, chosen.astype(jnp.int32)


def softmax_topk_routing(h, router, *, top_k: int):
    """Route ``h`` of ``(tokens, d)`` over all ``E`` experts of ``router``
    ``(d, E)``: ``p = softmax(h router)`` over the ``E``, the ``top_k``
    largest chosen, their weights ``p_e`` divided by the chosen ones' sum
    (Qwen3-MoE's ``norm_topk_prob``; :func:`moe_ffn`'s choice too, without
    its capacity).  The product and the
    softmax are float32, the product at ``highest``, as
    :func:`sigmoid_group_routing`'s.

    Returns ``(weights, chosen)``: float32 ``(tokens, top_k)`` and int32
    ``(tokens, top_k)`` expert numbers."""
    _, weights, chosen = _softmax_top_k(
        jnp.dot(h.astype(jnp.float32), router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST), top_k)
    return weights, chosen.astype(jnp.int32)


def _dot(x, w):
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def _streamed(x, mine, experts):
    """Every held expert over every token, the routing weight (zero where the
    token did not choose the expert) as the mask: the same device work
    whatever the router chose."""
    w_gate, w_up, w_down = experts["w_gate"], experts["w_up"], experts["w_down"]
    xb = x.astype(w_gate.dtype)
    gate = jnp.einsum("td,edf->tef", xb, w_gate, preferred_element_type=jnp.float32)
    up = jnp.einsum("td,edf->tef", xb, w_up, preferred_element_type=jnp.float32)
    inner = (jax.nn.silu(gate) * up * mine[:, :, None]).astype(w_down.dtype)
    return jnp.einsum("tef,efd->td", inner, w_down, preferred_element_type=jnp.float32)


def _grouped(x, weights, local, mine, experts):
    """The token-expert pairs sorted by held expert, then one expert's rows
    ``GROUP_TILE`` at a time: as many steps as the pairs that landed here
    need, none padded to a capacity and none dropped."""
    tile = GROUP_TILE
    tokens, d = x.shape
    top_k = local.shape[1]
    count = experts["w_gate"].shape[0]
    pairs = tokens * top_k
    key = jnp.where(mine, local, count).reshape(-1)             # pairs of other chips sort last
    order = jnp.argsort(key, stable=True)
    sizes = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0, dtype=jnp.int32)
    starts = jnp.cumsum(sizes) - sizes
    tiles = (sizes + tile - 1) // tile
    tiles_before = jnp.cumsum(tiles)                            # through each expert
    rows = jnp.take(x.astype(experts["w_gate"].dtype), order // top_k, axis=0)
    rows = jnp.pad(rows, ((0, tile), (0, 0)))

    def one_tile(i, out):
        e = jnp.searchsorted(tiles_before, i, side="right").astype(jnp.int32)
        first = starts[e] + (i - (tiles_before[e] - tiles[e])) * tile
        part = {name: jax.lax.dynamic_index_in_dim(w, e, keepdims=False)
                for name, w in experts.items()}
        h = jax.lax.dynamic_slice_in_dim(rows, first, tile)
        y = _dot(jax.nn.silu(_dot(h, part["w_gate"])) * _dot(h, part["w_up"]), part["w_down"])
        here = (first + jnp.arange(tile, dtype=jnp.int32)) < starts[e] + sizes[e]
        old = jax.lax.dynamic_slice_in_dim(out, first, tile)
        return jax.lax.dynamic_update_slice_in_dim(out, jnp.where(here[:, None], y, old),
                                                   first, axis=0)

    out = jax.lax.fori_loop(0, tiles_before[-1], one_tile,
                            jnp.zeros((pairs + tile, d), jnp.float32))
    place = jnp.argsort(order)                                  # where each pair's row lies
    back = jnp.take(out, place, axis=0).reshape(tokens, top_k, d)
    return jnp.sum(back * jnp.where(mine, weights, 0.0)[:, :, None], axis=1)


def held_experts_ffn(x, router, experts, *, held, top_k: int, n_group: int = 1,
                     topk_group: int = 1, scale: float = 1.0, bias=None):
    """The held experts' part of a routed gated-SiLU layer whose router is
    :func:`sigmoid_group_routing` (``router`` ``(d, E)`` over all ``E``
    experts): :func:`held_experts_sum` of its choice.

    Returns ``(y, counts)`` as :func:`held_experts_sum`."""
    if bias is None:
        bias = jnp.zeros((router.shape[1],), jnp.float32)
    weights, chosen = sigmoid_group_routing(x, router, bias, top_k=top_k, n_group=n_group,
                                            topk_group=topk_group, scale=scale)
    return held_experts_sum(x, weights, chosen, experts, held=held)


def held_experts_sum(x, weights, chosen, experts, *, held):
    """``sum over e chosen and held of g_e * Expert_e(x)`` for every token of
    ``x`` ``(tokens, d)``, float32, from a router's ``(weights, chosen)`` of
    ``(tokens, top_k)`` (:func:`sigmoid_group_routing`,
    :func:`softmax_topk_routing`).  ``experts`` holds this chip's, ``w_gate``
    and ``w_up`` of ``(count, d, f)`` and ``w_down`` of ``(count, f, d)``;
    ``held = (first, count)`` are their numbers among the ``E``.

    Two lowerings of one sum, chosen by the number of tokens alone: up to
    :data:`STREAM_TOKENS` (a decode step) every held expert is streamed once over
    all tokens, so that the device's work does not depend on the routing;
    past it (a prefill chunk) the pairs are sorted by expert and only the
    pairs that landed here are computed.  No token is ever dropped: there is
    no capacity.

    Returns ``(y, counts)``; ``counts`` are two int32 device numbers, the
    token-expert pairs computed here and the held experts that received a
    token."""
    first, count = held
    local = chosen - first
    mine = (local >= 0) & (local < count)
    lands = mine[:, :, None] & (local[:, :, None] == jnp.arange(count))    # (tokens, top_k, count)
    counts = {"pairs": jnp.sum(mine, dtype=jnp.int32),
              "hit": jnp.sum(jnp.any(lands, axis=(0, 1)), dtype=jnp.int32)}
    with jax.named_scope("ht.lm.moe_experts"):      # the held experts' products alone
        if x.shape[0] <= STREAM_TOKENS:
            dense = jnp.sum(jnp.where(lands, weights[:, :, None], 0.0), axis=1)
            return _streamed(x, dense, experts), counts
        return _grouped(x, weights, local, mine, experts), counts
