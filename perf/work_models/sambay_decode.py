"""One call of the decode cell: ``serve.decode_steps`` greedy steps of
``serve.sessions`` sequences at ``serve.context`` positions, from the
configuration's shapes alone.

A step must read every weight once (the batch shares them), the shared cache
once for each layer that attends over it (``shared_kv_read``), every window
ring once, and read and write every Mamba state.  The context is counted at
``serve.context`` for every step of the call (the steps add up to 8 more
positions: a lower bound).  FLOPs: two per weight and sequence, and the
attention products."""

from perf.reference.sambay import layer_types
from perf.work_models import shared_kv_read


def layer_kinds(config: dict) -> list:
    """The kind of every layer, by the published placement."""
    return list(layer_types(config))


def parameters(config: dict) -> dict:
    """Parameters of one layer of each kind (mixer, MLP and the two norms),
    of the tied embedding, and of the whole model."""
    d, f = config["hidden_size"], config["intermediate_size"]
    size = config["assumed"]["sizes"]
    di, ds, rank, taps = size["d_inner"], size["d_state"], size["dt_rank"], size["d_conv"]
    hd = d // config["num_attention_heads"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    attn_small = 4 * hd + 2 * hd                       # lambda vectors, RMS gain
    mixer = {
        "mamba": d * 2 * di + di * d + di * (rank + 2 * ds) + rank * di + di   # in, out, x, dt
        + ds * di + taps * di + di + di,                                      # A, conv, D
        "gmu": d * di + di * d,
        "window": d * (q + 2 * kv) + q * d + attn_small,
        "full": d * (q + 2 * kv) + q * d + attn_small,
        "cross": d * q + q * d + attn_small,
    }
    mlp = 3 * d * f + 4 * d                              # and both LayerNorms
    out = {kind: n + mlp for kind, n in mixer.items()}
    out["mlp"] = 3 * d * f
    out["embed"] = config["vocab_size"] * d
    out["total"] = out["embed"] + 2 * d + sum(out[k] for k in layer_kinds(config))
    return out


def work(config: dict, item: dict, chips: int) -> dict:
    serve = config["serve"]
    steps, batch, context = serve["decode_steps"], serve["sessions"], serve["context"]
    kinds = layer_kinds(config)
    size = config["assumed"]["sizes"]
    token_bytes = shared_kv_read.token_bytes(config)
    weights = 2 * parameters(config)["total"]
    window = kinds.count("window") * batch * min(config["sliding_window"], context) * token_bytes
    state = kinds.count("mamba") * batch * (
        size["d_state"] * size["d_inner"] * 4 + (size["d_conv"] - 1) * size["d_inner"] * 2)
    shared = shared_kv_read.work(config, item, chips)
    return {
        "flops": steps * batch * 2.0 * parameters(config)["total"] + shared["flops"],
        "bytes": steps * (weights + window + 2 * state) + shared["bytes"],
    }
