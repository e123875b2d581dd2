"""One call of the retention decode cell: the saved state copied back, then
``serve.decode_steps`` greedy steps of ``serve.sessions`` sequences, from the
configuration's shapes alone.

A step must read every weight once (the batch shares them; of the embedding
only the rows of the tokens fed), and read and write every layer's state
(``retention_state``).  The rewind reads the saved state and writes the live
one.  FLOPs: two per weight and sequence, and the state step's."""

from perf.work_models import retention_state


def parameters(config: dict) -> dict:
    """Parameters of one layer, of the embedding, of the head and of the
    whole model."""
    d, f, hd = config["hidden_size"], config["intermediate_size"], config["head_dim"]
    q, kv = config["num_attention_heads"] * hd, config["num_key_value_heads"] * hd
    mixer = 2 * d * q + 2 * d * kv                        # q and o, k and v
    mixer += d * config["num_key_value_heads"] + config["num_key_value_heads"]   # the gate
    mixer += 2 * hd                                       # the head norms of q and k
    out = {"mixer": mixer, "mlp": 3 * d * f, "layer": mixer + 3 * d * f + 2 * d}
    out["embed"] = out["head"] = config["vocab_size"] * d
    out["total"] = config["num_hidden_layers"] * out["layer"] + out["embed"] + out["head"] + d
    return out


def state_bytes(config: dict) -> int:
    """Every layer's state of every session, as the mathematics needs it."""
    return (config["serve"]["sessions"] * config["num_hidden_layers"]
            * config["num_key_value_heads"] * retention_state.head_state_bytes(config["head_dim"]))


def step_bytes(config: dict) -> float:
    n = parameters(config)
    itemsize = 4 if config["dtype"] == "float32" else 2
    read = n["total"] - n["embed"] + config["serve"]["sessions"] * config["hidden_size"]
    return float(itemsize * read + 2 * state_bytes(config))


def work(config: dict, item: dict, chips: int) -> dict:
    serve = config["serve"]
    steps, batch = serve["decode_steps"], serve["sessions"]
    n = parameters(config)
    stepped = retention_state.work(config, item, chips)
    return {
        "flops": steps * batch * 2.0 * (n["total"] - n["embed"]) + stepped["flops"],
        "bytes": steps * step_bytes(config) + 2.0 * state_bytes(config),
    }
