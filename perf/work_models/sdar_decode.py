"""One call of the block-diffusion decode cell: ``serve.decode_blocks`` blocks
of ``serve.sessions`` sequences of SDAR-30B-A3B-Chat, from the configuration's
shapes alone (the rewind before them copies the pending blocks: nothing).

A block is ``denoising_steps`` denoising passes and one commit pass, each over
the block's ``block_length`` positions of every session.  A denoising pass
must read every weight once (the batch shares them; of the embedding only the
rows of the tokens fed; **every expert counted**: 16 x 4 x 8 / 128 = 4 tokens
an expert and pass, about 98% of the experts hit, so a layer is bound by
reading its experts whatever the router chose), the head, and the key/value
stream: every layer reads every visible row of every session once a pass
(:func:`kv_stream`).  A commit pass needs no head, and of the last layer only
its key and value projections: its stream and its experts are read by the
other layers alone.  The rows are counted at the shortest prompt's end for
every session (``serve.context_min``, a lower bound).  FLOPs: two per weight
and token outside the routed experts, of those a token reaches ``experts per
token``, and the attention's products with the visible rows."""


def kv_stream(shapes: dict) -> dict:
    """The least bytes a call's stream moves: each key/value row (of one
    layer: ``row_bytes``) a pass visited, read once.  Bytes only: the floor is
    HBM's (a row of 2,048 B is 32 query rows x 256 x 2 FLOP, 0.08 ns at the
    matrix unit's peak against 2.5 ns of HBM)."""
    return {"flops": 0.0, "bytes": float(shapes["kv_rows_streamed"] * shapes["row_bytes"])}


def parameters(config: dict) -> dict:
    """Parameters of an attention block, of the router, of one expert, of a
    layer (every expert), of the embedding, the head and of all that the chip
    holds."""
    d, f, hd = config["hidden_size"], config["moe_intermediate_size"], config["head_dim"]
    q_width = config["num_attention_heads"] * hd
    kv_width = config["num_key_value_heads"] * hd
    out = {"attention": 2 * d * q_width + 2 * d * kv_width + 2 * hd,
           "kv_projection": 2 * d * kv_width,
           "router": d * config["num_experts"], "expert": 3 * d * f}
    out["layer"] = out["attention"] + out["router"] + 2 * d + config["num_experts"] * out["expert"]
    out["embed"] = out["head"] = config["vocab_size"] * d
    out["total"] = config["num_hidden_layers"] * out["layer"] + out["embed"] + out["head"] + d
    return out


def _itemsize(config: dict) -> int:
    return 4 if config["dtype"] == "float32" else 2


def of_config(config: dict) -> dict:
    """The shapes of a configuration's serving call: the key/value rows its
    passes stream (every session at the shortest prompt's end) and the bytes
    of a row of one layer."""
    serve, layers = config["serve"], config["num_hidden_layers"]
    width, steps = config["assumed"]["block_length"], config["assumed"]["denoising_steps"]
    seen = sum(serve["context_min"] + width * (j + 1) for j in range(serve["decode_blocks"]))
    return {"kv_rows_streamed": (steps * layers + layers - 1) * serve["sessions"] * seen,
            "row_bytes": 2 * config["num_key_value_heads"] * config["head_dim"] * _itemsize(config)}


def pass_bytes(config: dict) -> dict:
    """A denoising pass's least bytes by part, its first block's: the routed
    experts, the key/value stream, the head, the rest of the weights and the
    embedding rows of the tokens fed."""
    n, size = parameters(config), _itemsize(config)
    serve, layers = config["serve"], config["num_hidden_layers"]
    width = config["assumed"]["block_length"]
    rows = layers * serve["sessions"] * (serve["context_min"] + width)
    experts = layers * config["num_experts"] * n["expert"]
    return {"experts": float(size * experts),
            "kv_stream": kv_stream({"kv_rows_streamed": rows,
                                    "row_bytes": of_config(config)["row_bytes"]})["bytes"],
            "head": float(size * n["head"]),
            "rest": float(size * (layers * (n["attention"] + n["router"])
                                  + serve["sessions"] * width * config["hidden_size"]))}


def commit_bytes(config: dict) -> dict:
    """A commit pass's least bytes by part: no head, and the last layer's key
    and value projections alone."""
    n, size = parameters(config), _itemsize(config)
    serve, layers = config["serve"], config["num_hidden_layers"]
    width = config["assumed"]["block_length"]
    part = pass_bytes(config)
    return {"experts": part["experts"] * (layers - 1) / layers,
            "kv_stream": part["kv_stream"] * (layers - 1) / layers,
            "rest": float(size * ((layers - 1) * (n["attention"] + n["router"]) + n["kv_projection"]
                                  + serve["sessions"] * width * config["hidden_size"]))}


def work(config: dict, item: dict, chips: int) -> dict:
    serve, layers = config["serve"], config["num_hidden_layers"]
    width, steps = config["assumed"]["block_length"], config["assumed"]["denoising_steps"]
    blocks, tokens = serve["decode_blocks"], serve["sessions"] * width
    n, size = parameters(config), _itemsize(config)
    reached = config["num_experts_per_tok"] * n["expert"]
    per_layer = n["attention"] + n["router"] + reached
    denoise = 2.0 * tokens * (layers * per_layer + n["head"])
    commit = 2.0 * tokens * ((layers - 1) * per_layer + n["kv_projection"])
    shapes = of_config(config)
    heads, hd = config["num_attention_heads"], config["head_dim"]
    # each visible key/value row: QK and PV of the block's positions x the group's heads
    attend = shapes["kv_rows_streamed"] * width * (heads // config["num_key_value_heads"]) * 4 * hd
    stream = kv_stream(shapes)["bytes"]
    weights = blocks * (steps * (sum(pass_bytes(config).values()) - pass_bytes(config)["kv_stream"])
                        + sum(commit_bytes(config).values()) - commit_bytes(config)["kv_stream"])
    del size
    return {"flops": blocks * (steps * denoise + commit) + attend, "bytes": weights + stream}
