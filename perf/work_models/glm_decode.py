"""One call of the drafting decode cell: ``serve.decode_steps`` speculative
steps of ``serve.sessions`` sequences of GLM-4.7-Flash, from the
configuration's shapes alone (the rewind before them copies two tokens a
session: nothing).

A step checks two positions a session (the pending token and its draft)
through the main layers and makes one draft through the MTP module.  It must
read every weight once (the batch shares them; of the embedding only the rows
of the tokens fed; **every expert counted**: 2 x 16 x 4 / 64 = 2 tokens an
expert and step, about 87% of the experts hit, so a layer is bound by reading
its experts whatever the router chose), the head twice (checking and
drafting), and the full latent stream: every layer (the MTP layer's too)
reads every visible row of every session once a step (:func:`latent_stream`).
The rows are counted at the shortest prompt's end for every session and
step (``serve.context_min``, a lower bound).  FLOPs: two per weight and token
outside the routed experts, of those a token reaches ``experts per token``,
and the attention's products with the visible rows in the latent space."""


def latent_stream(shapes: dict) -> dict:
    """The least bytes a call's full reads move: each row a layer's stream
    visited, read once (``latent_bytes`` a row: its latent and rotary parts).
    Bytes only: the floor is HBM's (a row of 1,152 B is 40 x 1,088 x 2 FLOP
    of two positions' 20 heads, 0.44 ns at the matrix unit's peak against
    1.41 ns of HBM)."""
    return {"flops": 0.0, "bytes": float(shapes["latent_rows_streamed"] * shapes["latent_bytes"])}


def parameters(config: dict) -> dict:
    """Parameters of an attention block, of one expert, of a dense layer, of
    an expert layer (every expert), of the embedding, the head, the MTP
    module and of all that the chip holds."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    heads, rank, q_rank = (config["num_attention_heads"], config["kv_lora_rank"],
                           config["q_lora_rank"])
    nope, rope, vd = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    experts = config["n_routed_experts"]
    attention = (d * q_rank + q_rank * heads * (nope + rope) + d * (rank + rope)
                 + rank * heads * (nope + vd) + heads * vd * d + q_rank + rank)
    out = {"attention": attention, "expert": 3 * d * f, "router": d * experts + experts}
    out["dense_layer"] = attention + 2 * d + 3 * d * config["intermediate_size"]
    out["expert_layer"] = attention + 2 * d + out["router"] + (experts + 1) * out["expert"]
    out["embed"] = out["head"] = config["vocab_size"] * d
    out["mtp"] = 2 * d * d + out["expert_layer"] + 3 * d
    dense = config["first_k_dense_replace"]
    out["total"] = (dense * out["dense_layer"]
                    + (config["num_hidden_layers"] - dense) * out["expert_layer"]
                    + out["embed"] + out["head"] + out["mtp"] + d)
    return out


def of_config(config: dict) -> dict:
    """The shapes of a configuration's serving call."""
    serve = config["serve"]
    itemsize = 4 if config["dtype"] == "float32" else 2
    layers = config["num_hidden_layers"] + config["num_nextn_predict_layers"]
    rows = layers * serve["sessions"] * (serve["context_min"] + 2) * serve["decode_steps"]
    return {"latent_rows_streamed": rows,
            "latent_bytes": (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * itemsize}


def step_bytes(config: dict) -> dict:
    """A step's least bytes by part: the experts (routed and shared, main and
    MTP layers), the full latent stream, the head (twice), the rest of the
    weights and the embedding rows of the tokens fed."""
    n = parameters(config)
    itemsize = 4 if config["dtype"] == "float32" else 2
    moe_layers = config["num_hidden_layers"] - config["first_k_dense_replace"] + 1
    experts = moe_layers * (config["n_routed_experts"] + 1) * n["expert"]
    one_step = dict(config, serve=dict(config["serve"], decode_steps=1))
    rest = n["total"] - n["embed"] - n["head"] - experts
    return {"experts": float(itemsize * experts),
            "latent_stream": latent_stream(of_config(one_step))["bytes"],
            "head": float(2 * itemsize * n["head"]),
            "rest": float(itemsize * (rest + 4 * config["serve"]["sessions"] * config["hidden_size"]))}


def work(config: dict, item: dict, chips: int) -> dict:
    serve = config["serve"]
    steps, batch = serve["decode_steps"], serve["sessions"]
    n = parameters(config)
    moe_layers = config["num_hidden_layers"] - config["first_k_dense_replace"] + 1
    routed = moe_layers * config["n_routed_experts"] * n["expert"]
    reached = moe_layers * config["num_experts_per_tok"] * n["expert"]
    weights = n["total"] - n["embed"] - routed + reached + n["head"]        # the head twice
    rows = of_config(config)["latent_rows_streamed"] / steps / batch       # a session and step
    heads = config["num_attention_heads"]
    attend = rows * 2 * heads * (2 * config["kv_lora_rank"] + config["qk_rope_head_dim"])
    return {
        "flops": steps * batch * 2.0 * (2 * weights + attend),
        "bytes": steps * sum(step_bytes(config).values()),
    }
