"""One call of the sparse decode cell: ``serve.decode_steps`` greedy steps of
``serve.sessions`` sequences of a DeepSeek-V3.2 share, from the configuration's
shapes alone (the rewind before them copies one token a session: nothing).

A step must read every weight that lies here once (the batch shares them; of
the embedding only the rows of the tokens fed; **every held expert counted**,
``sparse_read.expert_weights`` says why), every visible index key and the
chosen latent rows (``sparse_read``).  FLOPs: two per weight and sequence
outside the routed experts; of those a token reaches ``experts per token x
held / router's`` in expectation; and the indexer's and the attention's
products with their caches."""

from perf.work_models import sparse_read


def parameters(config: dict) -> dict:
    """Parameters of an attention block (the indexer included), of one
    expert, of a dense layer, of an expert layer as held here and with every
    expert of the router, of the embedding, the head and of all that lies
    here."""
    d, f = config["hidden_size"], config["moe_intermediate_size"]
    heads, rank, q_rank = (config["num_attention_heads"], config["kv_lora_rank"],
                           config["q_lora_rank"])
    nope, rope, vd = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    routed = config["assumed"]["sizes"]["router_experts"]
    mla = (d * q_rank + q_rank * heads * (nope + rope) + d * (rank + rope)
           + rank * heads * (nope + vd) + heads * vd * d + q_rank + rank)
    indexer = (q_rank * config["index_n_heads"] * config["index_head_dim"]
               + d * config["index_head_dim"] + d * config["index_n_heads"]
               + 2 * config["index_head_dim"])
    out = {"mla": mla, "indexer": indexer, "attention": mla + indexer, "expert": 3 * d * f,
           "router": d * routed + routed}
    outside = out["attention"] + 2 * d + out["router"] + out["expert"]      # with the shared expert
    out["dense_layer"] = out["attention"] + 2 * d + 3 * d * config["intermediate_size"]
    out["expert_layer"] = outside + config["n_routed_experts"] * out["expert"]
    out["expert_layer_uncut"] = outside + routed * out["expert"]
    out["embed"] = out["head"] = config["vocab_size"] * d
    dense = config["first_k_dense_replace"]
    out["total"] = (dense * out["dense_layer"]
                    + (config["num_hidden_layers"] - dense) * out["expert_layer"]
                    + out["embed"] + out["head"] + d)
    return out


def cache_token_bytes(config: dict) -> int:
    """Both caches' bytes a position and session, every layer."""
    shapes = sparse_read.of_config(config)
    return shapes["layers"] * (shapes["latent_bytes"] + shapes["index_bytes"])


def step_bytes(config: dict) -> float:
    n = parameters(config)
    shapes = dict(sparse_read.of_config(config), steps=1)
    itemsize = 4 if config["dtype"] == "float32" else 2
    read = n["total"] - n["embed"] + config["serve"]["sessions"] * config["hidden_size"]
    return (float(itemsize * read) + sparse_read.index_scan(shapes)["bytes"]
            + sparse_read.latent_rows(shapes)["bytes"])


def work(config: dict, item: dict, chips: int) -> dict:
    serve = config["serve"]
    steps, batch = serve["decode_steps"], serve["sessions"]
    n = parameters(config)
    shapes = sparse_read.of_config(config)
    routed_held = shapes["moe_layers"] * config["n_routed_experts"] * n["expert"]
    reached = (shapes["moe_layers"] * n["expert"] * config["num_experts_per_tok"]
               * config["n_routed_experts"] / config["assumed"]["sizes"]["router_experts"])
    weights = n["total"] - n["embed"] - routed_held + reached
    keys = shapes["layers"] * shapes["context"]
    rows = shapes["layers"] * min(shapes["context"], shapes["selected"])
    heads = config["num_attention_heads"]
    caches = (keys * config["index_n_heads"] * config["index_head_dim"]
              + rows * heads * (2 * config["kv_lora_rank"] + config["qk_rope_head_dim"]))
    return {
        "flops": steps * batch * 2.0 * (weights + caches),
        "bytes": steps * step_bytes(config),
    }
