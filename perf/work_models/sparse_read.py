"""What a decode call of a model with latent attention, a learned sparse
selection and held experts must read, in three parts, from the shapes
themselves (what the roofline readers have: a run's ``lm.decode`` span):

- ``index_scan``: every layer scores every visible index key of every session
  once a step (``index_bytes`` a key);
- ``latent_rows``: every layer then reads the chosen latent rows, ``selected``
  a session or all of a shorter context (``latent_bytes`` a row, read once as
  key and value);
- ``expert_weights``: every expert layer reads every held expert once a step
  (``expert_bytes`` an expert), whatever the router chose: at the deployment's
  load a held expert is idle in 0.03% of steps, and a program that skipped
  idle experts would be faster only through the cut.

The context is counted as at the call's first step for every step (a lower
bound).  Bytes only: each part is bound by reading, not by its products (an
index key of 256 B is 16,384 FLOP: 0.31 ns of bytes against 0.08 ns of the
matrix unit)."""

# what a decode span says of a call
SHAPES = ("steps", "layers", "moe_layers", "batch", "context", "selected", "latent_bytes",
          "index_bytes", "experts_held", "expert_bytes")


def index_scan(shapes: dict) -> dict:
    n = shapes["steps"] * shapes["layers"] * shapes["batch"] * shapes["context"]
    return {"flops": 0.0, "bytes": float(n * shapes["index_bytes"])}


def latent_rows(shapes: dict) -> dict:
    rows = min(shapes["context"], shapes["selected"])
    n = shapes["steps"] * shapes["layers"] * shapes["batch"] * rows
    return {"flops": 0.0, "bytes": float(n * shapes["latent_bytes"])}


def expert_weights(shapes: dict) -> dict:
    n = shapes["steps"] * shapes["moe_layers"] * shapes["experts_held"]
    return {"flops": 0.0, "bytes": float(n * shapes["expert_bytes"])}


def of_config(config: dict) -> dict:
    """The shapes of a configuration's serving call."""
    serve = config["serve"]
    itemsize = 4 if config["dtype"] == "float32" else 2
    layers = config["num_hidden_layers"]
    return {
        "steps": serve["decode_steps"], "layers": layers,
        "moe_layers": layers - config["first_k_dense_replace"], "batch": serve["sessions"],
        "context": serve["context"], "selected": config["index_topk"],
        "latent_bytes": (config["kv_lora_rank"] + config["qk_rope_head_dim"]) * itemsize,
        "index_bytes": config["index_head_dim"] * itemsize,
        "experts_held": config["n_routed_experts"],
        "expert_bytes": 3 * config["hidden_size"] * config["moe_intermediate_size"] * itemsize,
    }
