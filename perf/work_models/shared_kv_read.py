"""The reads of the shared key/value cache in one call of the decode cell:
every layer that attends over it (the full-attention layer and each
cross-attention layer) reads ``context`` tokens of every sequence once a
step, ``token_bytes`` each (keys and values of all key/value heads in
bfloat16, stored once).  From shapes alone, whatever implements the read.
FLOPs: a score and a value product for every query head and key.

``work`` counts them from a configuration; ``read_work`` from the shapes
themselves, which is what the roofline's reader has (a run's decode span)."""

from perf.reference.sambay import layer_types

# what a decode span says of a call: the arguments of ``read_work``
SHAPES = ("steps", "readers", "batch", "context", "token_bytes")


def readers(config: dict) -> int:
    """Layers that attend over the shared cache: the full-attention layer and
    the cross-attention layers."""
    kinds = layer_types(config)
    return kinds.count("full") + kinds.count("cross")


def token_bytes(config: dict) -> int:
    """Keys and values of every key/value head, in the configuration's type."""
    hd = config["hidden_size"] // config["num_attention_heads"]
    return 2 * config["num_key_value_heads"] * hd * (4 if config["dtype"] == "float32" else 2)


def read_work(steps, readers, batch, context, token_bytes, flops_per_token=0.0) -> dict:
    """Every reader reads every visible token of every sequence once a step."""
    reads = steps * readers * batch * context
    return {"flops": reads * flops_per_token, "bytes": float(reads * token_bytes)}


def work(config: dict, item: dict, chips: int) -> dict:
    serve = config["serve"]
    hd = config["hidden_size"] // config["num_attention_heads"]
    return read_work(
        serve["decode_steps"], readers(config), serve["sessions"], serve["context"],
        token_bytes(config),
        # a score and a value product for every query head and key
        flops_per_token=config["num_attention_heads"] * (2.0 * hd + 2.0 * 2 * hd))
