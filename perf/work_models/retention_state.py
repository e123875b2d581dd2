"""The state step of a power-retention model in one call of a decode cell:
every layer reads and writes its whole state once a step.  The state of one
key/value head is ``head_dim (head_dim + 1) / 2`` features by ``head_dim``
values and a normaliser, in float32: what the mathematics needs, whatever the
program's layout holds beside it (a blocked layout with rows that stay zero
moves more bytes and gains no floor).  From shapes alone.  FLOPs: a decay and
an update for every number of the state, and a product and a sum for each of
the ``heads // kv_heads`` query heads that read it.

``work`` counts them from a configuration; ``step_work`` from the shapes
themselves, which is what the roofline's reader has (a run's decode span)."""

# what a decode span says of a call: the arguments of ``step_work``
SHAPES = ("steps", "layers", "batch", "kv_heads", "head_dim")


def head_state_bytes(head_dim: int) -> int:
    """One key/value head's state for one sequence."""
    return 4 * (head_dim * (head_dim + 1) // 2) * (head_dim + 1)


def step_work(steps, layers, batch, kv_heads, head_dim, readers=0) -> dict:
    """Every layer reads and writes every sequence's state once a step."""
    numbers = steps * layers * batch * kv_heads * head_state_bytes(head_dim) // 4
    return {"flops": float(numbers * (3 + 2 * readers)),
            "bytes": float(2 * 4 * numbers)}


def work(config: dict, item: dict, chips: int) -> dict:
    serve = config["serve"]
    return step_work(
        serve["decode_steps"], config["num_hidden_layers"], serve["sessions"],
        config["num_key_value_heads"], config["head_dim"],
        readers=config["num_attention_heads"] // config["num_key_value_heads"])
