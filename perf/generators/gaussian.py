"""A ``rows x cols`` matrix of independent N(0, 1) entries."""

import jax
import jax.numpy as jnp

from . import rows_in_blocks


def make(config: dict, seed: int, sharding) -> dict:
    rows, cols = int(config["rows"]), int(config["cols"])
    dtype = jnp.dtype(config["dtype"])

    def block(key, first, nrows):
        del first
        return jax.random.normal(key, (nrows, cols), jnp.float32).astype(dtype)

    return {"a": rows_in_blocks(block, rows, cols, seed, sharding)}
