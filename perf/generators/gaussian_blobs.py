"""Rows drawn around ``blobs`` centres: row r belongs to blob ``r % blobs``.

``x[r] = centre[r % blobs] + N(0, 1)``, centres ``~ N(0, center_scale**2)``
per coordinate.  The interleaving is known to the drivers, which start a fit
from one row of each blob; it costs the program nothing, which sees rows.
"""

import jax
import jax.numpy as jnp

from . import key_from_seed, rows_in_blocks


def centres(config: dict, seed: int):
    key = jax.random.fold_in(key_from_seed(seed), 0xB10B)
    shape = (int(config["data"]["blobs"]), int(config["features"]))
    scale = float(config["data"]["center_scale"])
    return scale * jax.random.normal(key, shape, jnp.float32)


def make(config: dict, seed: int, sharding) -> dict:
    rows, feats = int(config["rows"]), int(config["features"])
    blobs = int(config["data"]["blobs"])
    dtype = jnp.dtype(config["dtype"])

    def block(key, first, nrows, mu):
        which = (first + jnp.arange(nrows, dtype=jnp.int32)) % blobs
        noise = jax.random.normal(key, (nrows, feats), jnp.float32)
        return (noise + mu[which]).astype(dtype)

    return {"x": rows_in_blocks(block, rows, feats, seed, sharding, centres(config, seed))}
