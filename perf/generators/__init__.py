"""Data generators: ``make(config, seed, sharding)`` returns device arrays
made in one jitted call from the seed.  One file per kind, named in a
configuration's ``data.generator``; the shapes are the configuration's own
top-level keys, a generator's parameters sit beside its name under ``data``.

Rows are made block by block on the device that holds them, so that making
the data never takes more device memory than the data and one block: a
process's peak never falls again, and the peak a run reports has to be the
program's, not the generator's.
"""

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from perf.reference import block_rows

BLOCK_ROWS = 1 << 19


def key_from_seed(seed: int):
    """A PRNG key from any whole number (seeds pass 2**31).  ``rbg`` keys: the
    chip's own generator makes 1.28e9 normals in a fraction of the seconds
    threefry takes, and set-up is most of what a check costs."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def rows_in_blocks(make_block, rows: int, cols: int, seed: int, sharding, *operands):
    """A ``(rows, cols)`` array, row-sharded as ``sharding`` says.

    ``make_block(key, first_row, nrows, *operands)`` returns rows
    ``first_row .. first_row + nrows`` as ``(nrows, cols)``; each block has a
    key of its own, folded from the seed and the block's number, so the data
    depend on the seed and the shape alone.  Whatever else depends on the
    seed comes in as ``operands`` (small arrays, on every device): a value
    closed over would be a constant of the program, and every new seed would
    compile it anew (3.3 s a run at 2e7 x 64; my chip runs, PR 24).
    """
    mesh = sharding.mesh
    axis = sharding.spec[0]
    ndev = mesh.shape[axis] if axis is not None else 1
    if rows % ndev:
        raise ValueError(f"{rows} rows do not divide over {ndev} devices")
    rows_local = rows // ndev
    # blocks of about 128 MiB
    block = block_rows(rows_local, max(8, min(BLOCK_ROWS, (1 << 25) // max(cols, 1))))
    nblocks = rows_local // block

    def per_device(key_data, *operands):
        key = jax.random.wrap_key_data(key_data, impl="rbg")
        dev = jax.lax.axis_index(axis) if axis is not None else 0

        def one(b):
            number = dev * nblocks + b
            first = dev * rows_local + b * block
            return make_block(jax.random.fold_in(key, number), first, block, *operands)

        out = jax.lax.map(one, jnp.arange(nblocks, dtype=jnp.int32))
        return out.reshape(rows_local, cols)

    fn = jax.jit(jax.shard_map(
        per_device, mesh=mesh, in_specs=P(), out_specs=sharding.spec,
        check_vma=False,
    ))
    return fn(jax.random.key_data(key_from_seed(seed)), *operands)
