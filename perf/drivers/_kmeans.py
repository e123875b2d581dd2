"""What the two KMeans drivers share: where a fit starts."""

import jax
import jax.numpy as jnp
import numpy as np


def init_rows(seed: int, index: int, rows: int, k: int) -> np.ndarray:
    """Row numbers of the initial centres of call ``index``: one row of each
    blob (row r belongs to blob ``r % k``, see generators/gaussian_blobs),
    drawn from the seed.  The reference starts from the same rows."""
    rng = np.random.default_rng([int(seed), int(index), 0x1217])
    return (np.arange(k) + k * rng.integers(0, rows // k, size=k)).astype(np.int32)


@jax.jit
def rows_of(x, idx):
    """``x[idx]`` for a handful of rows, as one dynamic slice each: a gather
    over the tall array makes XLA lay out a transposed copy of all of it
    (9.5 GB at 2e7 x 64, my chip run, PR 24)."""
    return jnp.concatenate(
        [jax.lax.dynamic_slice_in_dim(x, idx[j], 1, axis=0) for j in range(idx.shape[0])]
    )


def fit(ht, x, fit_cfg: dict, rows_idx):
    """``KMeans(...).fit(x)`` from explicit initial centres ``x[rows_idx]``."""
    init = ht.array(rows_of(x.larray, jnp.asarray(rows_idx)), split=None)
    est = ht.cluster.KMeans(
        n_clusters=fit_cfg["n_clusters"], init=init,
        max_iter=fit_cfg["max_iter"], tol=fit_cfg["tol"],
    )
    return est.fit(x)
