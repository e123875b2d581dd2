"""A run with the timed path broken underneath comes out as not correct.

Each case plants one fault in the program (in a child process, before the
run starts), skips the harness's look for a chip (``--rehearse-cpu``) and
drives the rest of a run: a step that returns its state unchanged, half of
the batch left out with the mean taken over the rest, an answer altered
where it is produced, and a call that fails inside the window while the loop
goes on.  (The exchange between chips left out comes back with the first
four-chip cell: PERF.md section 7.)
"""

import pytest

from perf.tests._util import run_cell

STATE_UNCHANGED = """
import jax.numpy as jnp
from heat_tpu.cluster import kmeans
def _stuck(x, centers, k, max_iter, tol):
    return centers, jnp.float32(0), jnp.float32(0), jnp.int32(max_iter)
kmeans._lloyd_loop = _stuck
"""

HALF_BATCH = """
from heat_tpu.cluster import kmeans
_step = kmeans._lloyd_step
kmeans._lloyd_step = lambda x, centers, k: _step(x[: x.shape[0] // 2], centers, k)
"""

LABEL_ALTERED = """
from heat_tpu.cluster import _kcluster
from heat_tpu.core.dndarray import DNDarray
_assign = _kcluster._KCluster._assign_to_cluster
def _altered(self, x, *a, **kw):
    lab = _assign(self, x, *a, **kw)
    arr = lab.larray.at[0, 0].set((lab.larray[0, 0] + 1) % self.n_clusters)
    return DNDarray(arr, lab.gshape, lab.dtype, lab.split, lab.device, lab.comm)
_kcluster._KCluster._assign_to_cluster = _altered
"""

QR_ROW_ALTERED = """
import sys
qrmod = sys.modules["heat_tpu.core.linalg.qr"]
_fact = qrmod._cholesky_qr2
def _altered(arr, **kw):
    q, r = _fact(arr, **kw)
    return q.at[0].multiply(-1.0), r
qrmod._cholesky_qr2 = _altered
"""

QR_HALF_ROWS = """
import sys
qrmod = sys.modules["heat_tpu.core.linalg.qr"]
_fact = qrmod._cholesky_qr2
def _half(arr, **kw):
    q, r = _fact(arr, **kw)
    return q.at[q.shape[0] // 2:].set(0.0), r
qrmod._cholesky_qr2 = _half
"""

CALL_FAILS_ONCE = """
import sys
qrmod = sys.modules["heat_tpu.core.linalg.qr"]
_fact, _seen = qrmod._cholesky_qr2, []
def _once(arr, **kw):
    _seen.append(1)
    if len(_seen) == 6:   # four warm-up calls, then the window's second call
        raise MemoryError("RESOURCE_EXHAUSTED: planted")
    return _fact(arr, **kw)
qrmod._cholesky_qr2 = _once
"""

CASES = {
    "kmeans_fit-state_unchanged": ("kmeans_fit", 1, STATE_UNCHANGED, "centers_err"),
    "kmeans_fit-half_batch": ("kmeans_fit", 1, HALF_BATCH, "centers_err"),
    "kmeans_fit-label_altered": ("kmeans_fit", 1, LABEL_ALTERED, "label_gap"),
    "qr_tall-row_altered": ("qr_tall", 1, QR_ROW_ALTERED, "resid"),
    "qr_tall-half_rows": ("qr_tall", 1, QR_HALF_ROWS, "orth"),
    "qr_tall-call_fails": ("qr_tall", 1, CALL_FAILS_ONCE, "failed_calls"),
}


@pytest.mark.parametrize("name,chips,patch,caught_by", list(CASES.values()), ids=list(CASES))
def test_fault_is_caught(name, chips, patch, caught_by):
    rc, result, err = run_cell(name, chips=chips, patch="import heat_tpu\n" + patch)
    assert rc == 0, err[-2000:]
    assert result["correct"] is False
    value, limit = result["check"][caught_by]
    assert not value <= limit, result["check"]
    assert "correct: False" in err.strip().splitlines()[-1]


def test_cluster_numbers_are_no_part_of_the_answer():
    """The same clustering with two clusters' numbers swapped (centres and
    labels alike) is judged as the clustering it is; labels swapped without
    their centres are wrong."""
    import jax.numpy as jnp
    import numpy as np

    from perf.drivers import Arr, _kmeans, kmeans_fit
    from perf.generators import gaussian_blobs
    from perf.reference import lloyd
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import jax

    cfg = {"rows": 8192, "features": 16, "dtype": "float32",
           "data": {"generator": "gaussian_blobs", "blobs": 8, "center_scale": 1.0}}
    mesh = Mesh(np.array(jax.devices()[:1]), ("x",))
    x = gaussian_blobs.make(cfg, 7, NamedSharding(mesh, P("x", None)))["x"]
    fit_cfg = {"n_clusters": 8, "max_iter": 10, "tol": -1.0}
    start = _kmeans.init_rows(7, 0, 8192, 8)
    centres, inertia = lloyd.fit(x, _kmeans.rows_of(x, jnp.asarray(start)), 10)
    labels = lloyd.assign(x, centres)
    swap = np.array([0, 5, 2, 3, 4, 1, 6, 7])
    kept = {"centers": Arr(centres[swap]), "labels": Arr(jnp.asarray(swap)[labels].reshape(-1, 1)),
            "inertia": float(inertia), "n_iter": 10, "start": start}
    check_cfg = {"limits": {"centers_err": 1.5e-3}, "stated_operands": "bfloat16"}
    numbers, info = kmeans_fit.judge_fit(x, kept, fit_cfg, check_cfg)
    assert numbers["centers_err"] < 1e-6 and numbers["label_gap"] < 1e-6
    assert info["clusters_renumbered"] == 2 and info["centers_err_by_number"] > 0.1
    kept["centers"] = Arr(centres)          # labels renumbered, centres not
    numbers, info = kmeans_fit.judge_fit(x, kept, fit_cfg, check_cfg)
    assert numbers["label_gap"] > 0.1 and numbers["centers_err"] < 1e-6
    # centres far from the reference's on a start that the reference itself
    # holds at the stated precision: not left out, and over the limit
    kept["centers"] = Arr(centres * 1.01)
    numbers, info = kmeans_fit.judge_fit(x, kept, fit_cfg, check_cfg)
    assert numbers["centers_err"] > 5e-3 and info["starts_left_out"] == 0
