# Continuous-benchmark sparse-compute-tier workloads (round 21): the
# tuned SpMV driven THROUGH its autotune-dispatched surfaces (DCSR @
# vector, the sparse Spectral embedding, the k-NN-graph serving
# endpoint), with the tuning plane enabled so each row records the
# measured arm choice — and with the memtrack ledger on so each row
# carries the sparse-vs-dense HBM-bytes delta the DCSR layout actually
# bought (the acceptance bar is >=3x residency vs the 4*n^2-byte dense
# affinity at <=5% density; bytes are exact ledger sums, not modeled).
#
# The rows are measured from a COLD tuning table — the timed region
# includes the explore phase running both arms — and the note says
# which arm the table resolved to.
# The residency and zero-densification columns are the headline; the
# wall rides the arm choice, hence the wide cited tolerance
# (history.py).
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import scipy.sparse

import heat_tpu as ht
from heat_tpu.core import autotune, memtrack, telemetry
from heat_tpu.sparse.matmul import SPMV_ARMS
from heat_tpu.utils.monitor import record

import config


def _spmv_arm_note():
    """(arm, suffix) from the tuning table after a workload ran: the
    resolved winner of a ("dense","gather") entry, or the honest static
    default when tuning never saw the site."""
    rows = [
        r for r in autotune.report()["rows"]
        if set(r.get("arms", ())) == set(SPMV_ARMS)
    ]
    if not rows:
        return (
            "gather",
            " spmv arms never explored (tuning off or prior-resolved): "
            "the static gather path served every call",
        )
    winners = [r["winner"] or "exploring" for r in rows]
    return winners[0], f" measured arm choice: {winners[0]}"


class _Tuned:
    """Scoped tuning plane for one workload: API-enabled, table cleared
    on entry so the row always measures a cold explore-then-stick."""

    def __enter__(self):
        self.prev = autotune.set_enabled(True)
        autotune.reset()
        return self

    def __exit__(self, *exc):
        autotune.set_enabled(self.prev)
        autotune.reset()
        return False


def _residency_fields(dense_nbytes, sparse_nbytes):
    """The sparse-vs-dense HBM columns: the exact ledger bytes the DCSR
    buffers hold resident against the 4*n^2 a dense affinity would."""
    return {
        "dense_hbm_bytes": int(dense_nbytes),
        "sparse_hbm_bytes": int(sparse_nbytes),
        "hbm_bytes_saved": int(dense_nbytes) - int(sparse_nbytes),
        "residency_ratio": round(dense_nbytes / max(sparse_nbytes, 1), 2),
    }


def _spmv_csr(rng):
    n, density = config.SPMV_N, config.SPMV_DENSITY
    sp = scipy.sparse.random(
        n, n, density=density, random_state=rng, format="csr",
        dtype=np.float32,
    )
    with telemetry.telemetry_level("events"):
        memtrack.reset()
        A = ht.sparse.sparse_csr_matrix(sp, split=0)
        # everything registered since the reset IS the DCSR: the three
        # device buffers (values f32 + indices/indptr int32)
        sparse_nbytes = sum(memtrack.summary()["bytes_by_dtype"].values())
        memtrack.reset()
    x = ht.array(rng.standard_normal(n).astype(np.float32))
    xm = ht.array(
        rng.standard_normal((n, config.SPMV_RHS_K)).astype(np.float32)
    )
    with _Tuned(), telemetry.telemetry_level("events"):
        telemetry.clear_events()

        def run_mv(reps):
            y = None
            for _ in range(reps):
                y = ht.sparse.matmul(A, x)
            config.drain(y.larray)

        run_mv(1)  # warmup: compile every arm's program
        sl = config.slope(run_mv)
        ym = ht.sparse.matmul(A, xm)  # multi-rhs rides the same winner
        config.drain(ym.larray)
        arm, note_arm = _spmv_arm_note()
        densifies = len(telemetry.events(kind="sparse_densify"))
    record(
        "spmv_csr", sl.per_unit_s, per="matvec",
        n=n, nnz=int(A.nnz), density=round(A.nnz / (n * n), 5),
        rhs_k=config.SPMV_RHS_K, arm=arm, densifies=densifies,
        **sl.fields(),
        **_residency_fields(4 * n * n, sparse_nbytes),
        **config.hbm_fields(8 * A.nnz + 4 * n + 4 * n, sl.per_unit_s),
        note="row-split DCSR @ replicated vector through the tuned "
             "dispatch — dense (todense+matmul, the authoritative "
             "reference) vs gather (jitted segment-sum) vs kernel "
             "(lane-aware Pallas ELL).  The residency columns are the "
             "headline (exact ledger bytes of the three DCSR buffers "
             "vs the 4*n^2 dense affinity); the wall includes the cold "
             "explore running every arm, and explore rounds densify by "
             "design (the dense arm IS the reference), so `densifies` "
             "counts explore-phase work, not steady-state leaks."
             + note_arm,
    )


def _spectral_sparse(rng):
    n, f = config.KNNG_N, config.KNNG_F
    X = np.concatenate([
        rng.normal(0.0, 0.3, size=(n // 2, f)),
        rng.normal(3.0, 0.3, size=(n - n // 2, f)),
    ]).astype(np.float32)
    x = ht.array(X, split=0)
    with _Tuned(), telemetry.telemetry_level("events"):
        memtrack.reset()
        telemetry.clear_events()
        model = ht.cluster.Spectral(
            n_clusters=2, gamma=1.0, affinity="knn",
            n_neighbors=config.KNNG_K, n_lanczos=config.KNNG_LANCZOS,
        )
        t0 = time.perf_counter()
        model.fit(x)
        wall = time.perf_counter() - t0
        densifies = len(telemetry.events(kind="sparse_densify"))
        graph_events = telemetry.events(kind="knn_graph")
        # ledger upper bound on the sparse pipeline's residency: graph +
        # Laplacian DCSR slabs, the embedding and the KMeans state — all
        # of it together still dwarfed by the dense (n, n) affinity
        sparse_nbytes = sum(memtrack.summary()["bytes_by_dtype"].values())
        arm, note_arm = _spmv_arm_note()
    assert densifies == 0, (
        f"sparse Spectral densified {densifies}x — the whole point of "
        "the sparse tier is that the dense (n, n) affinity never exists"
    )
    ge = graph_events[0] if graph_events else {}
    record(
        "spectral_sparse", wall, per="fit",
        n=n, features=f, k=config.KNNG_K, m=config.KNNG_LANCZOS,
        nnz=int(ge.get("nnz", 0)), density=round(ge.get("density", 0.0), 5),
        arm=arm, densifies=densifies,
        **_residency_fields(4 * n * n, sparse_nbytes),
        note="whole Spectral.fit: knn_graph (row-tiled on-device top-k) "
             "-> norm_sym Laplacian (pure value transform, same "
             "sparsity) -> Lanczos over matvec_program (resolved "
             "gather/kernel winner, never dense) -> KMeans on the "
             "embedding.  densifies==0 is ASSERTED — the dense "
             "affinity never existed.  Single-run whole-fit wall like "
             "the kmeans rows (host readbacks in the estimator), hence "
             "the wide cited tolerance." + note_arm,
    )


def _serving_knn_graph(rng):
    from heat_tpu import serving

    n, f = 64, config.KNNG_F
    X = np.concatenate([
        rng.normal(0.0, 0.3, size=(n // 2, f)),
        rng.normal(3.0, 0.3, size=(n - n // 2, f)),
    ]).astype(np.float32)
    spec = ht.cluster.Spectral(
        n_clusters=2, gamma=1.0, affinity="knn", n_neighbors=6,
        n_lanczos=12,
    )
    spec.fit(ht.array(X, split=0))

    sizes = rng.integers(1, 33, size=config.KNNG_REQS)
    payloads = [
        rng.normal(1.5, 1.5, size=(int(s), f)).astype(np.float32)
        for s in sizes
    ]
    telemetry.reset_group("serving")
    with telemetry.telemetry_level("events"):
        eng = serving.ServingEngine()
        try:
            eng.register(
                "knn_embed", spec, feature_dim=f, min_bucket=8,
                max_batch=32, max_delay_s=0.002, warm=True,
            )
            for p in payloads[:3]:  # touch every bucket before timing
                eng.predict("knn_embed", p, timeout=120)
            telemetry.clear_events()
            fusion_before = telemetry.snapshot_group("fusion").get("misses", 0)
            steps_before = eng.stats()["step_compiles"]
            t0 = time.perf_counter()
            with ThreadPoolExecutor(max_workers=8) as pool:
                futures = list(
                    pool.map(lambda p: eng.submit("knn_embed", p), payloads)
                )
                for fut in futures:
                    fut.result(120)
            wall = time.perf_counter() - t0
            step_delta = eng.stats()["step_compiles"] - steps_before
            fusion_delta = (
                telemetry.snapshot_group("fusion").get("misses", 0)
                - fusion_before
            )
            densifies = len(telemetry.events(kind="sparse_densify"))
            graph_calls = len(telemetry.events(kind="knn_graph"))
            stats = eng.stats()
            latency = stats["latency"]["knn_embed"]
            batches = stats["batches"]
        finally:
            eng.close()
    assert step_delta == 0 and fusion_delta == 0 and densifies == 0, (
        f"no-retrace law broken under sparse serving traffic: "
        f"step_compiles+{step_delta}, fusion misses+{fusion_delta}, "
        f"densifies+{densifies}"
    )
    record(
        "serving_knn_graph", wall, per=f"{len(payloads)}-requests",
        requests=len(payloads), corpus_rows=n, feature_dim=f,
        step_compiles_delta=step_delta, fusion_misses_delta=fusion_delta,
        densifies=densifies, graph_calls=graph_calls, batches=batches,
        p50_ms=round(latency["p50_s"] * 1e3, 3),
        p99_ms=round(latency["p99_s"] * 1e3, 3),
        note="fitted sparse Spectral behind the bucketed front door: "
             "each batch runs graph -> sparse Laplacian -> Lanczos "
             "embedding, knn_graph's pow2 slab caps (bucket_cap=True) "
             "keep same-bucket requests on ONE compiled program — "
             "zero step compiles, zero fusion misses, zero "
             "densifications are ASSERTED, not observed.  Single-run "
             "batched wall over a thread pool like serving_batch, "
             "hence the wide cited tolerance.",
    )


def run():
    rng = np.random.default_rng(21)
    _spmv_csr(rng)
    _spectral_sparse(rng)
    _serving_knn_graph(rng)


if __name__ == "__main__":
    run()
