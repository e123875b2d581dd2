# Continuous-benchmark clustering workloads (reference: benchmarks/cb/
# cluster.py: kmeans/kmedians/kmedoids on spherical synthetic clusters).
#
# Two kinds of record:
#  * whole-fit wall times for the three reference-parity estimators
#    (single-run; includes the estimator's own n_iter/inertia host
#    readbacks), and
#  * kmeans_lloyd_iter — seconds per Lloyd iteration at the headline
#    config (2e7x64 f32, k=8), measured as a
#    chain-delta slope over max_iter (tol=-1 disables the convergence
#    early-exit; max_iter is a traced argument, so no recompiles).  The
#    derived kmeans_samples_per_s comes from this, making the artifact
#    comparable with the documented per-iteration throughput.
import time

import heat_tpu as ht
from heat_tpu.utils.monitor import record

import config


def _fit(cls, init, data):
    est = cls(n_clusters=4, init=init)
    est.fit(data)
    return config.drain(est.cluster_centers_.larray)


def _timed_fit(name, cls, init, data):
    _fit(cls, init, data)  # warmup: compile the fit loop
    t0 = time.perf_counter()
    _fit(cls, init, data)
    record(
        name, time.perf_counter() - t0, per="fit",
        method="single-run",
        note="includes the estimator's n_iter/inertia readbacks",
    )


def _lloyd_slope():
    data = ht.random.randn(config.LLOYD_N, config.LLOYD_F, split=0)

    def run_k(k):
        est = ht.cluster.KMeans(
            n_clusters=config.LLOYD_K, init="random", max_iter=k,
            tol=-1.0, random_state=7,
        )
        est.fit(data)
        config.drain(est.cluster_centers_.larray)

    run_k(1)  # warmup: compile init + Lloyd loop (max_iter is traced)
    sl = config.slope(run_k, k1=2)
    record(
        "kmeans_lloyd_iter", sl.per_unit_s, per="lloyd-iteration",
        n=config.LLOYD_N, f=config.LLOYD_F, k=config.LLOYD_K,
        **sl.fields(),
        # mandatory traffic: one pass over X per iteration (centers/labels
        # are noise at f=64, k=8) — Lloyd at this shape is HBM-bound, so
        # the roofline fraction is the honest score, not MFU
        **config.hbm_fields(
            config.LLOYD_N * config.LLOYD_F * 4.0, sl.per_unit_s
        ),
    )


def _northstar_slope():
    """BASELINE.md's KMeans north-star: 1e8x64 bf16 on one chip.  The
    packed payload is generated at ingest (cluster.packing.randn_packed —
    the lane-padded form never exists) and the fit runs the blocked Lloyd
    loop; per-iteration seconds via the same max_iter chain-delta."""
    n, f, k = config.NORTHSTAR_N, config.NORTHSTAR_F, config.NORTHSTAR_K
    ps = ht.cluster.randn_packed(n, f)

    def run_k(kk):
        est = ht.cluster.KMeans(
            n_clusters=k, init="random", max_iter=kk, tol=-1.0,
            random_state=7,
        )
        est.fit(ps)
        config.drain(est.cluster_centers_.larray)

    run_k(1)  # warmup: compile
    sl = config.slope(run_k, k1=2)
    record(
        "kmeans_lloyd_iter_bf16_northstar", sl.per_unit_s,
        per="lloyd-iteration", n=n, f=f, k=k, dtype="bfloat16",
        packed=True, **sl.fields(),
        **config.hbm_fields(n * f * 2.0, sl.per_unit_s),
        note="hbm model = one bf16 pass over the payload (the floor); "
             "the measured ~2.3 passes are the verified minimum: the "
             "update GEMM needs contracted-dim-major row blocks, and the "
             "per-block transpose was probed against direct contraction "
             "(11.9 GB global relayout, round 4) and block sizes "
             "2^13..2^21 (round 5; 2^21 fastest)",
    )


def run():
    data = ht.utils.data.spherical.create_spherical_dataset(
        num_samples_cluster=config.CLUSTER_N,
        radius=1.0,
        offset=4.0,
        dtype=ht.float32,
        random_state=1,
    )
    _timed_fit("kmeans", ht.cluster.KMeans, "kmeans++", data)
    _timed_fit("kmedians", ht.cluster.KMedians, "kmedians++", data)
    _timed_fit("kmedoids", ht.cluster.KMedoids, "kmedoids++", data)
    del data
    _lloyd_slope()
    _northstar_slope()


if __name__ == "__main__":
    run()
