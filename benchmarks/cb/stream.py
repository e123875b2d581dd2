# Continuous-benchmark out-of-core streaming workloads (round 22): the
# double-buffered host->device prefetch engine (core/stream.py) driven
# through its real consumers — a KMeans fit on a FILE-BACKED corpus 4x
# the residency budget, and a streamed k-NN corpus behind the bucketed
# serving front door — with the tuning plane enabled so each row records
# the measured slab arm, and the memtrack ledger on so each row carries
# the PEAK staging bytes against the budget it promised to respect (the
# acceptance bar: peak <= budget while the centroids match the in-memory
# fit at the documented tolerance).
#
# Honesty contract: on the CPU CI mesh the "device" is host RAM, so the
# prefetch thread and the consumer contend for the same cores and the
# measured overlap fraction is scheduler-dependent — the walls carry a
# wide cited tolerance (history.py) and the headline is the asserted
# budget/parity/no-retrace laws, not the seconds.
import os
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import heat_tpu as ht
from heat_tpu.core import autotune, memtrack, telemetry
from heat_tpu.core.stream import STREAM_ARMS
from heat_tpu.utils.monitor import record

import config


def _stream_arm_note():
    """(arm, suffix) from the tuning table after a workload ran: the
    resolved winner of a slab-fraction entry, or the honest static
    default when tuning never resolved the site."""
    rows = [
        r for r in autotune.report()["rows"]
        if set(r.get("arms", ())) == set(STREAM_ARMS)
    ]
    if not rows:
        return (
            "slab_full",
            " stream arms never explored (tuning off or prior-resolved): "
            "the full budget-derived slab served every pass",
        )
    winners = [r["winner"] or "exploring" for r in rows]
    return winners[0], f" measured slab arm: {winners[0]}"


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


def _blobs(rng, n, f, k):
    centers = rng.normal(0.0, 5.0, size=(k, f))
    x = centers[rng.integers(0, k, size=n)] + rng.normal(
        0.0, 0.3, size=(n, f)
    )
    return x.astype(np.float32)


def _stream_kmeans(rng, tmp):
    n, f, k = config.STREAM_N, config.STREAM_F, config.STREAM_K
    x_np = _blobs(rng, n, f, k)
    path = os.path.join(tmp, "stream_corpus.npy")
    np.save(path, x_np)
    budget = x_np.nbytes // 4  # the corpus is exactly 4x the budget
    init = ht.array(x_np[:k].copy(), split=None)
    km_mem = ht.cluster.KMeans(
        n_clusters=k, init=init, max_iter=config.STREAM_ITERS, tol=1e-6
    )
    km_mem.fit(ht.array(x_np, split=0))
    km = ht.cluster.KMeans(
        n_clusters=k, init=init, max_iter=config.STREAM_ITERS, tol=1e-6
    )
    with _Tuned(), telemetry.telemetry_level("events"):
        memtrack.reset()
        telemetry.clear_events()
        t0 = time.perf_counter()
        km.fit_stream(path, budget=budget)
        wall = time.perf_counter() - t0
        rep = km.last_stream_report
        peak = (memtrack.summary()["peak_bytes_by_tag"] or {}).get(
            "staging", 0
        )
        arm, note_arm = _stream_arm_note()
        memtrack.reset()
    # THE acceptance bars, asserted inside the workload: the ledgered
    # peak staging residency respects the budget the pass planned
    # under, and the streamed centroids match the in-memory fit at the
    # documented tolerance (identical f32 math, only the slab-wise
    # accumulation order differs)
    assert 0 < peak <= budget, (
        f"peak staging bytes {peak} escaped the {budget}-byte budget"
    )
    c_mem = np.asarray(km_mem.cluster_centers_.larray)
    c_str = np.asarray(km.cluster_centers_.larray)
    np.testing.assert_allclose(c_str, c_mem, rtol=1e-4, atol=1e-4)
    centroid_delta = float(np.max(np.abs(c_str - c_mem)))
    record(
        "stream_kmeans", wall, per="fit",
        n=n, features=f, k=k, passes=km._n_iter,
        corpus_mb=round(x_np.nbytes / 2**20, 2),
        budget_mb=round(budget / 2**20, 2),
        peak_staging_mb=round(peak / 2**20, 2),
        peak_vs_budget=round(peak / budget, 4),
        slabs=rep["slabs"], slab_rows=rep["slab_rows"],
        bytes_read=rep["bytes_read"],
        overlap_frac=round(rep["overlap_frac"], 4),
        oom_retries=rep["oom_retries"],
        centroid_max_delta=centroid_delta, arm=arm,
        note="exact multi-pass Lloyd over a .npy corpus 4x the "
             "residency budget: each pass re-streams the file through "
             "the double-buffered prefetch engine, per-slab jitted "
             "stats accumulate on device, centers update on host.  "
             "peak<=budget and centroid parity (rtol 1e-4) are "
             "ASSERTED, not observed; overlap_frac is the measured "
             "fraction of host I/O hidden behind device compute.  "
             "Single-run whole-fit wall (per-pass host readbacks), "
             "hence the wide cited tolerance." + note_arm,
    )


def _stream_knn_serving(rng, tmp):
    from heat_tpu import serving

    n, f = config.STREAM_KNN_N, config.STREAM_KNN_F
    x_np = _blobs(rng, n, f, 2)
    y_np = (x_np[:, 0] > x_np[:, 0].mean()).astype(np.int32)
    path = os.path.join(tmp, "stream_knn_corpus.npy")
    np.save(path, x_np)
    budget = x_np.nbytes // 4

    sizes = rng.integers(1, 33, size=config.STREAM_REQS)
    payloads = [
        rng.normal(0.0, 3.0, size=(int(s), f)).astype(np.float32)
        for s in sizes
    ]
    telemetry.reset_group("serving")
    with _Tuned(), telemetry.telemetry_level("events"):
        model = ht.classification.KNeighborsClassifier(n_neighbors=5)
        model.fit_stream(path, y_np, budget=budget)
        eng = serving.ServingEngine()
        try:
            eng.register(
                "knn_stream", model, feature_dim=f, min_bucket=8,
                max_batch=32, max_delay_s=0.002, warm=True,
            )
            for p in payloads[:3]:  # touch every bucket before timing
                eng.predict("knn_stream", p, timeout=120)
            telemetry.clear_events()
            fusion_before = telemetry.snapshot_group("fusion").get(
                "misses", 0
            )
            steps_before = eng.stats()["step_compiles"]
            t0 = time.perf_counter()
            with ThreadPoolExecutor(max_workers=8) as pool:
                futures = list(
                    pool.map(
                        lambda p: eng.submit("knn_stream", p), payloads
                    )
                )
                for fut in futures:
                    fut.result(120)
            wall = time.perf_counter() - t0
            step_delta = eng.stats()["step_compiles"] - steps_before
            fusion_delta = (
                telemetry.snapshot_group("fusion").get("misses", 0)
                - fusion_before
            )
            stream_events = telemetry.events(kind="serving_stream")
            rep = model.last_stream_report
            arm, note_arm = _stream_arm_note()
            stats = eng.stats()
            latency = stats["latency"]["knn_stream"]
            batches = stats["batches"]
        finally:
            eng.close()
            model.close_stream()
    assert step_delta == 0 and fusion_delta == 0, (
        f"no-retrace law broken under streamed serving traffic: "
        f"step_compiles+{step_delta}, fusion misses+{fusion_delta}"
    )
    assert stream_events, "serving_stream events never surfaced"
    overlaps = [e["overlap_frac"] for e in stream_events]
    record(
        "stream_knn_serving", wall, per=f"{len(payloads)}-requests",
        requests=len(payloads), corpus_rows=n, feature_dim=f,
        corpus_mb=round(x_np.nbytes / 2**20, 2),
        budget_mb=round(budget / 2**20, 2),
        slabs_per_pass=rep["slabs"], slab_rows=rep["slab_rows"],
        overlap_frac=round(float(np.mean(overlaps)), 4),
        step_compiles_delta=step_delta,
        fusion_misses_delta=fusion_delta,
        stream_passes=len(stream_events), batches=batches,
        p50_ms=round(latency["p50_s"] * 1e3, 3),
        p99_ms=round(latency["p99_s"] * 1e3, 3),
        arm=arm,
        note="streamed k-NN behind the bucketed front door: the corpus "
             "HANDLE is fitted (4x the residency budget), every batch "
             "re-streams it past the device-resident queries through "
             "the running top-k merge, and the plan is cached on the "
             "model so same-bucket requests share ONE compiled merge "
             "program — zero step compiles and zero fusion misses are "
             "ASSERTED.  overlap_frac is the per-pass mean from the "
             "serving_stream events.  Single-run batched wall over a "
             "thread pool like serving_batch, hence the wide cited "
             "tolerance." + note_arm,
    )


def run():
    rng = np.random.default_rng(22)
    with tempfile.TemporaryDirectory(prefix="heat_cb_stream_") as tmp:
        _stream_kmeans(rng, tmp)
        _stream_knn_serving(rng, tmp)


if __name__ == "__main__":
    run()
