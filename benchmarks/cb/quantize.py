# Continuous-benchmark quantized-epilogue workloads (round 16): the
# int8 weight path driven THROUGH its autotune-dispatched surfaces
# (matmul_quantized, moe_ffn, the serving k-NN endpoint), with the
# tuning plane enabled so each row records the measured arm choice —
# and with the memtrack ledger on so each row carries the HBM-bytes
# delta the quantization actually bought (the acceptance bar is >=3x
# weight residency vs the f32 master; bytes are exact, not modeled).
#
# Honesty contract: on the CPU CI mesh the int8 arm usually does NOT
# win on wall (no int8 MXU path; the dequant epilogue is extra work),
# so the rows are measured from a COLD tuning table — the timed region
# includes the explore phase running BOTH arms — and the note says
# which arm the table resolved to.  The residency columns are the
# headline; the wall rides the arm choice, hence the wide cited
# tolerance (history.py).
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import heat_tpu as ht
from heat_tpu.core import autotune, memtrack, quantize, telemetry
from heat_tpu.utils.monitor import record

import config


def _quant_arm_note():
    """(arm, suffix) from the tuning table after a workload ran: the
    resolved winner of a ("bf16","int8") entry, or the honest decline."""
    rows = [
        r for r in autotune.report()["rows"]
        if tuple(r.get("arms", ())) == quantize.QUANT_ARMS
    ]
    if not rows:
        return (
            "bf16",
            " quant arm declined (tuning off or traced inputs): the "
            "dequantized reference path served every call",
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


def _residency_fields(master_nbytes, qw_nbytes, by_dtype):
    """The HBM-bytes delta columns: exact buffer sizes from the ledger,
    not a model."""
    return {
        "master_hbm_bytes": int(master_nbytes),
        "quant_hbm_bytes": int(qw_nbytes),
        "hbm_bytes_saved": int(master_nbytes) - int(qw_nbytes),
        "residency_ratio": round(master_nbytes / max(qw_nbytes, 1), 2),
        "ledger_int8_bytes": int(by_dtype.get("int8", 0)),
    }


def _linear_int8(rng):
    m, k, n = config.QLINEAR_M, config.QLINEAR_K, config.QLINEAR_N
    x = ht.array(rng.standard_normal((m, k)).astype(np.float32), split=0)
    w = ht.array(rng.standard_normal((n, k)).astype(np.float32), split=0)
    master_nbytes = int(w.parray.nbytes)  # ht: HT002 ok — .nbytes is shape metadata, no device readback
    with telemetry.telemetry_level("events"):
        memtrack.reset()
        qw = quantize.quantize_weights(w, "int8", axis=0)
        by_dtype = memtrack.summary()["bytes_by_dtype"]
        memtrack.reset()
    qwt = qw.T
    with _Tuned():

        def run_mm(reps):
            out = None
            for _ in range(reps):
                out = quantize.matmul_quantized(x, qwt)
            config.drain(out.larray)

        run_mm(1)  # warmup: compile both arms' programs
        sl = config.slope(run_mm)
        arm, note_arm = _quant_arm_note()
    record(
        "linear_int8", sl.per_unit_s, per="matmul",
        m=m, k=k, n=n, arm=arm, **sl.fields(),
        **_residency_fields(master_nbytes, qw.nbytes, by_dtype),
        **config.mfu_fields(
            config.matmul_flops_mkn(m, k, n), sl.per_unit_s,
            config.PEAK_BF16_TFLOPS, "bf16",
        ),
        note="int8 weight resident in HBM (absmax per out-channel), "
             "dequant folded into the ring epilogue as runtime operands; "
             "f32 accumulation.  The residency columns are the headline "
             "(exact ledger bytes, ~4x vs the f32 master); the wall "
             "includes the cold explore running both arms."
             + note_arm,
    )


def _moe_ffn_int8(rng):
    from heat_tpu.parallel.expert import moe_ffn

    t, dm, h = config.MOE_T, config.MOE_D, config.MOE_H
    import jax.numpy as jnp

    x = jnp.asarray(rng.standard_normal((t, dm)), jnp.float32)
    gate = jnp.asarray(rng.standard_normal((dm, 8)), jnp.float32)
    w_in = jnp.asarray(rng.standard_normal((8, dm, h)) / 32, jnp.float32)
    w_out = jnp.asarray(rng.standard_normal((8, h, dm)) / 32, jnp.float32)
    master_nbytes = int(w_in.nbytes) + int(w_out.nbytes)  # ht: HT002 ok — .nbytes is shape metadata, no device readback
    with telemetry.telemetry_level("events"):
        memtrack.reset()
        q_in = quantize.quantize_tensor(w_in, "int8", axis=(0, 2))
        q_out = quantize.quantize_tensor(w_out, "int8", axis=(0, 2))
        by_dtype = memtrack.summary()["bytes_by_dtype"]
        memtrack.reset()
    quant_nbytes = q_in.nbytes + q_out.nbytes
    with _Tuned():

        def run_moe(reps):
            y = None
            for _ in range(reps):
                y, _aux = moe_ffn(x, gate, q_in, q_out, k=2)
            config.drain(y)

        run_moe(1)
        sl = config.slope(run_moe)
        arm, note_arm = _quant_arm_note()
    record(
        "moe_ffn_int8", sl.per_unit_s, per="moe-pass",
        tokens=t, d_model=dm, d_ff=h, k=2, arm=arm, **sl.fields(),
        **_residency_fields(master_nbytes, quant_nbytes, by_dtype),
        **config.mfu_fields(
            config.moe_flops(t, dm, h, k=2), sl.per_unit_s,
            config.PEAK_BF16_TFLOPS, "bf16",
        ),
        note="per-(expert, channel) int8 expert weights through the "
             "routed FFN; scales enter the shard program as runtime "
             "operands (a re-quantized checkpoint never retraces).  The "
             "bf16 arm dequantizes and runs the master path — bitwise "
             "the unquantized flow — so explore's reference result is "
             "exact." + note_arm,
    )


def _serving_knn(rng):
    from heat_tpu import serving

    n, f = config.QKNN_N, config.QKNN_F
    X = rng.standard_normal((n, f)).astype(np.float32)
    labels = (X[:, 0] > 0).astype(np.int32)
    knn = ht.classification.KNeighborsClassifier(n_neighbors=5)
    knn.fit(ht.array(X, split=0), ht.array(labels, split=0))
    master_nbytes = int(knn.x.parray.nbytes)  # ht: HT002 ok — .nbytes is shape metadata, no device readback

    requests = [
        rng.standard_normal((int(r), f)).astype(np.float32)
        for r in rng.integers(1, 9, size=config.QKNN_REQS)
    ]
    telemetry.reset_group("serving")
    eng = serving.ServingEngine()
    try:
        eng.register(
            "knn", knn, feature_dim=f, min_bucket=8, max_batch=32,
            max_delay_s=0.002, warm=True, quantize=True,
        )
        quant_nbytes = knn._qx.nbytes
        t0 = time.perf_counter()
        with ThreadPoolExecutor(max_workers=8) as pool:
            futures = list(
                pool.map(lambda r: eng.submit("knn", r), requests)
            )
            for fut in futures:
                fut.result(60)
        wall = time.perf_counter() - t0
        stats = eng.stats()
        latency = stats["latency"]["knn"]
        batches = stats["batches"]
    finally:
        eng.close()
    # the k-NN path is not arm-dispatched (the quantized ring cdist is a
    # direct shard program): probe which path a bucket-shaped query takes
    # and record that as the row's measured choice
    from heat_tpu.spatial import distance

    probe = distance.cdist_quantized(
        ht.array(np.zeros((8, f), np.float32), split=0), knn._qx
    )
    if probe is not None:
        arm = "ring_int8"
        note_arm = (
            " measured path: quantized ring cdist (int8 corpus blocks on "
            "the wire, per-step dequant at the unit)"
        )
    else:
        arm = "dequant_fallback"
        note_arm = (
            " measured path: dequantize-per-call fallback (ring-ineligible "
            "layout, e.g. a 1-device mesh)"
        )
    record(
        "serving_knn", wall, per=f"{len(requests)}-requests",
        requests=len(requests), corpus_rows=n, feature_dim=f, arm=arm,
        master_hbm_bytes=master_nbytes, quant_hbm_bytes=int(quant_nbytes),
        hbm_bytes_saved=master_nbytes - int(quant_nbytes),
        residency_ratio=round(master_nbytes / max(int(quant_nbytes), 1), 2),
        batches=batches,
        p50_ms=round(latency["p50_s"] * 1e3, 3),
        p99_ms=round(latency["p99_s"] * 1e3, 3),
        note="batched k-NN endpoint over an int8 corpus "
             "(register(quantize=True) released the f32 master at "
             "registration — the residency columns are exact buffer "
             "bytes).  Single-run batched wall over a thread pool like "
             "serving_batch, hence the wide cited tolerance." + note_arm,
    )


def run():
    rng = np.random.default_rng(16)
    _linear_int8(rng)
    _moe_ffn_int8(rng)
    _serving_knn(rng)


if __name__ == "__main__":
    run()
