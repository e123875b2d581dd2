# Continuous-benchmark linalg workloads (reference: benchmarks/cb/linalg.py:
# matmul n=3000 split 0/1, qr n=2000 tiles 1-2 split 0/1, lanczos n=50 f64).
#
# Every rate is a chain-delta slope (config.slope): the workload runs as a
# dependent chain of k identical units ending in one drain readback, timed
# at two chain lengths, so the fixed readback cost cancels.  Each
# recorded wall_s is seconds PER UNIT (one matmul, one qr, ...).

import heat_tpu as ht
from heat_tpu.utils.monitor import record

import config


def _mm_chain(a, b):
    # dependent chain: each link's output feeds the next, so the final
    # readback forces every link; values may overflow — timing only
    def run_k(k):
        c = a
        for _ in range(k):
            c = c @ b
        config.drain(c.larray)
    return run_k


def _tsqr_kernel_chain(arr, mixed=False):
    # the CholeskyQR2 KERNEL (linalg/qr.py:_cholesky_qr2): the public
    # qr() adds one deliberate host sync per call (breakdown check),
    # which no chain can cancel — so the throughput number times the
    # kernel, and tsqr_user_call records the synchronous surface cost
    # separately (tsqr_user_call_defer times the check="defer" surface,
    # which IS chainable)
    from heat_tpu.core.linalg.qr import _cholesky_qr2

    def run_k(k):
        c = arr
        for _ in range(k):
            c, _ = _cholesky_qr2(c, calc_q=True, mixed=mixed)
        config.drain(c)
    return run_k


def _qr_defer_chain(a):
    # the public surface with check="defer": fully async, so the chain
    # delta applies — each link re-factors the previous link's Q.  Also
    # used for the square qr_split_* rows (round 5: the blocked path's
    # eager breakdown check would sync every link; the eager surface's
    # one-RTT cost is recorded by tsqr_user_call)
    def run_k(k):
        c = a
        for _ in range(k):
            c = ht.linalg.qr(c, check="defer").Q
        config.drain(c.larray)
    return run_k


def _lanczos_chain(B, m):
    def run_k(k):
        out = None
        for _ in range(k):
            V, _T = ht.lanczos(B, m=m)
            out = V
        config.drain(out.larray)
    return run_k


def run():
    n = config.MATMUL_N
    for sp in (0, 1):
        a = ht.random.random((n, n), split=sp)
        b = ht.random.random((n, n), split=sp)
        run_k = _mm_chain(a, b)
        run_k(1)  # warmup: compile (incl. the drain readback)
        sl = config.slope(run_k)
        record(
            f"matmul_split_{sp}", sl.per_unit_s, per="matmul",
            **sl.fields(),
            **config.mfu_fields(
                config.matmul_flops(n), sl.per_unit_s,
                config.PEAK_BF16_TFLOPS, "bf16 (default matmul precision)",
            ),
        )
        del a, b

    qn = config.QR_N
    for sp in (0, 1):
        a = ht.random.random((qn, qn), split=sp)
        run_k = _qr_defer_chain(a)
        run_k(1)
        sl = config.slope(run_k)
        record(
            f"qr_split_{sp}", sl.per_unit_s, per="qr",
            **sl.fields(),
            **config.mfu_fields(
                config.qr_flops(qn, qn), sl.per_unit_s,
                config.PEAK_F32_TFLOPS, "f32 = bf16/4",
            ),
            check="defer",
            note="reference-CI shape (square n=2048), blocked BCGS2 over "
                 "CholeskyQR2 panels (round 5: 5.9x over the Householder "
                 "fallback this row used through r4); still below the bar "
                 "because the shape's panel chain is latency/bandwidth-"
                 "bound — the compute-bound QR score is the tsqr_wide* rows",
        )
        del a

    tm, tn = config.TSQR_M, config.TSQR_N
    ts_flops = config.qr_flops(tm, tn)
    ts = ht.random.random((tm, tn), split=0)
    run_k = _tsqr_kernel_chain(ts.larray)
    run_k(1)
    sl = config.slope(run_k)
    record(
        "tsqr_tall_skinny", sl.per_unit_s, per="cholesky_qr2",
        surface="kernel", **sl.fields(),
        **config.mfu_fields(
            ts_flops, sl.per_unit_s, config.PEAK_F32_TFLOPS, "f32 = bf16/4"
        ),
    )
    # precision="mixed": pass-1 GEMMs in bf16/f32-accum (qr.py), the
    # variant that clears the BASELINE 40%-MFU bar on the f32-peak model
    run_k = _tsqr_kernel_chain(ts.larray, mixed=True)
    run_k(1)
    sl = config.slope(run_k)
    record(
        "tsqr_tall_skinny_mixed", sl.per_unit_s, per="cholesky_qr2",
        surface="kernel", precision="mixed", **sl.fields(),
        **config.mfu_fields(
            ts_flops, sl.per_unit_s, config.PEAK_F32_TFLOPS, "f32 = bf16/4"
        ),
    )
    # the public surface, eager check: one call, including its deliberate
    # breakdown-check sync
    import time as _time

    config.drain(ht.linalg.qr(ts).R.larray)  # warmup
    t0 = _time.perf_counter()
    config.drain(ht.linalg.qr(ts).R.larray)
    record(
        "tsqr_user_call", _time.perf_counter() - t0, per="qr-call",
        method="single-run",
        note="includes one host sync (qr.py breakdown check)",
    )
    # the public surface, check="defer": no sync, chain-delta applies
    run_k = _qr_defer_chain(ts)
    run_k(1)
    sl = config.slope(run_k)
    record(
        "tsqr_user_call_defer", sl.per_unit_s, per="qr-call",
        check="defer", **sl.fields(),
        **config.mfu_fields(
            ts_flops, sl.per_unit_s, config.PEAK_F32_TFLOPS, "f32 = bf16/4"
        ),
    )
    del ts

    # the BASELINE MFU-bar shape (1e6x1e3-class, compute-bound): f32 and
    # mixed kernels, MFU scored against the f32 peak model.  The n=128
    # rows above are HBM-bound (~22% MFU is their arithmetic-intensity
    # ceiling); this shape is where the 40% bar is meaningful.
    wm, wn = config.TSQR_WIDE_M, config.TSQR_WIDE_N
    w_flops = config.qr_flops(wm, wn)
    wide = ht.random.random((wm, wn), split=0)
    for mixed, row in ((False, "tsqr_wide"), (True, "tsqr_wide_mixed")):
        run_k = _tsqr_kernel_chain(wide.larray, mixed=mixed)
        run_k(1)
        sl = config.slope(run_k)
        record(
            row, sl.per_unit_s, per="cholesky_qr2",
            surface="kernel", shape=[wm, wn],
            **({"precision": "mixed"} if mixed else {}), **sl.fields(),
            **config.mfu_fields(
                w_flops, sl.per_unit_s, config.PEAK_F32_TFLOPS, "f32 = bf16/4"
            ),
        )
    del wide

    # overlap-scheduled collective matmul (parallel/overlap.py): the same
    # sharded GEMM under both schedules, reported as a ring/gspmd ratio.
    # Honesty note: on the CPU test mesh there is no ICI to overlap — the
    # "transfer" is a memcpy sharing the cores the dots run on, so the ring's
    # unrolled S-step program mostly measures dispatch overhead and ratios
    # ≳1 are EXPECTED off-TPU; the row exists to (a) pin the dispatch and
    # cache machinery under the benchmark harness and (b) read meaningfully
    # on a real v5e mesh, where bytes/step rides the ring links.
    from heat_tpu.parallel import overlap

    mn = config.MATMUL_N

    def _overlap_chain(a, b, out_split):
        def run_k(k):
            c = a
            for _ in range(k):
                ring = overlap.matmul(c, b, out_split=out_split)
                # gspmd mode declines → einsum path + resplit to the same
                # landing split (the second pass the ring schedule fuses away)
                c = ring if ring is not None else ht.resplit(c @ b, out_split)
            config.drain(c.larray)
        return run_k

    for row, sp_a, out_sp in (("matmul_overlap_ag", 0, 0), ("matmul_overlap_rs", 1, 1)):
        a = ht.random.random((mn, mn), split=sp_a)
        b = ht.random.random((mn, mn), split=0)
        per = {}
        for mode in ("ring", "gspmd"):
            overlap.set_mode(mode)
            try:
                run_k = _overlap_chain(a, b, out_sp)
                run_k(1)  # warmup: compile both legs
                per[mode] = config.slope(run_k).per_unit_s
            finally:
                overlap.set_mode(None)
        record(
            row, per["ring"], per="matmul",
            schedule="ring", gspmd_s=per["gspmd"],
            ring_over_gspmd=per["ring"] / per["gspmd"],
            **config.mfu_fields(
                config.matmul_flops(mn), per["ring"],
                config.PEAK_BF16_TFLOPS, "bf16 (default matmul precision)",
            ),
            note="low roofline off-TPU: no ICI to overlap on a host mesh, so "
                 "the unrolled ring pays S dispatches against a memcpy "
                 "'transfer' — the ratio is only meaningful on real TPU "
                 "links; rs lands the requested out-split with no resplit "
                 "second pass",
        )
        del a, b

    ln = 50
    A = ht.random.random((ln, ln), dtype=ht.float64, split=0)
    B = A @ A.T
    run_k = _lanczos_chain(B, ln)
    run_k(1)
    sl = config.slope(run_k)
    record(
        "lanczos", sl.per_unit_s, per="lanczos-m50",
        **sl.fields(),
        note="reference-CI shape (n=50 f64, m=50 sequential steps): "
             "dispatch/latency-bound by construction — ~2.6 MFLOP of "
             "dependent matvecs; no MFU model applies",
    )


if __name__ == "__main__":
    run()
