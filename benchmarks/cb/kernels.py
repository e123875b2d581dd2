# Continuous-benchmark kernel-tier workloads (round 15): the two Pallas
# kernels for the memory-bound tail that Mosaic compiles — fused
# CholeskyQR2 panel, fused lasso sweep — each driven THROUGH its
# autotune-dispatched surface (never called directly), with the tuning
# plane enabled so the row records the measured arm choice.
#
# Off TPU the kernels decline (interpret mode is a correctness tool), so
# rehearsal rows dispatch the classic arm and say so in the `arm` field.
# On TPU the same code registers the kernel arm, explores both
# lowerings, and the row carries whichever dispatch measurement won.
import numpy as np

import heat_tpu as ht
from heat_tpu.core import autotune
from heat_tpu.ops._pallas_common import KERNEL_ARMS
from heat_tpu.utils.monitor import record

import config


def _kernel_arm_note():
    """(arm, suffix) from the tuning table after a workload ran: the
    resolved winner of a kernel-arm entry, or the honest decline."""
    rows = [
        r for r in autotune.report()["rows"]
        if tuple(r.get("arms", ())) == KERNEL_ARMS
    ]
    if not rows:
        return (
            "classic",
            " kernel arm declined (off-TPU backend or unsupported "
            "layout): the Pallas tier only dispatches where it can win",
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


def run():
    rng = np.random.default_rng(15)

    # ---- qr_panel_fused: CholeskyQR2 through the fused panel kernel arm
    m, n = config.QR_PANEL_M, config.QR_PANEL_N
    a = ht.array(rng.standard_normal((m, n)).astype(np.float32))
    with _Tuned():

        def run_qr(k):
            q = r = None
            for _ in range(k):
                q, r = ht.linalg.qr(a, check="defer")
            config.drain_all(q.larray, r.larray)

        run_qr(1)
        sl = config.slope(run_qr)
        arm, note_arm = _kernel_arm_note()
    record(
        "qr_panel_fused", sl.per_unit_s, per="qr",
        m=m, n=n, arm=arm, **sl.fields(),
        **config.mfu_fields(
            config.qr_flops(m, n), sl.per_unit_s,
            config.PEAK_F32_TFLOPS, "f32=bf16/4",
        ),
        note="tall-skinny panel: classic is three launches (syrk, chol, "
             "trsm) with the Gram matrix round-tripping HBM; the fused "
             "kernel keeps G in VMEM and reads the panel once."
             + note_arm,
    )

    # ---- lasso_sweep_fused: CD fit through the fused sweep kernel arm
    m, n = config.LASSO_K_M, config.LASSO_K_N
    X = rng.standard_normal((m, n)).astype(np.float32)
    X /= np.sqrt((X * X).mean(axis=0)) + 1e-12
    beta = np.zeros((n, 1), np.float32)
    beta[:: max(n // 16, 1)] = 2.0
    y = X @ beta + 0.01 * rng.standard_normal((m, 1)).astype(np.float32)
    xa, ya = ht.array(X), ht.array(y)
    with _Tuned():

        def run_fit(k):
            est = ht.regression.Lasso(lam=0.01, max_iter=k, tol=-1.0)
            est.fit(xa, ya)
            config.drain(est.coef_.larray)

        run_fit(1)
        sl = config.slope(run_fit, k1=2)
        arm, note_arm = _kernel_arm_note()
    record(
        "lasso_sweep_fused", sl.per_unit_s, per="cd-sweep",
        m=m, n=n, arm=arm, **sl.fields(),
        **config.hbm_fields(3.0 * m * n * 4.0, sl.per_unit_s),
        note="classic re-streams the residual from HBM at every one of "
             "the n coordinate updates; the fused sweep holds it in VMEM "
             "across the whole sweep and reads X exactly once."
             + note_arm,
    )


if __name__ == "__main__":
    run()
