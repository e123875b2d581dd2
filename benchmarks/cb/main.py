# Continuous-benchmark entry (reference: benchmarks/cb/main.py, run by CI as
# `mpirun -n 4 python benchmarks/cb/main.py` under perun;
# .github/workflows/benchmark_main.yml:25).  Here: one process driving the
# whole mesh; each workload prints a JSON measurement line, and
# `--out FILE` writes the whole suite (raw measurements + derived
# north-star metrics) as one JSON document for the round's record.
import argparse
import json
import sys

import cluster
import config
import fusion
import history
import kernels
import linalg
import manipulations
import nn
import quantize
import regression
import router
import serving
import sparse
import stream
import wire

from heat_tpu.core import telemetry as _telemetry
from heat_tpu.utils import compile_cache as _compile_cache
from heat_tpu.utils import monitor as _monitor


def derive(measurements):
    """North-star metrics (BASELINE.md) computed from config + per-unit
    seconds.  Every input wall_s is a chain-delta slope (the time for ONE
    matmul / attention pass / Lloyd iteration / train step, with the fixed
    readback cost cancelled)."""
    by = {m["name"]: m for m in measurements}
    out = {}
    if "matmul_split_0" in by:
        n, t = config.MATMUL_N, by["matmul_split_0"]["wall_s"]
        out["matmul_tflops"] = round(config.matmul_flops(n) / t / 1e12, 3)
    if "tsqr_tall_skinny" in by:
        m, n = config.TSQR_M, config.TSQR_N
        t = by["tsqr_tall_skinny"]["wall_s"]
        # tall-skinny QR ~ 2mn^2 flops
        out["tsqr_gflops"] = round(2 * m * n * n / t / 1e9, 3)
    if "kmeans_lloyd_iter" in by:
        # per-Lloyd-iteration throughput at the headline 2e7x64 config
        # (round 2 divided a toy whole-fit wall into its sample count and
        # landed 3500x under)
        t = by["kmeans_lloyd_iter"]["wall_s"]
        out["kmeans_samples_per_s"] = round(config.LLOYD_N / t, 1)
    if "kmeans_lloyd_iter_bf16_northstar" in by:
        # the BASELINE.md 1e8x64 bf16 single-chip config (pack-at-ingest)
        t = by["kmeans_lloyd_iter_bf16_northstar"]["wall_s"]
        out["kmeans_bf16_northstar_samples_per_s"] = round(
            config.NORTHSTAR_N / t, 1
        )
    if "lasso_sweep" in by:
        t = by["lasso_sweep"]["wall_s"]
        out["lasso_rows_per_s"] = round(config.LASSO_M / t, 1)
    if "resnet50_dp_step" in by:
        t = by["resnet50_dp_step"]["wall_s"]
        out["resnet50_img_per_s"] = round(config.RESNET_BATCH / t, 2)
        if config.RESNET_IMG == 224:
            out["resnet50_tflops"] = round(
                config.resnet50_step_flops(config.RESNET_BATCH) / t / 1e12, 3
            )
    if "resnet50_s2d_dp_step" in by:
        t = by["resnet50_s2d_dp_step"]["wall_s"]
        out["resnet50_s2d_img_per_s"] = round(config.RESNET_BATCH / t, 2)
    if "flash_attention_forward" in by:
        bh, s, d = config.ATTN_BH, config.ATTN_S, config.ATTN_D
        t = by["flash_attention_forward"]["wall_s"]
        out["attention_tflops"] = round(
            config.attention_flops(bh, s, d, causal=True) / t / 1e12, 3)
    if "moe_ffn_forward" in by:
        tkn, dm, h = config.MOE_T, config.MOE_D, config.MOE_H
        t = by["moe_ffn_forward"]["wall_s"]
        out["moe_tflops"] = round(
            config.moe_flops(tkn, dm, h, k=2) / t / 1e12, 3)
    return out


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None, help="write suite JSON to this path")
    ap.add_argument(
        "--prom",
        default=None,
        help="after the run, write telemetry.export_prometheus() (every "
             "fusion/transport/overlap counter as a gauge) to this path",
    )
    ap.add_argument(
        "--only",
        default=None,
        help="comma-separated subset: "
             "linalg,cluster,manipulations,nn,regression,fusion,kernels,"
             "serving,router,quantize,wire,sparse,stream",
    )
    ap.add_argument(
        "--check-regression",
        action="store_true",
        help="after the run, compare each row against the best checked-in "
             "BENCH_cb_r*.json value for this backend (per-row noise "
             "tolerance; see history.py), attach the delta table to the "
             "--out document, and exit nonzero on any out-of-tolerance row",
    )
    args = ap.parse_args()

    suites = {
        "linalg": linalg.run,
        "cluster": cluster.run,
        "fusion": fusion.run,
        "kernels": kernels.run,
        "manipulations": manipulations.run,
        "nn": nn.run,
        "quantize": quantize.run,
        "regression": regression.run,
        "router": router.run,
        "serving": serving.run,
        "sparse": sparse.run,
        "stream": stream.run,
        "wire": wire.run,
    }
    selected = (
        [s.strip() for s in args.only.split(",") if s.strip()]
        if args.only
        else list(suites)
    )
    unknown = [s for s in selected if s not in suites]
    if unknown:
        ap.error(f"unknown suite(s) {unknown}; valid: {sorted(suites)}")
    _compile_cache.enable()
    for name in selected:
        suites[name]()

    import jax

    dev = jax.devices()[0]
    doc = {
        "suite": "cb",
        "backend": dev.platform,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
        "measurements": _monitor.measurements(),
        "derived": derive(_monitor.measurements()),
    }
    if config.REHEARSAL:
        # HEAT_TPU_CB_REHEARSAL=cpu: CI sizes on a CPU mesh — the walls
        # below are control-flow evidence, never device times
        doc["rehearsal"] = (
            "cpu rehearsal at CI sizes: wall_s values are not device times"
        )
        doc["derived"] = {}
    regressions = []
    if args.check_regression:
        # attaches doc["regression"] (the per-row delta table) in place,
        # so the --out document carries the verdict it was judged by
        regressions = history.check(doc)
    print(json.dumps(doc))
    if args.out:
        with open(args.out, "w") as fh:
            json.dump(doc, fh, indent=1)
    if args.prom:
        with open(args.prom, "w") as fh:
            fh.write(_telemetry.export_prometheus())
    sys.exit(1 if regressions else 0)
