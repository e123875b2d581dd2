"""ResNet-50 DP roofline arithmetic.

Compiles the EXACT fused train step the cb suite times, pulls XLA's own
cost analysis from the compiled module (bytes accessed + flops), and
divides by the chip's HBM bandwidth to get the minimum possible ms/step
for this program.  If measured/roofline >= ~85%, the step is memory-bound
and its MFU is the architecture's number, not an implementation gap.

Also runs the batch-scaling sweep: throughput vs batch size on the chip.

A device measurement: needs a TPU whose device_kind is in the peak table
(heat_tpu/core/roofline.py); otherwise it raises.  Output:
chiprun_out/ROOFLINE_resnet.json (git-ignored) and stdout.

Reference workload: /root/reference/examples/nn/imagenet-DASO/
(BASELINE.md DP row).
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "cb"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp

RESNET50_GMACS_PER_IMG = 4.09  # fwd; train ~3x (fwd + 2x bwd)


def build_step(batch, img, dt):
    import optax

    import heat_tpu as ht

    rng = np.random.default_rng(1)
    Xh = rng.standard_normal((batch, img, img, 3)).astype(np.float32).astype(dt)
    yh = rng.integers(0, 1000, batch)
    model = ht.nn.DataParallel(
        ht.models.ResNet50(num_classes=1000, dtype=dt),
        optimizer=ht.optim.DataParallelOptimizer(optax.sgd(0.1)),
    )
    model.init(0, Xh[: min(batch, 8)])
    X = ht.array(Xh, split=0)
    y = ht.array(yh, split=0)
    return model, X, y


def cost_analysis(model, X, y):
    """XLA's own per-module cost analysis of the fused train step."""
    # one real step warms the cache and materializes model._train_step
    model.train_step(X, y)
    bv = X.larray
    tv = y.larray
    lowered = model._train_step.lower(
        model.variables, model.optimizer.state, bv, tv
    )
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    return {
        "bytes_accessed": float(ca.get("bytes accessed", 0.0)),
        "xla_flops": float(ca.get("flops", 0.0)),
        "transcendentals": float(ca.get("transcendentals", 0.0)),
    }


def chain_delta_ms(model, X, y):
    from heat_tpu.utils.bench import chain_slope

    def drain(v):
        return float(np.asarray(v))

    def run_k(k):
        loss = None
        for _ in range(k):
            loss = model.train_step(X, y)
        drain(loss)

    run_k(1)
    sl = chain_slope(run_k, min_delta=0.4, trials=3)
    return sl.per_unit_s * 1e3, sl


def main():
    from heat_tpu.core import roofline
    from heat_tpu.utils import compile_cache

    peaks = roofline.require_peaks()
    hbm_gbps, peak_bf16_tflops = peaks["hbm_gbps"], peaks["bf16_tflops"]
    compile_cache.enable()
    dt, img, flagship_batch = jnp.bfloat16, 224, 256

    out = {
        "hardware": peaks["device"],
        "device_count": len(jax.devices()),
        "hbm_gbps_spec": hbm_gbps,
        "peak_bf16_tflops_spec": peak_bf16_tflops,
        "image": img,
        "dtype": "bfloat16",
    }

    model, X, y = build_step(flagship_batch, img, dt)
    ca = cost_analysis(model, X, y)
    measured_ms, sl = chain_delta_ms(model, X, y)

    roofline_ms = ca["bytes_accessed"] / (hbm_gbps * 1e9) * 1e3
    # useful-work FLOPs (2-flops-per-MAC, fwd + 2x bwd) for the MFU column
    useful_tflops_step = 2 * RESNET50_GMACS_PER_IMG * 3 * flagship_batch / 1e3
    out["flagship"] = {
        "batch": flagship_batch,
        "xla_bytes_accessed_gb": round(ca["bytes_accessed"] / 1e9, 3),
        "xla_flops_tflop": round(ca["xla_flops"] / 1e12, 3),
        "roofline_min_ms_per_step": round(roofline_ms, 2),
        "measured_ms_per_step": round(measured_ms, 2),
        "roofline_fraction": round(roofline_ms / measured_ms, 3) if measured_ms else None,
        "useful_tflops_per_step_model": round(useful_tflops_step, 3),
        "mfu_measured": round(
            useful_tflops_step / (measured_ms / 1e3) / peak_bf16_tflops, 3
        ) if measured_ms else None,
        "mfu_at_roofline": round(
            useful_tflops_step / (roofline_ms / 1e3) / peak_bf16_tflops, 3
        ) if roofline_ms else None,
        "method": f"chain-delta k1={sl.k1} k2={sl.k2}",
    }
    del model, X, y

    # batch-scaling sweep: the last unexercised lever
    sweep = []
    for b in (128, 256, 384):
        try:
            m, Xb, yb = build_step(b, img, dt)
            ms, _sl = chain_delta_ms(m, Xb, yb)
            sweep.append(
                {
                    "batch": b,
                    "ms_per_step": round(ms, 2),
                    "img_per_s": round(b / (ms / 1e3), 1),
                }
            )
            del m, Xb, yb
        except Exception as e:  # OOM at large batch is a legitimate result
            sweep.append({"batch": b, "error": type(e).__name__})
    out["batch_sweep"] = sweep

    out_dir = os.path.join(os.path.dirname(__file__), "..", "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "ROOFLINE_resnet.json"), "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
